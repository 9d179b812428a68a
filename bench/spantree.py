"""The engine's span tree as the per-layer metrics read it.

A span is (name, start, dur, args) on the host clock (run.SpanLog). The
engine gives every span an integer args["id"] and the args["parent"] id of
the span it ran inside (None for a root `step` span, one per
ServeEngine.step call). A program that gives no ids gives no tree: `index`
is then empty and the metrics that read the tree report nothing.
"""

from __future__ import annotations

CALLS = ("prefill/", "decode/")      # the device calls with their syncs


def index(spans) -> dict:
    """The spans that carry an id, by id."""
    return {s[3]["id"]: s for s in spans if "id" in s[3]}


def root(tree: dict, span):
    """The root span above `span` (itself if it has no parent)."""
    while span[3]["parent"] is not None:
        span = tree[span[3]["parent"]]
    return span


def in_window(window, start: float, dur: float) -> bool:
    return window.t0 <= start and start + dur <= window.t_close
