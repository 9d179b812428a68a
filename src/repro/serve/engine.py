"""Batched serving engine: continuous batching with an on-device hot loop.

The engine owns a fixed decode batch of `slots`; requests queue, prefill
into a free slot's cache lane, and decode step-locked with the rest of the
batch. Two optimizations move the hot loop on-device (seed behavior is
preserved bit-for-bit in serve/reference.py as the oracle):

  * **Bucketed prefill** — prompts are right-padded to power-of-two length
    buckets, and queued requests of the same bucket batch into prefill
    calls of a fixed width per bucket: W(b) lanes, as many as ride free on
    the weight read (see PREFILL_ROWS), never more than `slots`. A larger
    group splits into several calls. The jit cache is therefore bounded by
    the number of buckets (<= log2(max_len) variants) instead of one entry
    per distinct prompt length. Padding is inert for attention-only
    caches: causal masking keeps padded positions out of real positions'
    math, and a post-prefill length fixup masks the padded cache slots
    until decode overwrites them. Stateful mixers (SSM, ring
    buffers) join the bucket path via masked state updates driven by the
    per-lane true lengths (dt-masked SSD recurrence, true-length conv
    window, per-lane ring slot gather — see Model.forward(true_lens=...)).
    Models whose prefill genuinely can't share a padded batch (MoE
    capacity displacement, encoder-decoder/VLM non-token inputs) fall
    back to exact-length prefill (see Model.bucketed_prefill_ok).

  * **Fused multi-token decode** — a `lax.scan` of up to `decode_chunk`
    decode steps runs in one device call, carrying tokens / positions /
    budgets / EOS-alive masks as device arrays. The host syncs once per
    chunk (the admission boundary), not once per token. Chunk lengths are
    floored to powers of two so the decode jit cache stays bounded by
    log2(decode_chunk) variants. When the queue is non-empty the chunk is
    sized to the soonest-finishing lane so freed slots admit promptly;
    when the queue is drained, to the latest-finishing lane.

SOSA tie-in (§6.1 multi-tenancy): co-scheduling independent request
streams is exactly the paper's multi-tenant utilization argument — decode
GEMVs from many requests fuse into one batched GEMM, raising tiles/pod
(and with Model(use_pallas=True) they literally execute as one fused-lane
pod GEMM, kernels/systolic_gemm). Pass
`tracer=tenancy.ServeTraceRecorder()` to record the engine's actual
prefill/decode timeline; events are emitted in the same step-locked order
as the seed engine (decode events are reconstructed per scan step from the
chunk's emit masks), so `tenancy/trace.py` lowers them unchanged. The same
tracer gets each step's span tree (step > admit, prefill and decode calls
with their dispatch and sync, host work between them), which also lands on
the host plane of any jax.profiler capture as `engine.*` annotations.

Overload & failure semantics (serve/admission.py, serve/chaos.py): every
submitted request reaches exactly one terminal state — ``done`` |
``rejected`` | ``expired`` — and malformed requests raise
`InvalidRequest` at submit. `admission=` selects the policy (fifo | edf |
slo-aware: deadline ordering, bounded-queue backpressure, wave-model
predictive shedding, overload budget degradation); deadline expiry runs
at the existing per-chunk host sync (zero new syncs). `chaos=` injects a
seeded fault schedule at the device-call boundary: transient faults
retry with exponential backoff (`max_retries`, `backoff_s`) before the
affected requests are rejected with their slots reclaimed, and an EWMA
slow-chunk detector (train/fault.py machinery) halves the next chunk
while the device is degraded. With the defaults (fifo, unbounded, no
chaos, no deadlines) the hot loop is bit-identical to the seed: same
tokens, same jit cache sizes, same host-sync count (gated in
tests/test_serving.py and tests/test_admission.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.systolic_gemm.guard import GuardTape, as_guard
from ..models.attention import KVCache, PagedKVCache, RingKVCache
from ..models.model import CrossKV, Model
from ..models.ssm import SSMCache
from ..models.transformer import MLACache
from ..train.fault import Ewma
from .admission import (AdmissionConfig, AdmissionController, InvalidRequest,
                        NEW, SLO_AWARE, ServeStalled, WaveLatencyPredictor)
from .chaos import (FaultInjector, NumericalFault, PermanentFault,
                    SilentCorruption, SlowChunkDetector,
                    TransientDeviceError, check_lanes_finite)
from .paging import PagePool

_OFF = contextlib.nullcontext()       # a span while no tracer takes spans

# Token rows a bucketed prefill call carries: a bucket-b call computes
# W(b) = min(slots, next_pow2(ceil(PREFILL_ROWS / b))) lanes. A bf16
# projection of M rows does 2MKN FLOPs on 2KN weight bytes, M FLOP a byte;
# a TPU v5e's ridge is 197 TFLOP/s over 819 GB/s, about 240 FLOP a byte.
# Below the ridge the weight read bounds the call and extra lanes ride
# free; above it each lane costs compute in proportion, so a call carries
# about the ridge's rows and no more. Empty lanes are pure padding.
PREFILL_ROWS = 256


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # [S] int32
    max_new_tokens: int = 16
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # extra prefill-batch arrays (batch-dim included), e.g. whisper frames
    # {"frames": [1, src_len, d_model]} — merged into the prefill batch;
    # requests with extras always prefill exact-length (per-request shapes
    # can't join a shared bucket batch)
    extras: dict = dataclasses.field(default_factory=dict)
    # QoS envelope (serve/admission.py): deadline is seconds from submit
    # on the engine's clock; priority breaks deadline ties (lower = more
    # urgent). state walks new -> queued -> running -> one terminal state
    # (done | rejected | expired); reason says why a request was shed.
    deadline_s: Optional[float] = None
    priority: int = 0
    state: str = NEW
    reason: str = ""
    # stamped by the admission controller
    _seq: int = dataclasses.field(default=0, repr=False)
    _submit_t: float = dataclasses.field(default=0.0, repr=False)
    _admit_t: float = dataclasses.field(default=0.0, repr=False)
    _deadline: Optional[float] = dataclasses.field(default=None, repr=False)
    # jit cache sizes (prefill + decode) at admit time: a retire whose
    # epoch grew saw compile time inside its service wall — its κ
    # calibration sample is skipped (cold-start κ pollution bugfix)
    _jit_epoch: int = dataclasses.field(default=-1, repr=False)

    @property
    def finished(self) -> bool:
        return self.state in ("done", "rejected", "expired")


class _OpenSpan:
    """A span of the engine's tree while it is open."""

    __slots__ = ("name", "cat", "id", "parent", "timed", "t_start", "t_end",
                 "args", "held")

    def __init__(self, name: str, cat: str, sid: int,
                 parent: Optional[int], timed: bool):
        self.name, self.cat, self.id, self.parent = name, cat, sid, parent
        self.timed = timed
        self.t_start = self.t_end = None
        self.args: dict = {}
        self.held: list[_OpenSpan] = []    # closed children, untimed only

    def done(self, t_start: float, t_end: float, **args) -> None:
        """Bounds and args of an untimed span (a device call): set only
        when the call succeeded, so a failed call leaves no span."""
        self.t_start, self.t_end = t_start, t_end
        self.args.update(args)


class _SpanTree:
    """The engine's spans while a tracer that takes spans (`on_span`) is
    attached. Each span gets an integer `id` and the `parent` id of the
    span open around it (None for a `step`), both passed to `on_span`
    with its args, and opens a jax.profiler.TraceAnnotation named
    `engine.<name>` (any `/<size>` suffix dropped), so that a profile
    holds the same tree on the device trace's clock. The clock reads of a
    span lie inside its annotation."""

    def __init__(self, tracer, clock, t0: float):
        self.tracer, self.clock, self.t0 = tracer, clock, t0
        self.stack: list[_OpenSpan] = []
        self.next_id = 0
        # running totals that a step span's args are the growth of
        self.prefills = 0
        self.decode_steps = 0

    @contextlib.contextmanager
    def span(self, name: str, cat: str, timed: bool):
        """A timed span reads the clock on entry and exit; an untimed one
        is emitted only if its body calls `done` with the bounds, and the
        spans inside it only with it."""
        sp = _OpenSpan(name, cat, self.next_id,
                       self.stack[-1].id if self.stack else None, timed)
        self.next_id += 1
        with jax.profiler.TraceAnnotation("engine." + name.split("/")[0]):
            if timed:
                sp.t_start = self.clock()
            self.stack.append(sp)
            try:
                yield sp
            finally:
                self.stack.pop()
                if timed:
                    sp.t_end = self.clock()
        if sp.t_end is not None:
            self._emit(sp)

    def _emit(self, sp: _OpenSpan) -> None:
        for outer in reversed(self.stack):
            if not outer.timed:
                outer.held.append(sp)
                return
        if sp.cat == "prefill":
            self.prefills += 1
        elif sp.cat == "decode":
            self.decode_steps += sp.args["steps"]
        self.tracer.on_span(sp.name, ts=sp.t_start - self.t0,
                            dur=sp.t_end - sp.t_start, cat=sp.cat,
                            id=sp.id, parent=sp.parent, **sp.args)
        for child in sp.held:
            self._emit(child)


class ServeEngine:
    def __init__(self, model: Model, params, slots: int = 4,
                 max_len: int = 512, src_len: int = 0,
                 eos_id: Optional[int] = None, tracer=None,
                 decode_chunk: int = 8, prefill_buckets: bool = True,
                 min_bucket: int = 8, metrics=None, admission=None,
                 chaos=None, clock=None, max_retries: int = 3,
                 backoff_s: float = 1e-3, guard=None, paged: bool = False,
                 page_size: int = 16, kv_pages: Optional[int] = None,
                 recycle: Optional[bool] = None):
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.src_len = src_len
        self.eos_id = eos_id
        # optional duck-typed event sink (tenancy.ServeTraceRecorder): gets
        # on_prefill(rid, prompt_len) / on_decode(lanes, contexts) in the
        # engine's step-locked order, and (if it defines on_span) the span
        # tree of every step for the Perfetto export (obs/export.py):
        # step > admit, decode.prep, decode/chunk{n}, decode.retire; the
        # device calls prefill/* (category "prefill") and decode/*
        # ("decode") with their dispatch and sync, each with the
        # `state_bytes` of recurrent state its lanes hold, a prefill also
        # with the `width` in lanes it computed; the rest category
        # "engine". Without such a tracer no span is made and no
        # annotation opened.
        self.tracer = tracer
        # optional obs.metrics.MetricsRegistry. Recording is host-side
        # bookkeeping on values the engine already has at each chunk
        # boundary: metrics-on adds no host syncs and no jit cache entries
        # (the device-side accumulators below run unconditionally), gated
        # by tests/test_serving.py.
        self.metrics = metrics
        self.decode_chunk = max(1, decode_chunk)
        self.min_bucket = max(1, min_bucket)
        self.bucketed = bool(prefill_buckets) and model.bucketed_prefill_ok
        # paged=True swaps every global-attention KVCache leaf for a
        # PagedKVCache over a shared kv_pages-page pool; serve/paging.py
        # owns the host-side allocator, riding the existing one-sync-per-
        # chunk boundary. paged=False keeps the hot loop bit-identical to
        # the dense engine (same arrays, same jit entries, same syncs).
        self._pool: Optional[PagePool] = None
        if paged:
            if not self.bucketed:
                raise ValueError(
                    "paged serving requires the bucketed prefill path "
                    "(dense/ssm/hybrid families with prefill_buckets=True)")
            if kv_pages is None:
                # default pool covers the dense worst case exactly; size
                # it down to oversubscribe (admission then queues on pages)
                kv_pages = slots * (max_len // page_size)
            self._pool = PagePool(kv_pages, page_size, slots, max_len,
                                  chunk_slack=self.decode_chunk)
            self.cache = model.init_cache(slots, max_len, src_len=src_len,
                                          page_size=page_size,
                                          kv_pages=kv_pages)
        else:
            self.cache = model.init_cache(slots, max_len, src_len=src_len)
        # bytes of one lane's recurrent state (SSM conv window and SSD
        # state, every layer): what a prefill writes and a decode step
        # reads and writes per lane; 0 for families without such state
        self.lane_state_bytes = sum(
            leaf.lane_bytes() for leaf in jax.tree.leaves(
                self.cache, is_leaf=lambda x: isinstance(x, SSMCache))
            if isinstance(leaf, SSMCache))
        if metrics is not None:
            metrics.counter("serve.state_bytes", kind="ssm").inc(
                slots * self.lane_state_bytes)
        # in-chunk lane recycling: after the retires of a decode chunk,
        # re-run admission at the SAME host sync so a lane that died
        # mid-chunk hands its slot (and pages) to a queued request with no
        # intervening idle chunk. Default: on exactly when paged (the
        # extra admission pass changes chunk-length choices, which the
        # paged-off bit-identity gate forbids).
        self.recycle = bool(paged) if recycle is None else bool(recycle)
        self.recycled = 0
        self.active: list[Optional[Request]] = [None] * slots
        self.positions = np.zeros(slots, np.int32)
        self.budgets = np.zeros(slots, np.int32)
        self.queue: list[Request] = []
        self._buckets_seen: set[int] = set()
        self._batch_axes = self._probe_batch_axes()
        self._prefill_fn = jax.jit(self._prefill_paged_impl if paged
                                   else self._prefill_batched_impl)
        self._decode_fn = jax.jit(self._decode_chunk_impl,
                                  static_argnames=("n",))
        # injectable clock (serve/chaos.VirtualClock in tests/benchmarks);
        # everything time-dependent — spans, deadlines, backoff, EWMAs —
        # reads it, so failure scenarios replay deterministically
        self._clock = clock if clock is not None else time.perf_counter
        # admission policy: None/str/AdmissionConfig -> controller. The
        # default AdmissionConfig() is the seed engine exactly (fifo,
        # unbounded queue, no deadlines => no controller interference).
        if admission is None:
            admission = AdmissionConfig()
        elif isinstance(admission, str):
            admission = AdmissionConfig(policy=admission)
        predictor = None
        if isinstance(admission, AdmissionConfig):
            if admission.policy == SLO_AWARE:
                predictor = WaveLatencyPredictor(
                    model.cfg, admission.design, admission.tdp,
                    faulty_pods=admission.faulty_pods)
            admission = AdmissionController(
                admission, slots=slots, max_len=max_len,
                predictor=predictor, metrics=metrics)
        self.admission: AdmissionController = admission
        if self._pool is not None:
            # paged admission: free pages, not free slots, are the gating
            # resource — the controller rejects can-never-fit requests at
            # submit and (slo-aware) sheds on predicted page exhaustion
            self.admission.attach_pool(self._pool)
        # chaos: a ChaosConfig arms the seeded fault injector plus the
        # EWMA slow-chunk detector; None (default) leaves the hot loop
        # untouched (no per-call hooks at all)
        if chaos is not None and not isinstance(chaos, FaultInjector):
            chaos = FaultInjector(chaos, clock=clock)
        self._chaos: Optional[FaultInjector] = chaos
        self._slow_detect = SlowChunkDetector() if chaos is not None \
            else None
        # SDC guard (kernels/systolic_gemm/guard.py): None/"off" keeps the
        # hot loop bit-identical to an unguarded build; "probe"/"abft"
        # wrap the jitted bucketed-prefill and fused-decode impls in a
        # GuardTape so every pod GEMM is verified (and, under abft,
        # single corruptions repaired in-graph). The exact-length prefill
        # fallback stays outside the guard envelope (its model.prefill
        # jit cache would skip the tape's trace-time hooks on a hit).
        self._guard = as_guard(guard)
        self._guard_on = self._guard.mode != "off"
        self._sdc_plan = None         # armed per attempt by _device_call
        self._sdc_magnitude = (self._chaos.config.sdc_magnitude
                               if self._chaos is not None else 1e4)
        # host-side guard tallies (mirrored to metrics when enabled)
        self.guard_events = {"corrected": 0, "uncorrectable": 0,
                             "non_finite": 0}
        self._chunk_cap: Optional[int] = None
        self.max_retries = max(0, int(max_retries))
        self.backoff_s = float(backoff_s)
        # measured decode seconds/token (host floats, always cheap): the
        # deadline-aware chunk capping below sizes chunks with it
        self._sec_per_tok = Ewma(alpha=0.3)
        self._t0 = self._clock()
        self._tree = (_SpanTree(tracer, self._clock, self._t0)
                      if hasattr(tracer, "on_span") else None)

    # -- fault boundary -------------------------------------------------
    def _sleep(self, seconds: float) -> None:
        if seconds <= 0:
            return
        if hasattr(self._clock, "sleep"):
            self._clock.sleep(seconds)        # virtual time: no blocking
        else:
            time.sleep(seconds)

    def _device_call(self, kind: str, fn):
        """Run one device call through the fault boundary: the chaos
        injector may stall or raise per its seeded schedule; transient
        errors retry with exponential backoff up to `max_retries`, then
        escalate to PermanentFault. A guard-enabled `fn` additionally
        syncs its verdict flags and raises SilentCorruption on detected-
        but-uncorrected output — retried identically (recompute usually
        clears a transient flip; the injector replays a corrupt site for
        `transient_tries` attempts before it heals), but exhaustion
        re-raises SilentCorruption so the caller finalizes the lanes as
        ``sdc-uncorrectable`` instead of ``device-fault``. Results are
        returned (never assigned to engine state here), so a failed call
        leaves cache/lanes exactly as they were. With chaos disarmed and
        guard off this is a plain call."""
        if self._chaos is None and not self._guard_on:
            return fn()
        attempt = 0
        while True:
            try:
                if self._chaos is not None:
                    self._chaos.before(kind)
                    self._sdc_plan = (self._chaos.sdc_plan(kind)
                                      if self._guard_on else None)
                return fn()
            except (TransientDeviceError, SilentCorruption) as err:
                attempt += 1
                if self.metrics is not None:
                    self.metrics.counter("serve.chaos.retries",
                                         kind=kind).inc()
                if attempt > self.max_retries:
                    if isinstance(err, SilentCorruption):
                        raise
                    raise PermanentFault(
                        f"{kind} device call failed after {attempt} "
                        f"attempts: {err}") from err
                self._sleep(self.backoff_s * (2 ** (attempt - 1)))

    def _reject_group(self, reqs: list, reason: str) -> None:
        for r in reqs:
            self.admission.reject(r, reason)
        if self.metrics is not None:
            name = ("serve.chaos.sdc_uncorrectable"
                    if reason == "sdc-uncorrectable"
                    else "serve.chaos.permanent_faults")
            self.metrics.counter(name).inc()

    def _sdc_arr(self):
        """The attempt's injection plan as the traced int32[3] the guarded
        impls consume; (-1, 0, 0) disarms (no chaos / clean draw)."""
        plan = self._sdc_plan if self._sdc_plan is not None else (-1, 0, 0)
        return jnp.asarray(plan, jnp.int32)

    def _note_guard(self, corrected: int) -> None:
        if corrected > 0:
            self.guard_events["corrected"] += int(corrected)
            if self.metrics is not None:
                self.metrics.counter("serve.guard.corrected").inc(
                    int(corrected))

    def _shed_non_finite(self, pairs: list, where: str) -> None:
        """Finalize lanes whose logits went NaN/Inf: the typed
        NumericalFault is raised (check_lanes_finite) and caught at this
        boundary — recompute would return the same poison, so there is no
        retry; each affected request ends ``rejected`` with terminal
        reason ``non-finite-logits`` and everyone else keeps serving."""
        try:
            check_lanes_finite([(lane, True) for _, lane in pairs], where)
        except NumericalFault as err:
            for (r, _), lane in zip(pairs, err.lanes):
                self.admission.reject(r, "non-finite-logits")
            self.guard_events["non_finite"] += len(pairs)
            if self.metrics is not None:
                self.metrics.counter("serve.numerical_faults",
                                     where=where).inc(len(pairs))

    # -- telemetry ------------------------------------------------------
    def _span(self, name: str, cat: str = "engine", timed: bool = True):
        """A span of the engine's tree (see _SpanTree), or a shared null
        context while no tracer takes spans."""
        if self._tree is None:
            return _OFF
        return self._tree.span(name, cat, timed)

    def _compiled(self, fn: str, grew: bool) -> bool:
        """Pass on whether a device call compiled (its jit cache grew),
        counting it as serve.compiles{fn=prefill|decode}."""
        if grew and self.metrics is not None:
            self.metrics.counter("serve.compiles", fn=fn).inc()
        return grew

    def _observe_prefill(self, path: str, tokens: int, lanes: int,
                         width: int, seconds: float) -> None:
        m = self.metrics
        if m is None:
            return
        m.counter("serve.prefill.calls", path=path).inc()
        m.counter("serve.prefill.tokens").inc(tokens)
        m.counter("serve.prefill.pad_lanes").inc(width - lanes)
        m.counter("serve.prefill.seconds").inc(seconds)
        m.histogram("serve.prefill.us").record(seconds * 1e6)
        m.gauge("serve.prefill.lanes").set(lanes)
        m.gauge("serve.queue_depth").set(len(self.queue))

    def _observe_decode(self, n: int, lanes: int, emitted: int,
                        live_end: int, seconds: float) -> None:
        m = self.metrics
        if m is None:
            return
        m.counter("serve.decode.chunks").inc()
        m.counter("serve.decode.tokens").inc(emitted)
        m.counter("serve.decode.seconds").inc(seconds)
        m.histogram("serve.decode.chunk_len").record(n)
        m.gauge("serve.slot_occupancy").set(lanes / self.slots)
        m.gauge("serve.decode.live_lanes_end").set(live_end)
        m.gauge("serve.queue_depth").set(len(self.queue))

    # -- request flow --------------------------------------------------
    def submit(self, req: Request) -> None:
        """Validate + enqueue. Raises InvalidRequest (typed, names the
        offending field) for malformed requests; under a bounded queue the
        admission policy may shed (request ends ``rejected``, reason
        ``queue-full`` / ``shed-predicted-miss``) instead of enqueueing."""
        if self._pool is not None and req.extras:
            raise InvalidRequest(
                "extras", "paged serving cannot prefill per-request extra "
                "modalities (exact-length fallback is dense-only)")
        if self.admission.on_submit(self.queue, req, self._clock()):
            self.queue.append(req)
        if self.metrics is not None:
            self.metrics.gauge("serve.queue_depth").set(len(self.queue))

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.active) if r is None]

    def _bucket(self, prompt_len: int) -> int:
        b = max(self.min_bucket, prompt_len)
        b = 1 << (b - 1).bit_length()                # next power of two
        return min(b, self.max_len)

    def _width(self, bucket: int) -> int:
        """Lanes of a bucket-`bucket` prefill call (see PREFILL_ROWS): one
        width per bucket, so still one program per bucket."""
        w = -(-PREFILL_ROWS // bucket)
        return min(self.slots, 1 << (w - 1).bit_length())

    def _admit(self) -> None:
        with self._span("admit"):
            # queue sweep first: expire queued-past-deadline, shed predicted
            # misses (slo-aware), and order the queue per policy. Pure host
            # work; a fifo queue with no deadlines passes through untouched.
            self.admission.sweep(self.queue, self._clock())
            while self.queue:
                free = self._free_slots()
                if not free:
                    return
                if not self.bucketed or self.queue[0].extras:
                    # extras carry per-request shapes (e.g. frames) that can't
                    # join a shared bucket batch: prefill them exact-length
                    self._prefill_into(free[0], self.queue.pop(0))
                    continue
                # group the head-of-queue bucket: every queued request of the
                # same bucket rides the same prefill call (up to free slots)
                b = self._bucket(len(self.queue[0].prompt))
                take: list[Request] = []
                rest: list[Request] = []
                for r in self.queue:
                    if len(take) < len(free) and not r.extras and \
                            self._bucket(len(r.prompt)) == b:
                        if self._pool is not None:
                            # paged admission: a lane starts only if its
                            # worst-case page count (prompt + clamped budget +
                            # one chunk of inert-write slack) reserves now —
                            # the per-chunk mapping then can never fail.
                            # Requests that don't fit wait queued for pages.
                            worst = self._pool.worst_pages(
                                len(r.prompt), self._clamped_budget(r))
                            if not self._pool.can_reserve(worst):
                                rest.append(r)
                                continue
                            self._pool.reserve(free[len(take)], worst)
                        take.append(r)
                    else:
                        rest.append(r)
                self.queue = rest
                if not take:
                    # head bucket blocked on pages this quantum; retires at
                    # the next chunk sync will free some
                    return
                # one call per W(b) of the group, each with its own fault
                # handling: a failed call sheds only its own requests
                w = self._width(b)
                for i in range(0, len(take), w):
                    self._prefill_group(take[i: i + w], free[i: i + w], b)

    # -- bucketed prefill ------------------------------------------------
    def _probe_batch_axes(self):
        """Per-leaf batch axis of the cache pytree, found by diffing the
        shapes of a 2-lane cache against a 1-lane cache (static metadata;
        makes lane insertion exact instead of shape-guessed). Probed from
        abstract trees (jax.eval_shape: nothing lands on the device),
        never self.cache: the batch axis doesn't depend on the engine's
        slot count, and a slots==1 engine has no size difference of its
        own to diff (assuming axis 0 there scattered stacked-layer
        leaves — length [L, B], k [L, B, T, H, D] — along the LAYER axis,
        silently zeroing every layer past the first)."""
        # always probed from DENSE trees: the paged prefill runs its
        # forward over a dense transient lane cache, so the axes tree must
        # mirror that structure (the pool-shaped leaves never need axes)
        def shapes(batch):
            return jax.eval_shape(lambda: self.model.init_cache(
                batch, self.max_len, src_len=self.src_len))
        big, ref1 = shapes(2), shapes(1)

        def axis(b, small):
            for ax in range(b.ndim):
                if b.shape[ax] != small.shape[ax]:
                    return ax
            return 0
        return jax.tree.map(axis, big, ref1)

    def _prefill_group(self, reqs: list[Request], slot_list: list[int],
                       bucket: int) -> None:
        width = self._width(bucket)
        with self._span("prefill.pack"):
            toks = np.zeros((width, bucket), np.int32)
            true_lens = np.ones(width, np.int32)       # pad lanes: len 1
            slot_ids = np.full(width, -1, np.int32)
            for g, (r, s) in enumerate(zip(reqs, slot_list)):
                S = len(r.prompt)
                toks[g, :S] = r.prompt
                true_lens[g] = S
                slot_ids[g] = s
            args = [jnp.asarray(toks), jnp.asarray(slot_ids),
                    jnp.asarray(true_lens)]
            if self._pool is not None:
                # map each lane's prompt pages, then hand the impl a LANE-
                # indexed destination table (row g = lane g's pages,
                # sentinel-padded) for the page-granular scatter. The
                # slot-indexed device page_table is pushed separately
                # before the next decode chunk (step() checks pool.dirty).
                dest = np.full((width, self._pool.pages_per_lane),
                               self._pool.sentinel, np.int32)
                for g, (r, s) in enumerate(zip(reqs, slot_list)):
                    self._pool.map_to(s, len(r.prompt))
                    own = self._pool.owned(s)
                    dest[g, :len(own)] = own
                args.append(jnp.asarray(dest))
        self._buckets_seen.add(bucket)
        jit_before = self._prefill_fn._cache_size()
        with self._span(f"prefill/bucket{bucket}", "prefill",
                        timed=False) as sp:
            t_start = self._clock()
            try:
                with self._span("prefill.dispatch"):
                    first, cache = self._launch_prefill(args)
            except PermanentFault:
                # the whole group failed before any state was assigned:
                # shed the requests (terminal `rejected`), slots stay free
                # and their page reservations return to the pool
                self._reject_group(reqs, "device-fault")
                self._release_group(slot_list, len(reqs))
                return
            except SilentCorruption:
                self.guard_events["uncorrectable"] += 1
                self._reject_group(reqs, "sdc-uncorrectable")
                self._release_group(slot_list, len(reqs))
                return
            with self._span("prefill.sync"):
                self.cache = cache
                first = np.asarray(first)
            t_end = self._clock()
            n_tokens = int(sum(len(r.prompt) for r in reqs))
            compiled = self._compiled(
                "prefill", self._prefill_fn._cache_size() > jit_before)
            if sp is not None:
                # the group's own lanes are activated only below
                sp.done(t_start, t_end, bucket=bucket, lanes=len(reqs),
                        width=width, tokens=n_tokens,
                        rids=[r.rid for r in reqs],
                        stalled_lanes=sum(r is not None
                                          for r in self.active),
                        compiled=compiled,
                        state_bytes=len(reqs) * self.lane_state_bytes)
        if self.tracer is not None:
            for r in reqs:       # successful work only enters the trace
                self.tracer.on_prefill(r.rid, len(r.prompt),
                                       t=t_start - self._t0)
        self._observe_prefill("bucketed", n_tokens, len(reqs), width,
                              t_end - t_start)
        # a lane whose prefill logits were non-finite is encoded as a -1
        # first token (impl below) — shed it before the slot is activated
        poisoned = [(r, s) for g, (r, s) in enumerate(zip(reqs, slot_list))
                    if first[g] < 0]
        if poisoned:
            self._shed_non_finite(poisoned, where="prefill")
            if self._pool is not None:
                for _, s in poisoned:    # slot never activated: free pages
                    self._pool.release(s, now=self._clock())
        for g, (r, s) in enumerate(zip(reqs, slot_list)):
            if first[g] < 0:
                continue
            r.out.append(int(first[g]))
            self.active[s] = r
            self.positions[s] = len(r.prompt)
            self.budgets[s] = self.admission.clamp_budget(
                r, self._clamped_budget(r), len(self.queue))
            self.admission.note_admitted(r, t_end)
            r._jit_epoch = self._jit_sizes()
            self._retire_if_full(s)

    def _launch_prefill(self, args: list) -> tuple:
        """The jitted bucketed prefill through the fault boundary: its
        first tokens (on the device) and the new cache. Raises what
        _device_call raises."""
        if not self._guard_on:
            return self._device_call(
                "prefill", lambda: self._prefill_fn(
                    self.params, args[0], self.cache, *args[1:]))

        def call():
            first, cache, gstats = self._prefill_fn(
                self.params, args[0], self.cache, *args[1:],
                self._sdc_arr())
            flags = np.asarray(gstats)
            if int(flags[1]) > 0:
                raise SilentCorruption(
                    f"prefill: {int(flags[1])} uncorrected "
                    f"corruption(s) detected")
            return first, cache, int(flags[0])
        first, cache, corrected = self._device_call("prefill", call)
        self._note_guard(corrected)
        return first, cache

    def _prefill_forward(self, params, tokens, true_lens, sdc):
        """Shared body of both prefill impls: forward over a dense
        transient lane cache, per-lane last-real-position logits, length
        fixup. A lane with non-finite last-position logits encodes its
        first token as -1 — same arrays, same syncs as the healthy path.
        With the guard on, the forward runs under a GuardTape (every pod
        GEMM verified; `sdc` is the traced injection plan) and the tape
        totals become an extra output riding the existing sync."""
        lane_cache = self.model.init_cache(tokens.shape[0], self.max_len,
                                           src_len=self.src_len)
        # true_lens drives the stateful families' masked state updates
        # (SSM dt-masking + conv window, ring slot gather); attention-only
        # caches ignore it and rely on the _fix_lengths fixup below
        if self._guard_on:
            with GuardTape(self._guard, inject=sdc,
                           magnitude=self._sdc_magnitude) as tape:
                logits, lane_cache = self.model.forward(
                    params, {"tokens": tokens}, cache=lane_cache,
                    true_lens=true_lens)
            gstats = jnp.stack(tape.totals())
        else:
            logits, lane_cache = self.model.forward(params, {"tokens": tokens},
                                                    cache=lane_cache,
                                                    true_lens=true_lens)
            gstats = None
        idx = jnp.maximum(true_lens - 1, 0)
        last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
        first_tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        first_tok = jnp.where(jnp.isfinite(last).all(axis=-1), first_tok,
                              jnp.int32(-1))
        return first_tok, _fix_lengths(lane_cache, true_lens), gstats

    def _prefill_batched_impl(self, params, tokens, big_cache, slot_ids,
                              true_lens, sdc=None):
        """One jitted prefill over a [W(bucket), bucket] token batch:
        forward (see _prefill_forward) then scatter of each real lane into
        its slot of the batched cache. Every size comes from the tokens'
        shape, which the bucket fixes, so it compiles once per bucket."""
        first_tok, lane_cache, gstats = self._prefill_forward(
            params, tokens, true_lens, sdc)
        cache = big_cache
        for g in range(tokens.shape[0]):              # static unroll
            valid = slot_ids[g] >= 0
            slot = jnp.maximum(slot_ids[g], 0)
            cache = jax.tree.map(
                lambda big, lane, ax, v=valid, s=slot, g=g: jnp.where(
                    v,
                    jax.lax.dynamic_update_slice_in_dim(
                        big,
                        jax.lax.dynamic_slice_in_dim(lane, g, 1, axis=ax
                                                     ).astype(big.dtype),
                        s, axis=ax),
                    big),
                cache, lane_cache, self._batch_axes)
        if self._guard_on:
            return first_tok, cache, gstats
        return first_tok, cache

    def _prefill_paged_impl(self, params, tokens, big_cache, slot_ids,
                            true_lens, dest_pages, sdc=None):
        """Paged twin of _prefill_batched_impl: the identical forward over
        a dense transient lane cache, then a page-granular scatter of the
        attention KV into the pool (dest_pages: lane-indexed page rows the
        host allocator chose, sentinel-padded) while lane-resident state
        (SSM, ring windows) takes the same per-slot dense scatter as the
        dense impl. Still compiles once per bucket."""
        first_tok, lane_cache, gstats = self._prefill_forward(
            params, tokens, true_lens, sdc)
        cache = _paged_insert(big_cache, lane_cache, self._batch_axes,
                              slot_ids, true_lens, dest_pages)
        if self._guard_on:
            return first_tok, cache, gstats
        return first_tok, cache

    # -- exact-length prefill (SSM / ring / cross / MoE families) --------
    def _prefill_into(self, slot: int, req: Request) -> None:
        """Prefill a single request into one slot lane of the batched
        cache. The lane cache is built with the engine's src_len so
        encoder-decoder cross-KV lanes line up with the batched cache
        (regression: the seed dropped src_len here)."""
        S = len(req.prompt)
        new_len = S not in self._buckets_seen   # one shape per length
        self._buckets_seen.add(S)
        with self._span(f"prefill/exact{S}", "prefill", timed=False) as sp:
            t_start = self._clock()
            with self._span("prefill.pack"):
                lane_cache = self.model.init_cache(1, self.max_len,
                                                   src_len=self.src_len)
                batch = {"tokens": jnp.asarray(req.prompt)[None, :]}
                for key, val in req.extras.items():
                    batch[key] = jnp.asarray(val)
            try:
                with self._span("prefill.dispatch"):
                    logits, lane_cache = self._device_call(
                        "prefill", lambda: self.model.prefill(
                            self.params, batch, lane_cache))
            except PermanentFault:
                self._reject_group([req], "device-fault")
                return
            # fold the finiteness check into the one value already synced:
            # a poisoned lane yields -1 and is shed before slot activation
            with self._span("prefill.sync"):
                first = jnp.argmax(logits[0]).astype(jnp.int32)
                first = int(jnp.where(jnp.isfinite(logits[0]).all(), first,
                                      -1))
            if first >= 0:
                self.cache = _write_lane(self.cache, lane_cache, slot)
                req.out.append(first)
            t_end = self._clock()
            compiled = self._compiled("prefill", new_len)
            if sp is not None:
                sp.done(t_start, t_end, bucket=S, lanes=1, width=1,
                        tokens=S, rids=[req.rid],
                        stalled_lanes=sum(r is not None
                                          for r in self.active),
                        compiled=compiled,
                        state_bytes=self.lane_state_bytes)
        if first < 0:
            self._shed_non_finite([(req, slot)], where="prefill")
            return
        if self.tracer is not None:
            self.tracer.on_prefill(req.rid, S, t=t_start - self._t0)
        self._observe_prefill("exact", S, 1, 1, t_end - t_start)
        self.active[slot] = req
        self.positions[slot] = S
        self.budgets[slot] = self.admission.clamp_budget(
            req, self._clamped_budget(req), len(self.queue))
        self.admission.note_admitted(req, t_end)
        req._jit_epoch = self._jit_sizes()
        self._retire_if_full(slot)

    def _clamped_budget(self, req: Request) -> int:
        """Decode steps this request may take: its budget, clamped so the
        lane never appends past max_len (an oversized request degrades to
        a shorter completion instead of silently rewriting its last KV
        slot)."""
        return min(req.max_new_tokens - 1,
                   max(0, self.max_len - len(req.prompt)))

    def _retire_if_full(self, slot: int) -> None:
        """A prompt that fills the cache leaves no room for even the one
        forced decode step of a budget-0 lane — retire it with just the
        prefill token instead of letting the append clobber the last KV
        slot."""
        if self.positions[slot] >= self.max_len:
            self.admission.finish(self.active[slot], now=self._clock())
            self._release_slot(slot)

    def _release_slot(self, i: int) -> None:
        """Clear a lane AND return its pages — the single retirement path
        for every way a lane can die (finish, expiry, shed, device fault),
        so chaos can never leak pages."""
        if self._pool is not None:
            self._pool.release(i, now=self._clock())
        self.active[i] = None

    def _release_group(self, slot_list: list[int], n: int) -> None:
        if self._pool is not None:
            for s in slot_list[:n]:
                self._pool.release(s, now=self._clock())

    def _jit_sizes(self) -> int:
        """Combined prefill+decode jit cache entry count — the jit-epoch
        stamp for the cold-start κ fix (a service interval that saw ANY
        compile, its own or a co-resident lane's, is not a clean sample)."""
        total = 0
        for fn in (self._prefill_fn, self._decode_fn):
            try:
                total += int(fn._cache_size())
            except AttributeError:                    # pragma: no cover
                return -2     # can't tell -> epochs never match, skip all
        return total

    # -- fused decode loop ------------------------------------------------
    def _decode_chunk_impl(self, params, cache, toks, pos, bud, alive,
                           sdc=None, *, n: int):
        """n fused decode steps as one lax.scan on device. Carries the
        batched cache + per-lane (token, position, budget, alive) vectors;
        emits the per-step greedy tokens and emit masks, plus the chunk's
        telemetry accumulators (emitted-token total and live-lane count at
        chunk end) carried on device and drained with the chunk's one host
        sync — metrics read them for free, so metrics-on adds no syncs.
        A lane whose budget runs out (or that hits eos) drops out of the
        emit mask but keeps decoding inertly until the chunk ends — its
        slot is freed at the next admission boundary and prefill fully
        rewrites the lane.

        Always-on numerical guard: a lane whose logits go NaN/Inf stops
        emitting at that step and sets its flag in the stats vector (the
        flags ride the existing stats sync — zero new syncs; a healthy
        lane's tokens are untouched). With the PodGuard on, each scan
        step's model call runs under a GuardTape — the scan body traces
        once, so an armed `sdc` plan corrupts its target GEMM every step
        of the chunk — and the (corrected, uncorrected) totals join the
        stats vector."""
        eos = self.eos_id
        guard_on = self._guard_on

        def step(carry, _):
            cache, toks, pos, bud, alive, emitted, bad, gcorr, gunc = carry
            if guard_on:
                with GuardTape(self._guard, inject=sdc,
                               magnitude=self._sdc_magnitude) as tape:
                    logits, cache = self.model.decode_step(params, toks,
                                                           cache, pos)
                corr, unc = tape.totals()
                gcorr, gunc = gcorr + corr, gunc + unc
            else:
                logits, cache = self.model.decode_step(params, toks, cache,
                                                       pos)
            ok = jnp.isfinite(logits).all(axis=-1)
            bad = bad | (alive & ~ok)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            emit = alive & ok
            toks = jnp.where(emit, nxt, toks)
            bud = bud - emit.astype(bud.dtype)
            done = bud <= 0
            if eos is not None:
                done = done | (nxt == eos)
            alive = alive & ~done & ok
            pos = pos + 1
            emitted = emitted + emit.sum(dtype=jnp.int32)
            return (cache, toks, pos, bud, alive, emitted, bad,
                    gcorr, gunc), (toks, emit)

        carry0 = (cache, toks, pos, bud, alive, jnp.int32(0),
                  jnp.zeros(self.slots, bool), jnp.int32(0), jnp.int32(0))
        (cache, _, _, _, alive, emitted, bad, gcorr, gunc), (seq, emits) = \
            jax.lax.scan(step, carry0, None, length=n)
        parts = [jnp.stack([emitted, alive.sum(dtype=jnp.int32)]),
                 bad.astype(jnp.int32)]
        if guard_on:
            parts.append(jnp.stack([gcorr, gunc]))
        stats = jnp.concatenate(parts)
        return cache, seq, emits, stats

    def _chunk_len(self, live: list[int]) -> int:
        # queue waiting -> sync at the soonest lane completion (admit
        # early); queue drained -> run to the latest lane (fewest syncs)
        rem = [max(1, int(self.budgets[i])) for i in live]
        need = min(rem) if self.queue else max(rem)
        room = min(int(self.max_len - self.positions[i]) for i in live)
        n = max(1, min(self.decode_chunk, need, max(1, room)))
        if self._chunk_cap is not None:
            # slow-chunk mitigation (chaos armed + detector flagged):
            # shorter chunks while the device is degraded, so deadline
            # checks and admission come around sooner
            n = min(n, self._chunk_cap)
        deadlines = [self.active[i]._deadline for i in live
                     if self.active[i]._deadline is not None]
        spt = self._sec_per_tok.value
        if deadlines and spt is not None and spt > 0:
            # deadline-aware sizing: don't run a chunk so long the
            # earliest-deadline lane blows through its deadline between
            # host syncs. Only lanes with deadlines trigger this — the
            # bare fifo path is untouched (same chunk sizes as the seed).
            slack = min(deadlines) - self._clock()
            if slack <= 0:
                n = 1                 # sync asap; expiry reclaims the lane
            else:
                n = max(1, min(n, int(slack / spt)))
        # pow2 floor: <= log2(decode_chunk)+1 compiled chunk variants
        return 1 << (n.bit_length() - 1)

    def step(self) -> int:
        """One scheduling quantum: admission, then one fused decode chunk.
        Returns the number of lanes live at the chunk start. With a tracer
        that takes spans, the quantum is a root `step` span whose args
        count its prefill calls and decode steps."""
        tree = self._tree
        if tree is None:
            return self._step()
        prefills, decode_steps = tree.prefills, tree.decode_steps
        with tree.span("step", "engine", timed=True) as sp:
            live = self._step()
            sp.args.update(prefills=tree.prefills - prefills,
                           decode_steps=tree.decode_steps - decode_steps)
        return live

    def _step(self) -> int:
        self._admit()
        live = [i for i, r in enumerate(self.active) if r is not None]
        if not live:
            return 0
        with self._span("decode.prep"):
            n = self._chunk_len(live)
            if self._pool is not None:
                # map pages to cover this chunk's appends (live lanes reach
                # pos+n; a lane that dies mid-chunk writes inertly inside
                # the same bound — covered by its reservation's chunk
                # slack), then push the refreshed slot-indexed table if
                # anything changed. Host-side work + one async host->device
                # transfer: no syncs.
                for i in live:
                    self._pool.map_to(i, int(self.positions[i]) + n)
                if self._pool.dirty:
                    self.cache = self._with_table(self.cache)
            toks = np.zeros(self.slots, np.int32)
            alive0 = np.zeros(self.slots, bool)
            for i in live:
                toks[i] = self.active[i].out[-1]
                alive0[i] = True
            pos0 = self.positions.copy()
        jit_before = self._decode_fn._cache_size()
        with self._span(f"decode/chunk{n}", "decode", timed=False) as sp:
            t_start = self._clock()
            try:
                with self._span("decode.dispatch"):
                    cache, seq, emits, stats = self._launch_decode(
                        toks, pos0, alive0, n)
            except PermanentFault:
                # the chunk never ran (the injector raises before launch):
                # cache/positions are untouched. Shed the affected lanes
                # and free their slots so queued work keeps flowing.
                self._reject_group([self.active[i] for i in live],
                                   "device-fault")
                for i in live:
                    self._release_slot(i)
                return len(live)
            except SilentCorruption:
                # every retry recomputed the same corrupted chunk; no state
                # was assigned, so the lanes are intact but unservable —
                # finalize them as sdc-uncorrectable and free the slots
                self.guard_events["uncorrectable"] += 1
                self._reject_group([self.active[i] for i in live],
                                   "sdc-uncorrectable")
                for i in live:
                    self._release_slot(i)
                return len(live)
            with self._span("decode.sync"):
                self.cache = cache
                seq = np.asarray(seq)                 # the ONE host sync
                emits = np.asarray(emits)
                stats = np.asarray(stats)   # device accumulators, ready
            t_end = self._clock()
            compiled = self._compiled(
                "decode", self._decode_fn._cache_size() > jit_before)
            if sp is not None:
                sp.done(t_start, t_end, steps=n, lanes=len(live),
                        tokens=int(stats[0]), live_end=int(stats[1]),
                        rids=[self.active[i].rid for i in live],
                        compiled=compiled,
                        state_bytes=len(live) * self.lane_state_bytes)
        with self._span("decode.retire"):
            self._retire_chunk(live, n, pos0, seq, emits, stats, t_start,
                               t_end)
        return len(live)

    def _launch_decode(self, toks, pos0, alive0, n: int) -> tuple:
        """The jitted decode chunk through the fault boundary: the new
        cache, the chunk's tokens, emit masks and stats (on the device,
        the stats on the host under the guard). Raises what _device_call
        raises."""
        if not self._guard_on:
            return self._device_call(
                "decode", lambda: self._decode_fn(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(pos0), jnp.asarray(self.budgets),
                    jnp.asarray(alive0), n=n))

        def call():
            cache, seq, emits, stats = self._decode_fn(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(pos0), jnp.asarray(self.budgets),
                jnp.asarray(alive0), self._sdc_arr(), n=n)
            flags = np.asarray(stats)
            if int(flags[-1]) > 0:
                raise SilentCorruption(
                    f"decode chunk: {int(flags[-1])} uncorrected "
                    f"corruption(s) detected")
            return cache, seq, emits, flags
        cache, seq, emits, stats = self._device_call("decode", call)
        self._note_guard(int(stats[-2]))
        return cache, seq, emits, stats

    def _retire_chunk(self, live: list[int], n: int, pos0, seq, emits,
                      stats, t_start: float, t_end: float) -> None:
        """The host's work on a synced chunk: telemetry, the tracer's
        step-locked replay, retire with κ calibration, shedding of
        non-finite lanes, expiry and in-chunk recycling."""
        self._observe_decode(n, len(live), int(stats[0]), int(stats[1]),
                             t_end - t_start)
        emitted = int(stats[0])
        if emitted > 0 and t_end > t_start:
            self._sec_per_tok.observe((t_end - t_start) / emitted)
            if self._slow_detect is not None:
                # EWMA slow-chunk detection (train/fault.py discipline):
                # a flagged degradation halves the next chunk; a healthy
                # chunk lifts the cap again
                flagged = self._slow_detect.observe(
                    (t_end - t_start) / emitted)
                self._chunk_cap = max(1, n // 2) if flagged else None
        if self.tracer is not None:                   # step-locked replay
            dt_step = (t_end - t_start) / n
            for s in range(n):
                lanes = [i for i in live if emits[s, i]]
                if lanes:
                    self.tracer.on_decode(
                        len(lanes), [int(pos0[i]) + s for i in lanes],
                        t=(t_start - self._t0) + s * dt_step)
        jit_now = self._jit_sizes()
        for i in live:
            r = self.active[i]
            cnt = int(emits[:, i].sum())
            r.out.extend(int(seq[s, i]) for s in range(cnt))
            self.positions[i] += cnt
            self.budgets[i] -= cnt
            hit_eos = (self.eos_id is not None and cnt > 0
                       and int(seq[cnt - 1, i]) == self.eos_id)
            if self.budgets[i] <= 0 or hit_eos:
                if (self.admission.predictor is not None
                        and jit_now == r._jit_epoch):
                    # κ calibration: measured service wall-clock vs the
                    # wave model's prediction for the tokens this request
                    # ACTUALLY produced (len(out), not the full budget —
                    # early-EOS/clamped completions must not bias κ low).
                    # Skipped when the jit cache grew during service: the
                    # wall then includes compile time, which would inflate
                    # κ and shed the requests right behind a cold start.
                    self.admission.observe_service(
                        self.admission.predictor.model_seconds(
                            len(r.prompt), max(1, len(r.out))),
                        t_end - r._admit_t)
                self.admission.finish(r, now=t_end)
                self._release_slot(i)
        # non-finite lanes (flags rode the stats sync): a poisoned lane
        # stopped emitting at the bad step — it cannot have finished above
        # (its budget never reached 0 on a masked emit) — shed it and
        # free the slot; tokens emitted before detection are kept
        poisoned = [(self.active[i], i) for i in live
                    if self.active[i] is not None
                    and stats[2 + i]]
        if poisoned:
            self._shed_non_finite(poisoned, where="decode")
            for _, i in poisoned:
                self._release_slot(i)
        # deadline enforcement at the chunk's existing host sync (zero new
        # syncs): completion above wins over expiry in the same chunk
        for i in self.admission.expired_lanes(self.active, t_end):
            self.admission.expire(self.active[i], "deadline-exceeded")
            self._release_slot(i)
        if self.recycle and self.queue and \
                any(r is None for r in self.active):
            # in-chunk lane recycling: a lane that died inside THIS chunk
            # (eos/budget/deadline/fault — its emit mask went dead at step
            # s < n) hands its slot and pages to queued work at this same
            # host sync. The successor's prefill lands before the next
            # decode chunk, so no idle chunk intervenes, and the tracer
            # records the handoff step-locked (prefill event at this
            # boundary's wall time) exactly like a start-of-step admit.
            occupied = sum(r is not None for r in self.active)
            self._admit()
            self.recycled += max(
                0, sum(r is not None for r in self.active) - occupied)
        self._observe_paged()

    def _with_table(self, cache):
        """Push the pool's slot-indexed page table into every paged leaf
        (broadcast across stacked layers). An async host->device transfer
        of a tiny int32 array; same pytree structure, so no recompiles."""
        table = self._pool.table()

        def fix(node):
            if isinstance(node, PagedKVCache):
                pt = jnp.asarray(np.broadcast_to(table,
                                                 node.page_table.shape))
                return dataclasses.replace(node, page_table=pt)
            return node
        return jax.tree.map(fix, cache,
                            is_leaf=lambda x: isinstance(x, PagedKVCache))

    def _observe_paged(self) -> None:
        m, pool = self.metrics, self._pool
        if m is None or pool is None:
            return
        m.gauge("serve.paged.occupancy").set(pool.occupancy)
        m.gauge("serve.paged.pages_in_use").set(pool.pages_in_use)
        m.gauge("serve.paged.reserved_pages").set(pool.reserved_pages)
        chunks = m.counter("serve.decode.chunks").value
        if chunks:
            m.gauge("serve.paged.recycle_rate").set(self.recycled / chunks)

    def run_to_completion(self, max_steps: int = 10_000) -> None:
        """Drive the engine until queue and slots drain. Raises
        `ServeStalled` (naming the stuck request ids/states) if max_steps
        quanta pass with work still pending — a wedged engine fails loudly
        instead of returning as if it had finished."""
        for _ in range(max_steps):
            if not self.queue and not any(self.active):
                return
            self.step()
        if not self.queue and not any(self.active):
            return
        pending = {r.rid: r.state for r in self.queue}
        pending.update({r.rid: r.state
                        for r in self.active if r is not None})
        raise ServeStalled(pending, max_steps)

    # -- introspection ----------------------------------------------------
    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill shape variants: buckets hit on the bucketed
        path (where the regression gate is <= log2(max_len)), distinct
        prompt lengths on the exact-length fallback (unbounded by
        construction — the quantity the gate exists to expose)."""
        if self.bucketed:
            try:
                return int(self._prefill_fn._cache_size())
            except AttributeError:                    # pragma: no cover
                return len(self._buckets_seen)
        return len(self._buckets_seen)

    @property
    def max_prefill_compiles(self) -> int:
        return max(1, int(math.log2(self.max_len)))

    def paged_kv_stats(self) -> dict:
        """Host-side page-pool accounting (no device sync). KV bytes are
        derived from the paged leaves' actual dtypes/shapes; `dense_bytes`
        is what the same leaves would cost as slots x max_len dense lanes
        — the scaling the paged cache exists to beat. SSM/ring state is
        fixed-size lane-resident (nothing to page) and reported separately
        as `resident_lane_bytes` so the accounting stays honest."""
        pool = self._pool
        if pool is None:
            raise ValueError("paged_kv_stats requires paged=True")
        per_tok = 0
        resident = 0
        is_node = lambda x: isinstance(x, (PagedKVCache, RingKVCache,
                                           SSMCache))
        for leaf in jax.tree.leaves(self.cache, is_leaf=is_node):
            if isinstance(leaf, PagedKVCache):
                per_tok += (leaf.k.nbytes + leaf.v.nbytes) \
                    // (pool.n_pages * pool.page_size)
            elif isinstance(leaf, RingKVCache):
                resident += leaf.k.nbytes + leaf.v.nbytes
        resident += self.slots * self.lane_state_bytes
        live_tokens = sum(int(self.positions[i])
                          for i, r in enumerate(self.active)
                          if r is not None)
        return {
            "page_size": pool.page_size,
            "total_pages": pool.n_pages,
            "pages_in_use": pool.pages_in_use,
            "free_pages": pool.free_pages,
            "reserved_pages": pool.reserved_pages,
            "occupancy": pool.occupancy,
            "live_tokens": live_tokens,
            "mapped_tokens": pool.pages_in_use * pool.page_size,
            "kv_bytes_per_token": per_tok,
            "mapped_bytes": pool.pages_in_use * pool.page_size * per_tok,
            "pool_bytes": pool.n_pages * pool.page_size * per_tok,
            "dense_bytes": self.slots * self.max_len * per_tok,
            "resident_lane_bytes": resident,
            "recycled": self.recycled,
        }


def _fix_lengths(cache, true_lens):
    """Reset per-lane cache lengths from the padded bucket length to the
    true prompt lengths, so padded slots stay masked until decode appends
    overwrite them (the bucketed-prefill correctness fixup)."""
    def fix(node):
        if isinstance(node, (KVCache, MLACache)):
            length = jnp.broadcast_to(
                true_lens.astype(node.length.dtype), node.length.shape)
            return dataclasses.replace(node, length=length)
        return node
    return jax.tree.map(
        fix, cache, is_leaf=lambda x: isinstance(x, (KVCache, MLACache)))


_CACHE_NODES = (KVCache, PagedKVCache, RingKVCache, MLACache, SSMCache,
                CrossKV)


def _paged_insert(big_cache, lane_cache, batch_axes, slot_ids, true_lens,
                  dest_pages):
    """Merge a dense transient prefill cache into the persistent paged
    cache, node by node: PagedKVCache nodes take the page-granular scatter
    (their dense twin in `lane_cache` reshapes to pages and lands on the
    host-chosen `dest_pages`), every other node — SSM state, ring windows
    — takes the same per-slot dense scatter as the dense impl. The
    node-level tree.map is what lets the two trees disagree in type at
    exactly the paged positions (flatten_up_to pairs whole nodes)."""
    def is_node(x):
        return isinstance(x, _CACHE_NODES)

    def merge(big, lane, ax):
        if isinstance(big, PagedKVCache):
            return big.scatter_prefill(lane, dest_pages, slot_ids,
                                       true_lens)

        def one(b, l, a):
            out = b
            for g in range(slot_ids.shape[0]):        # static unroll
                valid = slot_ids[g] >= 0
                s = jnp.maximum(slot_ids[g], 0)
                out = jnp.where(
                    valid,
                    jax.lax.dynamic_update_slice_in_dim(
                        out,
                        jax.lax.dynamic_slice_in_dim(l, g, 1, axis=a
                                                     ).astype(b.dtype),
                        s, axis=a),
                    out)
            return out
        return jax.tree.map(one, big, lane, ax)
    return jax.tree.map(merge, big_cache, lane_cache, batch_axes,
                        is_leaf=is_node)


def _write_lane(batched_cache, lane_cache, slot: int):
    """Insert a 1-lane cache into slot `slot` of the batched cache.

    Both trees have identical structure; lane arrays have batch dim 1. The
    batch axis position differs by cache kind: stacked-layer caches are
    [L, B, ...], unstacked [B, ...] — detected from rank difference."""
    def ins(big, small):
        if small.shape == big.shape:
            return small
        # find the axis where big has `slots` and small has 1 (batch axis;
        # includes the per-lane length vectors [B] / [L, B])
        for ax in range(small.ndim):
            if small.shape[ax] == 1 and big.shape[ax] != 1:
                return jax.lax.dynamic_update_slice_in_dim(
                    big, small.astype(big.dtype), slot, axis=ax)
        return big
    return jax.tree.map(ins, batched_cache, lane_cache)
