"""Plain float32 reference of a dense GQA decoder (Llama architecture, as
Yi-6B publishes it): RMSNorm before attention and MLP, rotary position
embedding on q and k (rotate-half form), grouped-query attention in which
query head h reads key/value head h // (heads / kv_heads), causal softmax
at 1/sqrt(head_dim), a SiLU-gated MLP, a final RMSNorm and an untied LM
head. No cache, no batching tricks, no kernels: the whole sequence at
once, one layer at a time so that only one layer's weights are ever held
in float32.

Parameters are read by name from the benchmark's own weight tree
(bench/weights.py); the sizes come from the configuration file's published
keys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from refmath import HIGHEST, layer_slice, mm, rmsnorm

STACK = "layers"


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {"d": d, "heads": h, "kv": conf["num_key_value_heads"],
            "hd": conf.get("head_dim") or d // h,
            "eps": conf["rms_norm_eps"], "theta": conf["rope_theta"],
            "layers": conf["num_hidden_layers"]}


def embed(conf: dict, params, tokens):
    return jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)


def _rope(x, theta: float):
    """x [B, T, H, hd]; position t rotates pair (i, i + hd/2) by
    t * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(conf: dict, params, i, h, quant=None):
    """Residual stream h [B, T, d] through layer i."""
    m = dims(conf)
    p = layer_slice(params[STACK], i)
    x = rmsnorm(h, p["ln_attn"]["scale"], m["eps"])
    q = _rope(mm(x, p["attn"]["q"], quant=quant), m["theta"])
    k = _rope(mm(x, p["attn"]["k"], quant=quant), m["theta"])
    v = mm(x, p["attn"]["v"], quant=quant)
    rep = m["heads"] // m["kv"]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    s = s / jnp.sqrt(jnp.float32(m["hd"]))
    T = h.shape[1]
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    h = h + mm(a, p["attn"]["o"], 2, quant=quant)
    x = rmsnorm(h, p["ln_mlp"]["scale"], m["eps"])
    g = jax.nn.silu(mm(x, p["mlp"]["gate"], quant=quant))
    u = mm(x, p["mlp"]["up"], quant=quant)
    return h + mm(g * u, p["mlp"]["down"], quant=quant)


def head(conf: dict, params, h, quant=None):
    x = rmsnorm(h, params["ln_f"]["scale"], conf["rms_norm_eps"])
    if "unembed" in params["embed"]:
        return mm(x, params["embed"]["unembed"], quant=quant)
    return mm(x, params["embed"]["tok"].T, quant=quant)


def n_layers(conf: dict) -> int:
    return conf["num_hidden_layers"]


# -- operation counts (the model's, not the program's) --------------------

def body_params(conf: dict) -> int:
    """Matmul parameters of the layers (embedding and LM head excluded)."""
    m = dims(conf)
    d, hd = m["d"], m["hd"]
    attn = d * m["heads"] * hd + 2 * d * m["kv"] * hd + m["heads"] * hd * d
    return m["layers"] * (attn + 3 * d * conf["intermediate_size"])


def token_flops(conf: dict, context: int) -> float:
    """FLOPs to run one token through the layers when it attends to
    `context` positions (itself included): the projections, plus q.k and
    p.v over the context in every layer."""
    m = dims(conf)
    attn = 4 * m["heads"] * m["hd"] * context
    return 2.0 * body_params(conf) + m["layers"] * attn


def head_flops(conf: dict) -> float:
    return 2.0 * conf["hidden_size"] * conf["vocab_size"]


def gemm_shapes(conf: dict) -> list[tuple[int, int]]:
    """(K, N) of every projection the model asks the pod GEMM for."""
    m = dims(conf)
    d, hd, ff = m["d"], m["hd"], conf["intermediate_size"]
    return [(d, m["heads"] * hd), (d, m["kv"] * hd), (m["heads"] * hd, d),
            (d, ff), (ff, d), (d, conf["vocab_size"])]
