"""Plain float32 reference of a Mamba-2 language model (MambaLMHeadModel
with Mamba2 mixers, as state-spaces/mamba2-370m publishes it).

Each layer: RMSNorm of the residual stream, then the Mamba2 mixer:
`in_proj` split into [z, xBC, dt]; a depthwise causal convolution of
width d_conv with bias over xBC, then SiLU; xBC split into x (heads of
headdim), B and C (ngroups groups of d_state; head h reads group
h // (heads / ngroups)); dt = softplus(dt + dt_bias), A = -exp(A_log);
the recurrence h_t = exp(dt_t A) h_(t-1) + dt_t x_t B_t^T, y_t = h_t C_t
+ D x_t, run position by position (a lax.scan, not the program's chunked
form); a gated RMSNorm of y * silu(z); `out_proj`; the residual. Then a
final RMSNorm and the LM head tied to the embedding. The residual stream
is float32, as the published `residual_in_fp32` keeps it. No cache, no
batching tricks, no kernels, one layer at a time.

Parameters are read by name from the benchmark's weight tree
(bench/weights.py); the sizes come from the configuration file's
published keys and the Mamba2 defaults it writes out under `ssm_cfg`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from refmath import layer_slice, mm, rmsnorm

STACK = "layers"
STATE_BYTES = 2          # the recurrent state is held in bf16


def dims(conf: dict) -> dict:
    s = conf["ssm_cfg"]
    d = conf["d_model"]
    di = s["expand"] * d
    G, N = s["ngroups"], s["d_state"]
    return {"d": d, "di": di, "heads": di // s["headdim"],
            "hd": s["headdim"], "groups": G, "state": N,
            "conv": s["d_conv"], "conv_dim": di + 2 * G * N,
            "in_w": 2 * di + 2 * G * N + di // s["headdim"],
            "eps": conf["norm_epsilon"], "layers": conf["n_layer"],
            "vocab": conf["vocab"]}


def embed(conf: dict, params, tokens):
    return jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)


def _causal_conv(x, w, b):
    """x [B, T, C], w [K, C]: y[t] = sum_k w[k] x[t - (K-1) + k] + b."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, k:k + T] * w[k] for k in range(K)) + b


def _recurrence(x, dt, A, B, C, D):
    """x [b, T, H, P]; dt [b, T, H]; B, C [b, T, H, N]; A, D [H]. The
    state h [b, H, P, N] walks the positions in order, in float32
    elementwise arithmetic."""
    def step(h, t):
        x_t, dt_t, B_t, C_t = t
        decay = jnp.exp(dt_t * A)[..., None, None]
        h = decay * h + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None]
        y = jnp.sum(h * C_t[:, :, None], axis=-1) + D[:, None] * x_t
        return h, y

    b, _, H, P = x.shape
    h0 = jnp.zeros((b, H, P, B.shape[-1]), jnp.float32)
    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C))
    _, y = jax.lax.scan(step, h0, seq)
    return jnp.moveaxis(y, 0, 1)


def layer(conf: dict, params, i, h, quant=None):
    """Residual stream h [B, T, d] through layer i."""
    m = dims(conf)
    p = layer_slice(params[STACK], i)
    s = p["ssm"]
    di, gn, H, P = m["di"], m["groups"] * m["state"], m["heads"], m["hd"]
    x = rmsnorm(h, p["ln_ssm"]["scale"], m["eps"])
    z, xbc, dt = jnp.split(mm(x, s["in_proj"], quant=quant),
                           [di, 2 * di + 2 * gn], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc, s["conv_w"], s["conv_b"]))
    xs, B, C = jnp.split(xbc, [di, di + gn], axis=-1)
    b, T = h.shape[:2]
    rep = H // m["groups"]
    heads = lambda a: jnp.repeat(
        a.reshape(b, T, m["groups"], m["state"]), rep, axis=2)
    y = _recurrence(xs.reshape(b, T, H, P),
                    jax.nn.softplus(dt + s["dt_bias"]), -jnp.exp(s["A_log"]),
                    heads(B), heads(C), s["D"])
    y = _gated_rmsnorm(y.reshape(b, T, di), z, s["norm"], m["eps"])
    return h + mm(y, s["out_proj"], quant=quant)


def _gated_rmsnorm(y, z, w, eps: float):
    """Mamba-2's RMSNormGated with the gate before the norm, over all
    channels (one group)."""
    return rmsnorm(y * jax.nn.silu(z), w, eps)


def head(conf: dict, params, h, quant=None):
    x = rmsnorm(h, params["ln_f"]["scale"], conf["norm_epsilon"])
    return mm(x, params["embed"]["tok"].T, quant=quant)


def n_layers(conf: dict) -> int:
    return conf["n_layer"]


# -- operation and byte counts (the model's, not the program's) ----------

def body_params(conf: dict) -> int:
    """Matmul parameters of the layers (in_proj and out_proj)."""
    m = dims(conf)
    return m["layers"] * (m["d"] * m["in_w"] + m["di"] * m["d"])


def token_flops(conf: dict, context: int) -> float:
    """FLOPs to run one token through the layers in the recurrent form,
    whatever its context: the projections, the convolution (2 K per
    channel) and the state update (decay, dt x B^T added, C read out: 5
    per state element)."""
    m = dims(conf)
    state = m["heads"] * m["hd"] * m["state"]
    per_layer = 2 * m["conv"] * m["conv_dim"] + 5 * state
    return 2.0 * body_params(conf) + m["layers"] * per_layer


def head_flops(conf: dict) -> float:
    return 2.0 * conf["d_model"] * conf["vocab"]


def gemm_shapes(conf: dict) -> list[tuple[int, int]]:
    """(K, N) of every projection the model asks the pod GEMM for: in_proj,
    out_proj and the tied LM head."""
    m = dims(conf)
    return [(m["d"], m["in_w"]), (m["di"], m["d"]), (m["d"], m["vocab"])]


def state_shapes(conf: dict, slots: int) -> list[tuple[int, ...]]:
    """Shapes of the stacked recurrent state of `slots` lanes: the SSD
    state [layers, slots, heads, headdim, d_state] and the conv window
    [layers, slots, d_conv - 1, conv channels]."""
    m = dims(conf)
    return [(m["layers"], slots, m["heads"], m["hd"], m["state"]),
            (m["layers"], slots, m["conv"] - 1, m["conv_dim"])]


def state_bytes(conf: dict, lanes: int) -> int:
    """Bytes of recurrent state that `lanes` lanes hold, every layer."""
    m = dims(conf)
    per_layer = (m["heads"] * m["hd"] * m["state"]
                 + (m["conv"] - 1) * m["conv_dim"])
    return lanes * m["layers"] * per_layer * STATE_BYTES
