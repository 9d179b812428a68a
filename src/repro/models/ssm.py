"""Mamba-2 SSD (state-space duality) blocks — sub-quadratic token mixing.

Chunked SSD (the paper's Listing 1, in JAX): within a chunk of length C the
output is a masked matrix product (the "duality" — it is literally a batch
of small GEMMs, which is why SOSA's tiling applies to SSM archs, DESIGN.md
§4); across chunks a lax.scan carries the [H, P, N] state. Total cost
O(S·C) instead of O(S²).

Decode is the recurrent form: h <- exp(dt·A)·h + dt·B·x (O(1) per token),
so mamba2/hymba run the long_500k cell where full-attention archs cannot.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import ParamSpec, pod_dense


def ssm_schema(cfg: ArchConfig, layers: int | None = None) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    H = s.n_heads(d)
    G, N, K = s.n_groups, s.d_state, s.conv_kernel
    lead = (layers,) if layers else ()
    la = ("layers",) if layers else ()
    conv_dim = di + 2 * G * N
    return {
        # in_proj -> [z (gate), x, B, C, dt]
        "in_proj": ParamSpec(lead + (d, 2 * di + 2 * G * N + H),
                             la + ("embed", "ssm_inner")),
        "conv_w": ParamSpec(lead + (K, conv_dim), la + (None, "ssm_inner")),
        "conv_b": ParamSpec(lead + (conv_dim,), la + ("ssm_inner",), init="zeros"),
        "A_log": ParamSpec(lead + (H,), la + ("ssm_heads",), init="zeros"),
        "D": ParamSpec(lead + (H,), la + ("ssm_heads",), init="ones"),
        "dt_bias": ParamSpec(lead + (H,), la + ("ssm_heads",), init="zeros"),
        "norm": ParamSpec(lead + (di,), la + ("ssm_inner",), init="ones"),
        "out_proj": ParamSpec(lead + (di, d), la + ("ssm_inner", "embed")),
    }


def _split_proj(zxbcdt, cfg: ArchConfig):
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    G, N = s.n_groups, s.d_state
    H = s.n_heads(cfg.d_model)
    z, x, B, C, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + G * N, 2 * di + 2 * G * N], axis=-1)
    return z, x, B, C, dt


def _causal_conv(x, w, b, cache=None, true_lens=None):
    """Depthwise causal conv1d. x [B,S,Cd], w [K,Cd].
    cache: [B, K-1, Cd] trailing context for decode; returns (y, new_cache).
    true_lens [B]: per-lane valid length of a right-padded prefill — the
    returned context window then ends at each lane's *true* last token
    (ctx index L maps to input position L-(K-1)), bit-identical to what an
    exact-length prefill of that lane would have cached."""
    K = w.shape[0]
    if cache is None:
        ctx = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        ctx = jnp.concatenate([cache, x], axis=1)
    # y[t] = sum_k w[k] * ctx[t + k]
    S = x.shape[1]
    y = sum(ctx[:, k:k + S, :] * w[k] for k in range(K)) + b
    if K == 1:
        new_cache = ctx[:, :0, :]
    elif true_lens is not None:
        new_cache = jax.vmap(
            lambda c, l: jax.lax.dynamic_slice_in_dim(c, l, K - 1, axis=0)
        )(ctx, true_lens)
    else:
        new_cache = ctx[:, -(K - 1):, :]
    return y, new_cache


def ssd_chunked(x, dt, A, B, C, D, chunk: int, impl: str = "jnp"):
    """SSD forward. x [b,S,H,P]; dt [b,S,H]; A [H] (negative); B,C [b,S,G,N].
    Returns y [b,S,H,P] and final state [b,H,P,N]."""
    if impl == "pallas":
        from repro.kernels.ssd import ops as ssd_ops
        return ssd_ops.ssd(x, dt, A, B, C, D, chunk=chunk)
    return ssd_reference(x, dt, A, B, C, D, chunk)


def ssd_reference(x, dt, A, B, C, D, chunk: int):
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    L = nc * chunk

    # broadcast groups to heads (G divides H)
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2)   # [b,L,H,N]
    Ch = jnp.repeat(C, rep, axis=2)

    xc = x.reshape(b, nc, chunk, H, P)
    dtc = dt.reshape(b, nc, chunk, H).astype(jnp.float32)
    Bc = Bh.reshape(b, nc, chunk, H, N)
    Cc = Ch.reshape(b, nc, chunk, H, N)

    dA = dtc * A[None, None, None, :]                  # [b,nc,c,H] (<0)
    cum = jnp.cumsum(dA, axis=2)                       # within-chunk cumsum
    # intra-chunk: Y_intra[t] = sum_{s<=t} C_t.B_s exp(cum_t - cum_s) dt_s x_s
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # [b,nc,t,s,H]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)
    scores = jnp.einsum("bqthn,bqshn->bqtsh", Cc, Bc).astype(jnp.float32)
    M = scores * decay * dtc[:, :, None, :, :]
    y_intra = jnp.einsum("bqtsh,bqshp->bqthp", M.astype(x.dtype), xc)

    # chunk states: S_q = sum_s exp(cum_end - cum_s) dt_s B_s x_s^T
    decay_end = jnp.exp(cum[:, :, -1:, :] - cum)       # [b,nc,c,H]
    states = jnp.einsum("bqsh,bqshn,bqshp->bqhpn",
                        (decay_end * dtc).astype(x.dtype), Bc, xc)

    # inter-chunk recurrence over nc
    chunk_decay = jnp.exp(cum[:, :, -1, :])            # [b,nc,H]

    def step(h, inp):
        st, dec = inp                                  # [b,H,P,N], [b,H]
        h_new = h * dec[:, :, None, None].astype(h.dtype) + st
        return h_new, h

    h0 = jnp.zeros((b, H, P, N), x.dtype)
    h_final, h_prev = jax.lax.scan(
        step, h0, (states.transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(1, 0, 2)))
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)           # [b,nc,H,P,N]

    # contribution of carried state: Y_inter[t] = C_t exp(cum_t) h_prev
    y_inter = jnp.einsum("bqth,bqthn,bqhpn->bqthp",
                         jnp.exp(cum).astype(x.dtype), Cc, h_prev)
    y = (y_intra + y_inter).reshape(b, L, H, P)[:, :S]
    y = y + x.reshape(b, L, H, P)[:, :S] * D[None, None, :, None]
    return y, h_final


def ssd_decode_step(x, dt, A, B, C, D, h):
    """One-token recurrence. x [b,H,P]; dt [b,H]; B,C [b,G,N]; h [b,H,P,N].

    As mamba_ssm's selective_state_update: the state is read in its
    stored dtype, updated and read out in float32, and stored back in its
    own dtype, so the bytes moved are those of the stored state."""
    H = x.shape[1]
    G = B.shape[1]
    rep = H // G
    f32 = jnp.float32
    Bh = jnp.repeat(B, rep, axis=1).astype(f32)   # [b,H,N]
    Ch = jnp.repeat(C, rep, axis=1).astype(f32)
    xf = x.astype(f32)
    dtf = dt.astype(f32)
    dA = jnp.exp(dtf * A[None, :])[..., None, None]
    # elementwise products and a sum, not einsums: a dot on a TPU rounds
    # float32 operands to bf16 at default precision
    dBx = (dtf[..., None] * xf)[..., None] * Bh[:, :, None, :]
    h_new = h.astype(f32) * dA + dBx
    y = jnp.sum(h_new * Ch[:, :, None, :], axis=-1) + xf * D[None, :, None]
    return y.astype(x.dtype), h_new.astype(h.dtype)


@dataclasses.dataclass
class SSMCache:
    """Decode state: conv context + SSD state (optionally layer-stacked)."""
    conv: jax.Array    # [(L,) B, K-1, conv_dim]
    state: jax.Array   # [(L,) B, H, P, N]

    @staticmethod
    def zeros(cfg: ArchConfig, batch: int, layers: int | None = None,
              dtype=jnp.bfloat16):
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        H = s.n_heads(cfg.d_model)
        conv_dim = di + 2 * s.n_groups * s.d_state
        cshape = (batch, s.conv_kernel - 1, conv_dim)
        sshape = (batch, H, s.head_dim, s.d_state)
        if layers:
            cshape = (layers,) + cshape
            sshape = (layers,) + sshape
        return SSMCache(jnp.zeros(cshape, dtype), jnp.zeros(sshape, dtype))

    def lane_bytes(self) -> int:
        """Device bytes of ONE lane's SSM state (conv window + SSD state).
        The state is fixed-size regardless of context length — there is
        nothing for the paged KV pool to page, so paged serving keeps SSM
        state lane-resident and the memory accounting
        (ServeEngine.paged_kv_stats) reports it separately and honestly."""
        batch = self.conv.shape[-3]
        return (self.conv.size * self.conv.dtype.itemsize
                + self.state.size * self.state.dtype.itemsize) // batch


jax.tree_util.register_dataclass(
    SSMCache, data_fields=["conv", "state"], meta_fields=[])


def apply_ssm(p: dict, u, cfg: ArchConfig, cache: SSMCache | None = None,
              impl: str = "jnp", true_lens=None, use_pallas: bool = False):
    """Full Mamba-2 mixer. u [B,S,D] -> ([B,S,D], new_cache_or_None).

    use_pallas runs in_proj and out_proj on the systolic pod GEMM
    (layers.pod_dense; either may be a LayerSlice of its stack).

    Prefill/train: chunked SSD (cache may be None). When S == 1 and a cache
    is provided, takes the O(1) recurrent path.

    true_lens [B] (bucketed prefill): the input is right-padded to a shared
    bucket length and the recurrence must not integrate the padding. The
    masked state update is dt <- dt * (pos < L): a padded step then has
    exp(dt·A) = 1 and dt·B·x = 0 — an exact identity on the SSD state —
    and contributes exactly zero to every real position's intra-chunk
    output, so real-lane outputs and the final state are bit-identical to
    an exact-length prefill. The conv context window is gathered at the
    true length (`_causal_conv`).
    """
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    H = s.n_heads(cfg.d_model)
    P = s.head_dim
    G, N = s.n_groups, s.d_state

    if use_pallas:
        zxbcdt = pod_dense(u, p["in_proj"])
    else:
        zxbcdt = jnp.einsum("bsd,de->bse", u, p["in_proj"])
    z, x, B, C, dt = _split_proj(zxbcdt, cfg)
    xBC = jnp.concatenate([x, B, C], axis=-1)
    conv_cache = cache.conv if cache is not None else None
    if true_lens is not None and u.shape[1] == 1:
        true_lens = None                        # decode: nothing is padded
    xBC, new_conv = _causal_conv(xBC, p["conv_w"], p["conv_b"], conv_cache,
                                 true_lens=true_lens)
    xBC = jax.nn.silu(xBC)
    x, B, C = jnp.split(xBC, [di, di + G * N], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    if true_lens is not None:
        valid = jnp.arange(u.shape[1])[None, :] < true_lens[:, None]
        dt = dt * valid[..., None]              # exact 0 at padded steps
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    bsz, S = u.shape[0], u.shape[1]
    xh = x.reshape(bsz, S, H, P)
    Bh = B.reshape(bsz, S, G, N)
    Ch = C.reshape(bsz, S, G, N)

    if cache is not None and S == 1:
        y, h_new = ssd_decode_step(
            xh[:, 0], dt[:, 0], A, Bh[:, 0], Ch[:, 0], p["D"], cache.state)
        y = y[:, None]
    else:
        y, h_new = ssd_chunked(xh, dt, A, Bh, Ch, p["D"], s.chunk_size, impl)

    y = y.reshape(bsz, S, di)
    # gated RMSNorm (Mamba-2)
    yf = (y * jax.nn.silu(z)).astype(jnp.float32)
    var = jnp.mean(yf * yf, axis=-1, keepdims=True)
    y = (yf * jax.lax.rsqrt(var + 1e-6)).astype(u.dtype) * p["norm"]
    if use_pallas:
        out = pod_dense(y, p["out_proj"])
    else:
        out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    new_cache = SSMCache(new_conv, h_new) if cache is not None else None
    return out, new_cache
