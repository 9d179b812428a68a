"""Model assembly: blocks, segments and stacks for the 10 assigned archs.

A model is a list of *segments*; each segment is a homogeneous stack of
layers scanned with jax.lax.scan (params carry a leading `layers` axis), so
HLO size is O(#segments), not O(depth). Heterogeneity is expressed between
segments:

  dense LMs           [("layers", dense, L)]
  dbrx                [("moe", moe, L)]
  deepseek-v2         [("dense0", dense-mla, 1), ("moe", moe-mla, L-1)]
  mamba2              [("layers", ssm, L)]
  hymba               global-attn layers split the SWA stack:
                      [g0 | swa x14 | g15 | swa x15 | g31], all hybrid blocks
  llama-3.2-vision    [("blocks", vlm 5-layer group, L/5)] (4 dense + 1 cross)
  whisper             encoder [("enc", encoder, L)] + decoder
                      [("dec", cross-decoder, L)]

Biases are omitted throughout (weights dominate; noted in DESIGN.md).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import attention as attn_mod
from .attention import (KVCache, PagedKVCache, RingKVCache, chunked_attention,
                        decode_attention)
from .layers import (LayerSlice, ParamSpec, apply_mlp, apply_norm,
                     apply_rope, embed, mlp_schema, norm_schema, pod_dense,
                     unembed, embed_schema)
from .moe import apply_moe, moe_schema
from .ssm import SSMCache, apply_ssm, ssm_schema

Constrain = Callable[[jax.Array, str], jax.Array]
_id_constrain: Constrain = lambda x, kind: x


@dataclasses.dataclass(frozen=True)
class Segment:
    name: str
    kind: str                  # dense | moe | ssm | hybrid | vlm | encoder | crossdec
    n: int                     # number of layers (or groups for vlm)
    window: Optional[int] = None   # sliding window for attention (hybrid)


def segments(cfg: ArchConfig) -> list[Segment]:
    if cfg.family == "vlm":
        assert cfg.n_layers % cfg.cross_attn_every == 0
        return [Segment("blocks", "vlm", cfg.n_layers // cfg.cross_attn_every)]
    if cfg.family == "ssm":
        return [Segment("layers", "ssm", cfg.n_layers)]
    if cfg.family == "hybrid":
        segs: list[Segment] = []
        prev = 0
        for gi, g in enumerate(sorted(cfg.global_attn_layers)):
            if g > prev:
                segs.append(Segment(f"swa{gi}", "hybrid", g - prev,
                                    window=cfg.sliding_window))
            segs.append(Segment(f"glob{gi}", "hybrid", 1, window=None))
            prev = g + 1
        if prev < cfg.n_layers:
            segs.append(Segment("swa_tail", "hybrid", cfg.n_layers - prev,
                                window=cfg.sliding_window))
        return segs
    if cfg.family == "moe":
        fd = cfg.moe.first_dense_layers
        segs = []
        if fd:
            segs.append(Segment("dense0", "dense", fd))
        segs.append(Segment("moe", "moe", cfg.n_layers - fd))
        return segs
    if cfg.encoder_decoder:
        return [Segment("dec", "crossdec", cfg.n_layers)]
    return [Segment("layers", "dense", cfg.n_layers)]


# --------------------------------------------------------------------------
# attention blocks (GQA and MLA)
# --------------------------------------------------------------------------

def attn_schema(cfg: ArchConfig, layers: int | None) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    lead = (layers,) if layers else ()
    la = ("layers",) if layers else ()
    if cfg.mla:
        m = cfg.mla
        qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
        return {
            "q_a": ParamSpec(lead + (d, m.q_lora_rank), la + ("embed", None)),
            "q_a_norm": ParamSpec(lead + (m.q_lora_rank,), la + (None,), init="ones"),
            "q_b": ParamSpec(lead + (m.q_lora_rank, cfg.n_heads, qk_dim),
                             la + (None, "heads", None)),
            "kv_a": ParamSpec(lead + (d, m.kv_lora_rank + m.qk_rope_head_dim),
                              la + ("embed", None)),
            "kv_a_norm": ParamSpec(lead + (m.kv_lora_rank,), la + (None,), init="ones"),
            "kv_b": ParamSpec(lead + (m.kv_lora_rank, cfg.n_heads,
                                      m.qk_nope_head_dim + m.v_head_dim),
                              la + (None, "heads", None)),
            "o": ParamSpec(lead + (cfg.n_heads, m.v_head_dim, d),
                           la + ("heads", None, "embed")),
        }
    return {
        "q": ParamSpec(lead + (d, cfg.n_heads, hd), la + ("embed", "heads", None)),
        "k": ParamSpec(lead + (d, cfg.n_kv_heads, hd), la + ("embed", "kv_heads", None)),
        "v": ParamSpec(lead + (d, cfg.n_kv_heads, hd), la + ("embed", "kv_heads", None)),
        "o": ParamSpec(lead + (cfg.n_heads, hd, d), la + ("heads", None, "embed")),
    }


def apply_gqa(p, x, cfg: ArchConfig, *, positions, causal=True, window=None,
              impl="chunked", cache: KVCache | RingKVCache | None = None,
              kv_rep: int = 1, kv_x=None, kv_block: int = 1024,
              use_pallas: bool = False, true_lens=None):
    """GQA attention. Train/prefill when cache is None or being filled;
    decode when x has S == 1 and cache is not None.
    kv_x: optional separate KV source (cross-attention).
    use_pallas routes the q/k/v/o projections through the systolic pod
    GEMM (layers.pod_dense, fused-lane form).
    true_lens [B]: per-lane valid length of a right-padded (bucketed)
    prefill — ring caches then gather each lane's last-window *real*
    tokens into their ring slots instead of the padded tail."""
    src = kv_x if kv_x is not None else x
    if use_pallas:
        q = pod_dense(x, p["q"])
        k = pod_dense(src, p["k"])
        v = pod_dense(src, p["v"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["q"])
        k = jnp.einsum("bsd,dhk->bshk", src, p["k"])
        v = jnp.einsum("bsd,dhk->bshk", src, p["v"])
    if cfg.use_rope and kv_x is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if kv_rep > 1:
        k = jnp.repeat(k, kv_rep, axis=2)
        v = jnp.repeat(v, kv_rep, axis=2)

    new_cache = None
    if cache is not None and x.shape[1] == 1:            # decode
        q_pos = positions[..., 0]                        # scalar or [B]
        if isinstance(cache, RingKVCache):
            new_cache = cache.append_token(k, v)
            k_pos = new_cache.positions()                # [B, W]
            out = decode_attention(q, new_cache.k, new_cache.v, k_pos,
                                   q_pos, window=window)
        elif isinstance(cache, PagedKVCache):
            # paged decode: append into the mapped page, then gather the
            # lane's pages back to a position-ordered dense view — same
            # decode_attention contract (k_pos -1 = invalid) as the dense
            # path, so tokens are bit-identical to KVCache serving.
            new_cache = cache.append(k, v)
            ck, cv, k_pos = new_cache.flat_view()
            out = decode_attention(q, ck, cv, k_pos, q_pos, window=window)
        else:
            new_cache = cache.append(k, v)
            ar = jnp.arange(new_cache.k.shape[1])
            k_pos = jnp.where(ar[None, :] < new_cache.length[:, None],
                              ar[None, :], -1)           # [B, S]
            out = decode_attention(q, new_cache.k, new_cache.v, k_pos,
                                   q_pos, window=window)
    else:                                                # train / prefill
        if cache is not None:
            if isinstance(cache, RingKVCache):
                W = cache.window
                S = k.shape[1]
                if true_lens is not None:
                    # bucketed prefill: per-lane gather of the last-window
                    # real tokens into ring layout (token p -> slot p % W).
                    # Slot s holds p(s) = last - ((last - s) mod W), the
                    # newest real position congruent to s; slots older than
                    # the window (or before position 0) stay zero and are
                    # masked by positions() via the true length.
                    last = (true_lens - 1)[:, None]            # [B, 1]
                    slots = jnp.arange(W)[None, :]             # [1, W]
                    pos = last - ((last - slots) % W)          # [B, W]
                    valid = (pos >= 0) & (pos > last - W)
                    idx = jnp.clip(pos, 0, S - 1)[..., None, None]
                    take = lambda a: jnp.where(
                        valid[..., None, None],
                        jnp.take_along_axis(
                            a, jnp.broadcast_to(
                                idx, (a.shape[0], W) + a.shape[2:]), axis=1),
                        0)
                    new_cache = RingKVCache(take(k), take(v),
                                            true_lens.astype(jnp.int32))
                else:
                    # exact-length prefill: keep last `window` tokens
                    kw = k[:, -W:]
                    vw = v[:, -W:]
                    pad = W - kw.shape[1]
                    if pad > 0:
                        kw = jnp.pad(kw, ((0, 0), (0, pad), (0, 0), (0, 0)))
                        vw = jnp.pad(vw, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    # ring layout: token p lives at slot p % W. If S < W
                    # the suffix already sits at its slots; otherwise
                    # rotate so the first kept token (p = S-W) lands on
                    # slot (S-W) % W.
                    roll = (S % W) if S >= W else 0
                    kw = jnp.roll(kw, roll, axis=1)
                    vw = jnp.roll(vw, roll, axis=1)
                    new_cache = RingKVCache(
                        kw, vw, jnp.full((k.shape[0],), S, jnp.int32))
            elif isinstance(cache, PagedKVCache):
                raise TypeError(
                    "PagedKVCache cannot be prefilled in place; prefill "
                    "through a dense transient cache and scatter_prefill "
                    "into the pool (the serve engine does)")
            else:
                new_cache = cache.append(k, v)
        out = chunked_attention(q, k, v, causal=causal, window=window,
                                q_offset=0, kv_block=kv_block) \
            if impl == "chunked" else \
            attn_mod.attention(q, k, v, impl=impl, causal=causal, window=window)
    B, S = x.shape[0], x.shape[1]
    out = out.reshape(B, S, cfg.n_heads, -1)
    if use_pallas:
        o_w = p["o"].reshape(-1, p["o"].shape[-1])       # [(H hd), d]
        return pod_dense(out.reshape(B, S, -1), o_w), new_cache
    return jnp.einsum("bshk,hkd->bsd", out, p["o"]), new_cache


@dataclasses.dataclass
class MLACache:
    c_kv: jax.Array     # [B, S, R]
    k_rope: jax.Array   # [B, S, rope_dim]
    length: jax.Array   # [B] per-lane

    @staticmethod
    def zeros(batch, max_len, kv_lora, rope_dim, dtype=jnp.bfloat16,
              layers: int | None = None):
        s1 = (batch, max_len, kv_lora)
        s2 = (batch, max_len, rope_dim)
        lshape: tuple[int, ...] = (batch,)
        if layers:
            s1, s2 = (layers,) + s1, (layers,) + s2
            lshape = (layers, batch)
        return MLACache(jnp.zeros(s1, dtype), jnp.zeros(s2, dtype),
                        jnp.zeros(lshape, jnp.int32))

    def append(self, c_new, r_new):
        idx = self.length                                # [B]
        upd = jax.vmap(
            lambda buf, new, i: jax.lax.dynamic_update_slice_in_dim(
                buf, new, i, axis=0))
        c = upd(self.c_kv, c_new, idx)
        r = upd(self.k_rope, r_new, idx)
        return MLACache(c, r, idx + c_new.shape[1])


jax.tree_util.register_dataclass(
    MLACache, data_fields=["c_kv", "k_rope", "length"], meta_fields=[])


def apply_mla(p, x, cfg: ArchConfig, *, positions, impl="chunked",
              cache: MLACache | None = None, kv_block: int = 1024):
    """DeepSeek-V2 MLA. Prefill: decompressed K/V + chunked attention.
    Decode: weight-absorbed form over the compressed cache (the latent
    cache is what makes 32k x 128-head decode fit in HBM)."""
    m = cfg.mla
    B, S, D = x.shape
    H = cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    scale = 1.0 / math.sqrt(qk_dim)

    q_lat = jnp.einsum("bsd,dr->bsr", x, p["q_a"])
    q_lat = _rms(q_lat, p["q_a_norm"])
    q = jnp.einsum("bsr,rhk->bshk", q_lat, p["q_b"])
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_lat = jnp.einsum("bsd,dr->bsr", x, p["kv_a"])
    c_kv, k_rope = kv_lat[..., :m.kv_lora_rank], kv_lat[..., m.kv_lora_rank:]
    c_kv = _rms(c_kv, p["kv_a_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]

    w_uk = p["kv_b"][..., :m.qk_nope_head_dim]      # [R, H, nope]
    w_uv = p["kv_b"][..., m.qk_nope_head_dim:]      # [R, H, v]

    if cache is not None and S == 1:                # absorbed decode
        new_cache = cache.append(c_kv, k_rope)
        ckv, krope, length = new_cache.c_kv, new_cache.k_rope, new_cache.length
        q_c = jnp.einsum("bshk,rhk->bshr", q_nope, w_uk)        # [B,1,H,R]
        s_nope = jnp.einsum("bshr,btr->bhst", q_c, ckv)
        s_rope = jnp.einsum("bshk,btk->bhst", q_rope, krope)
        s = (s_nope + s_rope).astype(jnp.float32) * scale       # [B,H,1,T]
        t_pos = jnp.arange(ckv.shape[1])
        s = s + jnp.where(t_pos[None, :] < length[:, None], 0.0,
                          attn_mod.NEG_INF)[:, None, None, :]
        pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        ctx_c = jnp.einsum("bhst,btr->bshr", pr, ckv)           # [B,1,H,R]
        ctx = jnp.einsum("bshr,rhv->bshv", ctx_c, w_uv)
        out = jnp.einsum("bshv,hvd->bsd", ctx, p["o"])
        return out, new_cache

    # prefill / train: decompress K, V per head
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, w_uk)
    v = jnp.einsum("bsr,rhv->bshv", c_kv, w_uv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = chunked_attention(qf, k, v, causal=True, softmax_scale=scale,
                            kv_block=kv_block)
    out = jnp.einsum("bshv,hvd->bsd", out, p["o"])
    new_cache = cache.append(c_kv, k_rope) if cache is not None else None
    return out, new_cache


def _rms(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def block_schema(cfg: ArchConfig, kind: str, layers: int | None) -> dict:
    d = cfg.d_model
    sch: dict = {}
    if kind in ("dense", "moe", "hybrid", "encoder", "crossdec"):
        sch["ln_attn"] = _norms(cfg, d, layers)
        sch["attn"] = attn_schema(cfg, layers)
    if kind in ("dense", "moe", "hybrid", "encoder", "crossdec", "cross_layer"):
        sch["ln_mlp"] = _norms(cfg, d, layers)
        if kind == "moe":
            sch["moe"] = moe_schema(cfg, layers)
        else:
            sch["mlp"] = mlp_schema(d, cfg.d_ff, cfg.activation, layers)
    if kind in ("ssm", "hybrid"):
        sch["ln_ssm"] = _norms(cfg, d, layers)
        sch["ssm"] = ssm_schema(cfg, layers)
    if kind in ("crossdec", "cross_layer"):
        sch["ln_cross"] = _norms(cfg, d, layers)
        sch["cross"] = attn_schema(
            dataclasses.replace(cfg, mla=None), layers)
    return sch


def _norms(cfg: ArchConfig, d: int, layers: int | None) -> dict:
    base = norm_schema(d, cfg.norm)
    if layers:
        return {k: ParamSpec((layers,) + v.shape, ("layers",) + v.axes,
                             init=v.init, dtype=v.dtype)
                for k, v in base.items()}
    return base


def apply_block(p, x, cfg: ArchConfig, kind: str, *,
                positions, window=None, impl="chunked", ssd_impl="jnp",
                cache: dict | None = None, kv_rep: int = 1,
                cross_src=None, causal=True, kv_block: int = 1024,
                constrain=None, use_pallas: bool = False, true_lens=None):
    """One layer. cache: dict with keys subset of {attn, ssm, cross} or None.
    cross_src: source embeddings for cross-attention (encoder output /
    image embeddings); at decode the per-layer cross K/V come from the
    cache instead. Returns (x, new_cache_dict).
    use_pallas: dense/GQA projections, the SSM's in/out projections, MLPs
    and the MoE expert dispatch (capacity-bucketed grouped pod GEMM,
    models/moe.py) run on the systolic pod kernels (MLA and the
    cross-attention q/o stay on the reference einsum path)."""
    new_cache: dict = {}
    act = jnp.dtype(cfg.dtype)

    def norm(name, y):
        """Pre-norm of the residual stream, in the compute dtype (the
        stream is float32 under cfg.residual_in_fp32)."""
        return apply_norm(p[name], y, cfg.norm).astype(act)

    def _cross_kv():
        """(k, v) for the cross attention + cache bookkeeping."""
        if cache is not None and "cross" in cache and x.shape[1] == 1:
            ck = cache["cross"]
            new_cache["cross"] = ck          # static across decode steps
            return ck.k, ck.v
        assert cross_src is not None, "cross layer needs cross_src"
        k, v = cross_kv_precompute(p["cross"], cross_src, cfg)
        if cache is not None and "cross" in cache:
            from .model import CrossKV
            new_cache["cross"] = CrossKV(k, v)
        return k, v
    if kind == "ssm":
        h = norm("ln_ssm", x)
        y, sc = apply_ssm(p["ssm"], h, cfg,
                          cache=cache.get("ssm") if cache else None,
                          impl=ssd_impl, true_lens=true_lens,
                          use_pallas=use_pallas)
        if sc is not None:
            new_cache["ssm"] = sc
        return x + y, new_cache

    if kind == "cross_layer":                    # vlm image layer
        h = norm("ln_cross", x)
        k, v = _cross_kv()
        q = jnp.einsum("bsd,dhk->bshk", h, p["cross"]["q"])
        out = chunked_attention(q, k, v, causal=False)
        a = jnp.einsum("bshk,hkd->bsd",
                       out.reshape(h.shape[0], h.shape[1], cfg.n_heads, -1),
                       p["cross"]["o"])
        x = x + a
        h = norm("ln_mlp", x)
        return x + apply_mlp(p["mlp"], h, cfg.activation,
                             use_pallas=use_pallas), new_cache

    if kind == "hybrid":
        h = norm("ln_attn", x)
        a, ac = apply_gqa(p["attn"], h, cfg, positions=positions,
                          causal=causal, window=window, impl=impl,
                          cache=cache.get("attn") if cache else None,
                          kv_rep=kv_rep, use_pallas=use_pallas,
                          true_lens=true_lens)
        s, sc = apply_ssm(p["ssm"], norm("ln_ssm", x),
                          cfg, cache=cache.get("ssm") if cache else None,
                          impl=ssd_impl, true_lens=true_lens,
                          use_pallas=use_pallas)
        if ac is not None:
            new_cache["attn"] = ac
        if sc is not None:
            new_cache["ssm"] = sc
        x = x + 0.5 * (a + s)
        h = norm("ln_mlp", x)
        return x + apply_mlp(p["mlp"], h, cfg.activation,
                             use_pallas=use_pallas), new_cache

    # attention blocks (dense / moe / encoder / crossdec)
    h = norm("ln_attn", x)
    if cfg.mla is not None and kind in ("dense", "moe"):
        a, ac = apply_mla(p["attn"], h, cfg, positions=positions, impl=impl,
                          cache=cache.get("attn") if cache else None,
                          kv_block=kv_block)
    else:
        a, ac = apply_gqa(p["attn"], h, cfg, positions=positions,
                          causal=causal, window=window, impl=impl,
                          cache=cache.get("attn") if cache else None,
                          kv_rep=kv_rep, kv_block=kv_block,
                          use_pallas=use_pallas, true_lens=true_lens)
    if ac is not None:
        new_cache["attn"] = ac
    x = x + a

    if kind == "crossdec":
        h = norm("ln_cross", x)
        k, v = _cross_kv()
        q = jnp.einsum("bsd,dhk->bshk", h, p["cross"]["q"])
        out = chunked_attention(q, k, v, causal=False)
        a = jnp.einsum("bshk,hkd->bsd",
                       out.reshape(h.shape[0], h.shape[1], cfg.n_heads, -1),
                       p["cross"]["o"])
        x = x + a

    h = norm("ln_mlp", x)
    if kind == "moe":
        y = apply_moe(p["moe"], h, cfg, constrain=constrain,
                      use_pallas=use_pallas)
    else:
        y = apply_mlp(p["mlp"], h, cfg.activation, use_pallas=use_pallas)
    return x + y, new_cache


def layer_view(p_seg, i, cfg: ArchConfig):
    """Layer i of a stacked segment, for the serving scan on the pod GEMM.

    The weights of pod GEMMs stay in their stacks as `LayerSlice`s, and
    the GEMM streams layer i's blocks from them: the MLP's, GQA's
    projections (q/k/v read in their stored [d, H, hd] layout; o's
    [H, hd, d] folds into [(H hd), d] without moving a byte) and the
    SSM's in_proj and out_proj. Every other leaf is indexed out; XLA
    fuses a norm scale's slice into its consumer."""
    def view(path, a):
        keys = tuple(getattr(k, "key", None) for k in path)
        if keys[:1] == ("mlp",) or (keys[:1] == ("attn",)
                                    and cfg.mla is None) or \
                keys[:2] in (("ssm", "in_proj"), ("ssm", "out_proj")):
            return LayerSlice(a, i)
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    return jax.tree_util.tree_map_with_path(view, p_seg)


def cross_kv_precompute(p_cross, src, cfg: ArchConfig):
    """K/V from an encoder output / image embeddings (no rope)."""
    k = jnp.einsum("bsd,dhk->bshk", src, p_cross["k"])
    v = jnp.einsum("bsd,dhk->bshk", src, p_cross["v"])
    return k, v
