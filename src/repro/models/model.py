"""Unified model API over the segment system.

    model = Model(get_arch("yi-6b"))
    params = model.init(rng)                       # or shapes() for dry-run
    loss = model.loss(params, batch)               # train
    logits, cache = model.prefill(params, batch)   # serving: prompt
    logits, cache = model.decode_step(params, tok, cache)  # serving: token

Caches, params and batches are plain pytrees; everything composes with jit,
shard_map, grad and the launch/ dry-run (which only ever touches
`model.schema()` shapes).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .attention import KVCache, PagedKVCache, RingKVCache
from .layers import (ParamSpec, apply_norm, cross_entropy_loss, embed,
                     embed_schema, init_from_schema, is_spec, norm_schema,
                     param_count, shapes_from_schema, unembed)
from .ssm import SSMCache
from .transformer import (MLACache, Segment, apply_block, block_schema,
                          layer_view, segments)

Constrain = Callable[[jax.Array, str], jax.Array]


@dataclasses.dataclass
class CrossKV:
    k: jax.Array   # [B, S_src, KV, hd]
    v: jax.Array

    @staticmethod
    def zeros(batch, src_len, n_kv, head_dim, dtype=jnp.bfloat16,
              layers: int | None = None):
        s = (batch, src_len, n_kv, head_dim)
        if layers:
            s = (layers,) + s
        return CrossKV(jnp.zeros(s, dtype), jnp.zeros(s, dtype))


jax.tree_util.register_dataclass(CrossKV, data_fields=["k", "v"], meta_fields=[])


def _stack_schema(sch, n: int):
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes,
                            init=s.init, scale=s.scale, dtype=s.dtype),
        sch, is_leaf=is_spec)


def _sinusoid(seq: int, d: int, offset=0):
    # offset: scalar or [B] (per-lane decode positions); returns
    # [1 or B, seq, d] broadcasting against [B, seq, d] activations.
    off = jnp.atleast_1d(jnp.asarray(offset))
    pos = (jnp.arange(seq)[None, :] + off[:, None]).astype(jnp.float32)
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)
    ang = pos[..., None] / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


class Model:
    def __init__(self, cfg: ArchConfig, attention_impl: str = "chunked",
                 ssd_impl: str = "jnp", kv_rep: int = 1,
                 constrain: Constrain | None = None, unroll: bool = False,
                 remat: bool = False, kv_block: int = 1024,
                 use_pallas: bool = False):
        self.cfg = cfg
        self.impl = attention_impl
        self.ssd_impl = ssd_impl
        self.kv_rep = kv_rep
        self.constrain = constrain or (lambda x, kind: x)
        # use_pallas routes dense/GQA projections + MLPs through the
        # systolic pod GEMM kernel with DSE-autotuned block geometry
        # (kernels/systolic_gemm; interpret mode on the CPU backend).
        # Reference einsum path stays the default and the numerics oracle.
        self.use_pallas = use_pallas
        # unroll=True replaces lax.scan with a Python loop over indexed
        # layer params — used by the dry-run's L1/L2 flop-calibration
        # compiles (XLA cost analysis counts a while body once; unrolled
        # variants + per-layer extrapolation recover exact totals).
        self.unroll = unroll
        # remat=True checkpoints each layer body: backward keeps only the
        # per-layer residual-stream carries (L x [B,S,D], sequence-sharded
        # under SP) and recomputes within-layer activations — the policy
        # that lets 340B train cells fit 16 GB/chip.
        self.remat = remat
        self.kv_block = kv_block   # chunked-attention block (SOSA DSE knob)
        self.segs = segments(cfg)

    def _body(self, fn):
        """Wrap a scan body with per-layer remat when training."""
        return jax.checkpoint(fn) if self.remat else fn

    def _scan(self, body, carry, xs):
        # an active PodGuard tape accumulates per-GEMM flags as traced
        # values on Python state — under lax.scan those would leak out of
        # the scan body, so a taped trace takes the unrolled path (guard
        # engines trade compile time for per-layer checksum visibility;
        # untaped traces keep the seed scan and its jit cache exactly)
        from ..kernels.systolic_gemm.guard import active_tape
        if not self.unroll and active_tape() is None:
            return jax.lax.scan(body, carry, xs)
        n = jax.tree.leaves(xs)[0].shape[0]
        ys = []
        for i in range(n):
            xi = jax.tree.map(lambda a: a[i], xs)
            carry, y = body(carry, xi)
            ys.append(y)
        stacked = None
        if ys and ys[0] is not None:
            stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
        return carry, stacked

    # -- schema / params ---------------------------------------------------
    def schema(self) -> dict:
        cfg = self.cfg
        sch: dict = {"embed": embed_schema(cfg.vocab, cfg.d_model,
                                           cfg.tie_embeddings),
                     "ln_f": norm_schema(cfg.d_model, cfg.norm)}
        for seg in self.segs:
            sch[seg.name] = self._segment_schema(seg)
        if cfg.encoder_decoder:
            sch["encoder"] = {
                "blocks": block_schema(cfg, "encoder", cfg.n_encoder_layers),
                "ln_f": norm_schema(cfg.d_model, cfg.norm),
            }
        if cfg.family == "vlm":
            sch["img_adapter"] = ParamSpec(
                (cfg.d_model, cfg.d_model), ("embed", None))
        return sch

    def _segment_schema(self, seg: Segment) -> dict:
        cfg = self.cfg
        if seg.kind == "vlm":
            inner = cfg.cross_attn_every - 1
            return {
                "plain": _stack_schema(block_schema(cfg, "dense", inner), seg.n),
                "cross": block_schema(cfg, "cross_layer", seg.n),
            }
        return block_schema(cfg, seg.kind, seg.n if seg.n > 1 else None)

    def init(self, rng) -> dict:
        return init_from_schema(rng, self.schema())

    def shapes(self) -> dict:
        return shapes_from_schema(self.schema())

    def param_count(self) -> int:
        return param_count(self.schema())

    # -- forward -----------------------------------------------------------
    def _embed_in(self, params, batch, offset=0):
        cfg = self.cfg
        x = embed(params["embed"], batch["tokens"])
        if cfg.residual_in_fp32:
            x = x.astype(jnp.float32)
        if not cfg.use_rope and cfg.family != "ssm":
            x = x + _sinusoid(x.shape[1], cfg.d_model,
                              offset=offset).astype(x.dtype)
        return self.constrain(x, "residual")

    def _encode(self, params, frames):
        """Whisper encoder over precomputed frame embeddings (stub frontend)."""
        cfg = self.cfg
        x = frames + _sinusoid(frames.shape[1], cfg.d_model).astype(frames.dtype)
        x = self.constrain(x, "residual")
        pos = jnp.arange(frames.shape[1])

        def body(carry, p_layer):
            h, _ = apply_block(p_layer, carry, cfg, "encoder", positions=pos,
                               impl=self.impl, causal=False,
                               use_pallas=self.use_pallas)
            return self.constrain(h, "residual"), None

        x, _ = self._scan(self._body(body), x, params["encoder"]["blocks"])
        return apply_norm(params["encoder"]["ln_f"], x, cfg.norm)

    def _cross_source(self, params, batch):
        cfg = self.cfg
        if cfg.encoder_decoder:
            return self._encode(params, batch["frames"])
        if cfg.family == "vlm":
            return jnp.einsum("bnd,de->bne", batch["image_embeds"],
                              params["img_adapter"])
        return None

    def _run_segment(self, seg: Segment, p_seg, x, positions, cache_seg,
                     cross_src, true_lens=None):
        cfg = self.cfg
        kw = dict(positions=positions, impl=self.impl, ssd_impl=self.ssd_impl,
                  kv_rep=self.kv_rep, window=seg.window,
                  kv_block=self.kv_block, constrain=self.constrain,
                  use_pallas=self.use_pallas, true_lens=true_lens)

        if seg.kind == "vlm":
            return self._run_vlm_segment(seg, p_seg, x, cache_seg,
                                         cross_src, kw)

        if seg.n == 1:
            x, nc = apply_block(p_seg, x, cfg, seg.kind, cache=cache_seg,
                                cross_src=cross_src, **kw)
            return self.constrain(x, "residual"), (nc if cache_seg is not None
                                                   else None)

        if cache_seg is None:                     # train/eval: plain scan
            def body(carry, p_layer):
                h, _ = apply_block(p_layer, carry, cfg, seg.kind,
                                   cache=None, cross_src=cross_src, **kw)
                return self.constrain(h, "residual"), None

            x, _ = self._scan(self._body(body), x, p_seg)
            return x, None

        # serving: carry the stacked cache and update layer i in place —
        # XLA reuses the carry buffer across iterations, so the KV cache
        # costs 1x HBM instead of the 2-3x an xs->ys scan would copy.
        # On the pod GEMM, an untaped scan closes over the stacked params
        # and scans the index alone: the GEMMs stream each layer's weights
        # from the stacks (transformer.layer_view) instead of reading a
        # copy the scan slices out of them.
        from ..kernels.systolic_gemm.guard import active_tape
        stream = self.use_pallas and not self.unroll and active_tape() is None

        def body(carry, xs):
            h, cache_st = carry
            p_layer, i = xs
            if stream:
                p_layer = layer_view(p_seg, i, cfg)
            cache_l = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                       keepdims=False),
                cache_st)
            h, nc = apply_block(p_layer, h, cfg, seg.kind, cache=cache_l,
                                cross_src=cross_src, **kw)
            cache_st = jax.tree.map(
                lambda a, nv: jax.lax.dynamic_update_index_in_dim(
                    a, nv.astype(a.dtype), i, 0),
                cache_st, nc)
            return (self.constrain(h, "residual"), cache_st), None

        (x, new_cache), _ = self._scan(
            body, (x, cache_seg),
            (None if stream else p_seg, jnp.arange(seg.n)))
        return x, new_cache

    def _run_vlm_segment(self, seg, p_seg, x, cache_seg, cross_src, kw):
        cfg = self.cfg

        if cache_seg is None:
            def group(carry, p_g):
                def inner(c2, p_l):
                    h2, _ = apply_block(p_l, c2, cfg, "dense", cache=None,
                                        **kw)
                    return self.constrain(h2, "residual"), None

                h, _ = self._scan(inner, carry, p_g["plain"])
                h, _ = apply_block(p_g["cross"], h, cfg, "cross_layer",
                                   cache=None, cross_src=cross_src, **kw)
                return self.constrain(h, "residual"), None

            x, _ = self._scan(self._body(group), x, p_seg)
            return x, None

        inner_n = cfg.cross_attn_every - 1

        def group(carry, xs):
            h, cache_st = carry
            p_g, gi = xs

            def inner(c2, xs2):
                h2, plain_st = c2
                p_l, li = xs2
                cache_l = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        jax.lax.dynamic_index_in_dim(a, gi, 0,
                                                     keepdims=False),
                        li, 0, keepdims=False),
                    plain_st)
                h2, nc = apply_block(p_l, h2, cfg, "dense", cache=cache_l,
                                     **kw)
                plain_st = jax.tree.map(
                    lambda a, nv: jax.lax.dynamic_update_index_in_dim(
                        a, jax.lax.dynamic_update_index_in_dim(
                            jax.lax.dynamic_index_in_dim(
                                a, gi, 0, keepdims=False),
                            nv.astype(a.dtype), li, 0),
                        gi, 0),
                    plain_st, nc)
                return (self.constrain(h2, "residual"), plain_st), None

            (h, plain_st), _ = self._scan(
                inner, (h, cache_st["plain"]),
                (p_g["plain"], jnp.arange(inner_n)))
            cross_l = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, gi, 0,
                                                       keepdims=False),
                cache_st["cross"])
            h, nc_cross = apply_block(p_g["cross"], h, cfg, "cross_layer",
                                      cache=cross_l, cross_src=cross_src,
                                      **kw)
            cross_st = jax.tree.map(
                lambda a, nv: jax.lax.dynamic_update_index_in_dim(
                    a, nv.astype(a.dtype), gi, 0),
                cache_st["cross"], nc_cross)
            return (self.constrain(h, "residual"),
                    {"plain": plain_st, "cross": cross_st}), None

        (x, new_cache), _ = self._scan(
            group, (x, cache_seg), (p_seg, jnp.arange(seg.n)))
        return x, new_cache

    def forward(self, params, batch, cache: dict | None = None,
                positions=None, true_lens=None):
        """Returns (logits, new_cache). cache None -> train/eval forward.
        true_lens [B]: per-lane valid lengths of a right-padded (bucketed)
        prefill — stateful mixers (SSM conv/SSD state, ring KV) apply
        masked state updates so the padding is inert (see apply_ssm /
        apply_gqa); attention-only KV caches ignore it (causal masking +
        the engine's post-prefill length fixup already handle padding)."""
        cfg = self.cfg
        S = batch["tokens"].shape[1]
        if positions is None:
            positions = jnp.arange(S)
        x = self._embed_in(params, batch,
                           offset=positions[..., 0] if S == 1 else 0)
        cross_src = self._cross_source(params, batch) if cache is None or \
            (cache is not None and S > 1) else None

        new_cache: dict = {}
        for seg in self.segs:
            cseg = cache.get(seg.name) if cache is not None else None
            x, nc = self._run_segment(seg, params[seg.name], x, positions,
                                      cseg, cross_src,
                                      true_lens=true_lens)
            if cache is not None:
                new_cache[seg.name] = nc
        x = apply_norm(params["ln_f"], x, cfg.norm).astype(cfg.dtype)
        logits = unembed(params["embed"], x, use_pallas=self.use_pallas)
        logits = self.constrain(logits, "logits")
        return logits, (new_cache if cache is not None else None)

    # -- training ----------------------------------------------------------
    def loss(self, params, batch):
        logits, _ = self.forward(params, batch)
        return cross_entropy_loss(logits, batch["labels"])

    # -- serving -----------------------------------------------------------
    @property
    def bucketed_prefill_ok(self) -> bool:
        """True when prefill lanes can be right-padded to a bucket length
        without corrupting serving state: attention-only KV/MLA caches are
        inert under padding (causal masking + the engine's post-prefill
        length fixup), and SSM / ring (sliding-window) caches now take
        masked state updates driven by the engine's per-lane `true_lens`
        (dt-masked SSD recurrence + true-length conv window, per-lane ring
        slot gather — see apply_ssm / apply_gqa), so ssm and hybrid join
        the bucket path. MoE capacity still lets padding tokens displace
        real ones, and encoder-decoder / VLM prompts carry non-token
        modalities — those families prefill exact-length.
        """
        return (self.cfg.family in ("dense", "ssm", "hybrid")
                and not self.cfg.encoder_decoder)

    def init_cache(self, batch: int, max_len: int, src_len: int = 0,
                   dtype=jnp.bfloat16, page_size: int | None = None,
                   kv_pages: int | None = None) -> dict:
        """page_size/kv_pages non-None builds a *paged* cache: every
        global-attention KVCache leaf becomes a PagedKVCache over a shared
        `kv_pages`-page pool (serve/paging.PagePool owns the host-side
        allocation). Ring (sliding-window) caches are already O(window)
        and SSM state is fixed-size per lane — neither has anything to
        page, so they stay lane-resident. Only the bucketed-prefill
        families (dense/ssm/hybrid) support paging: MLA/VLM/cross-decoder
        caches carry per-request shapes the page-granular prefill scatter
        does not cover."""
        cfg = self.cfg
        if (page_size is None) != (kv_pages is None):
            raise ValueError("page_size and kv_pages must be set together")
        if page_size is not None and not self.bucketed_prefill_ok:
            raise ValueError(
                f"paged KV cache requires a bucketed-prefill family "
                f"(dense/ssm/hybrid), not {cfg.family}")
        if page_size is not None and cfg.mla is not None:
            raise ValueError("paged KV cache does not support MLA caches")
        caches: dict = {}
        kv_v = max(1, cfg.n_kv_heads) * self.kv_rep
        hd = cfg.resolved_head_dim

        def kv_zeros(L):
            if page_size is not None:
                return PagedKVCache.zeros(batch, max_len, kv_v, hd,
                                          n_pages=kv_pages,
                                          page_size=page_size, dtype=dtype,
                                          layers=L)
            return KVCache.zeros(batch, max_len, kv_v, hd, dtype, layers=L)
        for seg in self.segs:
            L = seg.n if seg.n > 1 else None
            c: Any
            if seg.kind == "ssm":
                c = {"ssm": SSMCache.zeros(cfg, batch, layers=L, dtype=dtype)}
            elif seg.kind == "hybrid":
                if seg.window is not None:
                    att = RingKVCache.zeros(batch, min(seg.window, max_len),
                                            kv_v, hd, dtype)
                    if L:
                        att = jax.tree.map(
                            lambda a: jnp.broadcast_to(a, (L,) + a.shape).copy()
                            if a.ndim else jnp.zeros((L,), a.dtype), att)
                else:
                    att = kv_zeros(L)
                c = {"attn": att,
                     "ssm": SSMCache.zeros(cfg, batch, layers=L, dtype=dtype)}
            elif cfg.mla is not None and seg.kind in ("dense", "moe"):
                c = {"attn": MLACache.zeros(batch, max_len,
                                            cfg.mla.kv_lora_rank,
                                            cfg.mla.qk_rope_head_dim, dtype,
                                            layers=L)}
            elif seg.kind == "vlm":
                inner = cfg.cross_attn_every - 1
                plain = KVCache.zeros(batch, max_len, kv_v, hd, dtype)
                plain = jax.tree.map(
                    lambda a: jnp.zeros((seg.n, inner) + a.shape, a.dtype),
                    plain)
                cross = CrossKV.zeros(batch, src_len or cfg.n_image_tokens,
                                      cfg.n_kv_heads, hd, dtype, layers=seg.n)
                c = {"plain": {"attn": plain}, "cross": {"cross": cross}}
            elif seg.kind == "crossdec":
                c = {"attn": KVCache.zeros(batch, max_len, kv_v, hd, dtype,
                                           layers=L),
                     "cross": CrossKV.zeros(batch, src_len, cfg.n_kv_heads,
                                            hd, dtype, layers=L)}
            else:
                c = {"attn": kv_zeros(L)}
            caches[seg.name] = c
        return caches

    def prefill(self, params, batch, cache: dict):
        """Run the prompt through the model, filling `cache`.
        Returns (last-position logits [B, vocab], cache)."""
        logits, cache = self.forward(params, batch, cache=cache)
        return logits[:, -1, :], cache

    def decode_step(self, params, tokens, cache: dict, position):
        """tokens [B] or [B,1]; position: scalar index, or [B] per-lane
        indices (continuous batching with mixed-length requests)."""
        if tokens.ndim == 1:
            tokens = tokens[:, None]
        B = tokens.shape[0]
        pos_vec = jnp.broadcast_to(
            jnp.asarray(position, jnp.int32), (B,))
        positions = pos_vec[:, None]                     # [B, 1]
        logits, cache = self.forward(params, {"tokens": tokens}, cache=cache,
                                     positions=positions)
        return logits[:, -1, :], cache


