"""Attention: GQA (opt. QKV bias), DeepSeek MLA, cross-attention, KV cache.

Causal GQA self-attention on TPU (training, prefill) takes the Pallas
flash kernel with a backward pass (``flash_gate``); elsewhere long
sequences use a chunked online-softmax ("flash" in pure JAX, scan over key
blocks) so the (S,T) score matrix is never materialized, and short ones
the plain form.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, YarnConfig
from repro.dist.act_sharding import constrain, current_policy
from repro.dist.sharding import current_serve_tp
from repro.models.layers import apply_rope, dense_init, _dtype

PLAIN_MAX_SEQ = 2048          # above this, use chunked online-softmax
CHUNK = 1024

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# shared attention math


def plain_attention(q, k, v, *, causal: bool, q_offset=0,
                    kv_len: Optional[jnp.ndarray] = None,
                    scale: Optional[float] = None):
    """q:(B,S,H,D) k,v:(B,T,H,D) (KV already repeated to H heads).
    ``scale`` defaults to D^-0.5. Returns (B,S,H,D)."""
    b, s, h, d = q.shape
    t = k.shape[1]
    scale = d ** -0.5 if scale is None else scale
    s_ = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    if causal:
        qpos = jnp.arange(s) + q_offset
        kpos = jnp.arange(t)
        mask = kpos[None, :] <= qpos[:, None]
        s_ = jnp.where(mask[None, None], s_, NEG_INF)
    if kv_len is not None:                       # decode: valid cache prefix
        mask = jnp.arange(t)[None, :] < kv_len[:, None]       # (B,T)
        s_ = jnp.where(mask[:, None, None, :], s_, NEG_INF)
    w = jax.nn.softmax(s_, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", w.astype(q.dtype), v)


def chunked_attention(q, k, v, *, causal: bool, chunk: int = CHUNK,
                      scale: Optional[float] = None):
    """Online-softmax over key chunks. q,k:(B,S,H,D) v:(B,T,H,Dv)
    (Dv may differ from D, e.g. MLA's v_head_dim)."""
    b, s, h, d = q.shape
    dv = v.shape[-1]
    t = k.shape[1]
    c = min(chunk, t)
    assert t % c == 0, (t, c)
    n = t // c
    scale = d ** -0.5 if scale is None else scale
    qpos = jnp.arange(s)

    def body(carry, i):
        ki = jax.lax.dynamic_slice_in_dim(k, i * c, c, axis=1)
        vi = jax.lax.dynamic_slice_in_dim(v, i * c, c, axis=1)
        m, l, acc = carry
        s_ = jnp.einsum("bshd,bchd->bhsc", q, ki).astype(jnp.float32) * scale
        if causal:
            kpos = i * c + jnp.arange(c)
            mask = kpos[None, :] <= qpos[:, None]            # (S,C)
            s_ = jnp.where(mask[None, None], s_, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
        p = jnp.exp(s_ - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhsc,bchd->bhsd", p.astype(q.dtype), vi)
        acc = acc * corr[..., None].astype(q.dtype) + pv
        return (m_new, l, acc), None

    # flash-attention backward: recompute the (S,C) score block per chunk
    # instead of saving it (the bwd of this scan then stores only the
    # O(B*H*S) chunk-boundary carries, never the S x T matrix)
    body = jax.checkpoint(body, prevent_cse=False)

    m0 = jnp.full((b, h, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    a0 = jnp.zeros((b, h, s, dv), q.dtype)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(n))
    out = acc / jnp.maximum(l, 1e-30)[..., None].astype(q.dtype)
    return out.transpose(0, 2, 1, 3)             # (B,S,H,D)


def flash_gate(q, k, v, *, causal: bool, kv_len=None) -> bool:
    """Whether self-attention takes the causal GQA flash kernel
    (``repro.kernels.ops.causal_gqa_flash``) on un-repeated K/V. Decided
    on what the call shows: causal self-attention over the whole
    sequence (no ``kv_len``), S a multiple of 128, equal q and v head
    dims (not MLA), whole KV-head groups, no activation-sharding policy
    and no serving TP mesh (GSPMD cannot partition a ``pallas_call``; it
    would need a ``shard_map``), and a TPU backend. Every other call
    takes ``attention_math``."""
    from repro.kernels import ops
    s, h = q.shape[1], q.shape[2]
    return (causal and kv_len is None and k.shape[1] == s
            and s % 128 == 0 and v.shape[-1] == q.shape[-1]
            and h % k.shape[2] == 0 and current_policy() is None
            and current_serve_tp() is None and ops._on_tpu())


def attention_math(q, k, v, *, causal: bool, kv_len=None,
                   scale: Optional[float] = None):
    if q.shape[1] == k.shape[1] and q.shape[1] > PLAIN_MAX_SEQ:
        return chunked_attention(q, k, v, causal=causal, scale=scale)
    return plain_attention(q, k, v, causal=causal, kv_len=kv_len,
                           scale=scale)


# ---------------------------------------------------------------------------
# GQA


def init_gqa(rng, cfg: ArchConfig, cross: bool = False):
    d, dt = cfg.d_model, _dtype(cfg)
    hd = cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], d, cfg.n_heads * hd, dt),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, dt),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, dt),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dt)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dt)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dt)
    return p


def _proj_qkv(p, x, kv_x, cfg: ArchConfig):
    hd = cfg.resolved_head_dim
    b, s, _ = x.shape
    t = kv_x.shape[1]
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k = jnp.einsum("btd,de->bte", kv_x, p["wk"])
    v = jnp.einsum("btd,de->bte", kv_x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, t, cfg.n_kv_heads, hd)
    v = v.reshape(b, t, cfg.n_kv_heads, hd)
    return q, k, v


def _paged_append(pool, new, page_table, lens, ps, *, head_major=False):
    """Write one token per sequence into its page pool; position = lens[b]
    in logical pages. pool: (N, PS, ...) with new (B, ...), or the
    head-major GQA pool (N, Hkv, PS, hd) with new (B, Hkv, hd)."""
    b = new.shape[0]
    phys = page_table[jnp.arange(b), lens // ps]     # (B,)
    if head_major:
        return pool.at[phys, :, lens % ps].set(new.astype(pool.dtype))
    return pool.at[phys, lens % ps].set(new.astype(pool.dtype))


def _paged_read(pool, page_table):
    """Gather a contiguous (B, Pmax*PS, ...) view of the paged leaf.
    Used by MLA's absorbed decode (latent-space scores have no Pallas
    kernel); GQA paged decode goes through kernels/ops instead."""
    g = pool[jnp.maximum(page_table, 0)]             # (B, Pmax, PS, ...)
    return g.reshape(g.shape[0], -1, *g.shape[3:])


def apply_gqa(p, x, cfg: ArchConfig, *, positions=None, kv_x=None,
              cache=None, cache_index=None, causal=True,
              return_cache=False, page_table=None):
    """Self- or cross-attention.

    - training / encoder: cache=None, full seq.
    - prefill: return_cache=True -> returns populated cache.
    - decode: cache given + cache_index -> one-step update. cache_index
      may be a scalar (legacy: all rows at one position) or a (B,) vector
      of per-sequence lengths (serving: ragged continuous batch).
    - paged decode: cache holds ``k_pages``/``v_pages`` pools and
      ``page_table`` (B, Pmax) maps logical to physical pages
      (repro.serve.kv_cache). cache_index must then be the (B,) lengths.
    """
    cross = kv_x is not None
    src = kv_x if cross else x
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    g = cfg.n_heads // cfg.n_kv_heads

    def expand_kv(t):
        # repeat KV heads to the full H so the TP layout shards Q-heads and
        # keeps the (small) KV projections replicated (kv_heads of the
        # assigned archs never divide the 16-way model axis)
        return constrain(jnp.repeat(t, g, axis=2), "heads4") if g > 1 \
            else constrain(t, "heads4")

    if cache is not None and "ck" in cache:
        # cross-attention against precomputed (cached) encoder K/V
        q = jnp.einsum("bsd,de->bse", x, p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        qh = constrain(q.reshape(b, s, cfg.n_heads, hd), "heads4")
        out = plain_attention(qh, expand_kv(cache["ck"]),
                              expand_kv(cache["cv"]), causal=False)
        out = out.reshape(b, s, cfg.n_heads * hd)
        y = jnp.einsum("bse,ed->bsd", out, p["wo"])
        return y, cache
    if cache is not None and cache_index is not None and not cross:
        # single-token decode
        q, k_new, v_new = _proj_qkv(p, x, x, cfg)
        if cfg.rope in ("rope", "mrope"):
            pos = positions
            q = apply_rope(q, pos, cfg.rope_theta,
                           cfg.mrope_sections if cfg.rope == "mrope" else None)
            k_new = apply_rope(k_new, pos, cfg.rope_theta,
                               cfg.mrope_sections if cfg.rope == "mrope" else None)
        if "k_pages" in cache:
            # paged decode: append at (page_table[b, len//ps], len % ps),
            # then attend page-indirectly — kernels/ops dispatches to the
            # Pallas flash-decode kernel on TPU and to the grouped jnp
            # oracle elsewhere (DESIGN.md §6/§9/§12). Both are KV-head
            # grouped (each page fetched once per KV head, not once per
            # query head) so no repeat here, and both accept `lens` as a
            # scan carry: the serving engine's decode superstep advances
            # it on device across K tokens without a host round-trip.
            from repro.kernels.ops import paged_decode_attention
            lens = cache_index
            ps = cache["k_pages"].shape[2]            # (N, Hkv, PS, hd)
            kp = _paged_append(cache["k_pages"], k_new[:, 0], page_table,
                               lens, ps, head_major=True)
            vp = _paged_append(cache["v_pages"], v_new[:, 0], page_table,
                               lens, ps, head_major=True)
            tp_ctx = current_serve_tp()
            if tp_ctx is not None:
                # serving TP (DESIGN.md §14): kv-head-sharded pools, the
                # grouped kernel grid split per shard, output gathered
                # back to replicated (exact) before the wo projection
                from repro.kernels.decode_attention import tp_paged_decode
                out = tp_paged_decode(q[:, 0], kp, vp, page_table,
                                      lens + 1, mesh=tp_ctx[0],
                                      tp_axes=tp_ctx[1])[:, None]
            else:
                out = paged_decode_attention(q[:, 0], kp, vp, page_table,
                                             lens + 1)[:, None]  # (B,1,H,hd)
            y = jnp.einsum("bse,ed->bsd",
                           out.astype(x.dtype).reshape(b, s, -1), p["wo"])
            return y, {"k_pages": kp, "v_pages": vp}
        if jnp.ndim(cache_index):
            # ragged continuous batch: each row writes at its own length
            idx = cache_index
            k = cache["k"].at[jnp.arange(b), idx].set(
                k_new[:, 0].astype(cache["k"].dtype))
            v = cache["v"].at[jnp.arange(b), idx].set(
                v_new[:, 0].astype(cache["v"].dtype))
            kv_len = idx + 1
            new_cache = {"k": k, "v": v}
        else:
            idx = cache_index
            k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, idx,
                                                    axis=1)
            v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, idx,
                                                    axis=1)
            kv_len = jnp.broadcast_to(idx + 1, (b,))
            new_cache = {"k": k, "v": v}
        # decode: the cache is head_dim-sharded over TP (so 32k x B caches
        # fit per device); pin q/k/v to the same layout so the score
        # contraction becomes partial-dot + a tiny (B,H,1,T) all-reduce
        # instead of an all-gather of the whole cache.
        qh = constrain(q, "hd_tp")
        kx = constrain(jnp.repeat(k, g, axis=2), "hd_tp") if g > 1 \
            else constrain(k, "hd_tp")
        vx = constrain(jnp.repeat(v, g, axis=2), "hd_tp") if g > 1 \
            else constrain(v, "hd_tp")
        out = plain_attention(qh, kx, vx, causal=False, kv_len=kv_len)
    else:
        q, k, v = _proj_qkv(p, x, src, cfg)
        if not cross and cfg.rope in ("rope", "mrope"):
            q = apply_rope(q, positions, cfg.rope_theta,
                           cfg.mrope_sections if cfg.rope == "mrope" else None)
            k = apply_rope(k, positions, cfg.rope_theta,
                           cfg.mrope_sections if cfg.rope == "mrope" else None)
        qh = constrain(q, "heads4")
        if flash_gate(qh, k, v, causal=(causal and not cross)):
            from repro.kernels import ops
            out = ops.causal_gqa_flash(qh, k, v)
        else:
            out = attention_math(qh, expand_kv(k), expand_kv(v),
                                 causal=(causal and not cross))
        if cross:
            new_cache = {"ck": k, "cv": v} if return_cache else None
        else:
            new_cache = {"k": k, "v": v} if return_cache else None

    out = out.reshape(b, s, cfg.n_heads * hd)
    y = jnp.einsum("bse,ed->bsd", out, p["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# DeepSeek MLA


def init_mla(rng, cfg: ArchConfig):
    m = cfg.mla
    d, dt, h = cfg.d_model, _dtype(cfg), cfg.n_heads
    ks = jax.random.split(rng, 8)
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank is None:
        q = {"w_q": dense_init(ks[0], d, h * qd, dt)}
    else:
        q = {"w_dq": dense_init(ks[0], d, m.q_lora_rank, dt),
             "q_norm": jnp.ones((m.q_lora_rank,), dt),
             "w_uq": dense_init(ks[1], m.q_lora_rank, h * qd, dt)}
    return {
        **q,
        "w_dkv": dense_init(ks[2], d, m.kv_lora_rank, dt),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dt),
        "w_kr": dense_init(ks[3], d, m.qk_rope_head_dim, dt),
        "w_uk": dense_init(ks[4], m.kv_lora_rank, h * m.qk_nope_head_dim, dt
                           ).reshape(m.kv_lora_rank, h, m.qk_nope_head_dim),
        "w_uv": dense_init(ks[5], m.kv_lora_rank, h * m.v_head_dim, dt
                           ).reshape(m.kv_lora_rank, h, m.v_head_dim),
        "wo": dense_init(ks[6], h * m.v_head_dim, d, dt),
    }


# ---------------------------------------------------------------------------
# YaRN (DeepSeek-V2's rope_scaling; its modeling code's yarn_* helpers)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, y: YarnConfig) -> np.ndarray:
    """(dim/2,) rotary frequencies: the original ones for the fast
    dimensions, divided by ``factor`` for the slow ones, and a linear ramp
    between, over ``original_max_position_embeddings``."""
    def corr(rot):                                  # yarn_find_correction_dim
        return dim * math.log(y.original_max_position_embeddings
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    return (extra / y.factor * ramp + extra * (1.0 - ramp)).astype(
        np.float32)


def _mla_rope(cfg: ArchConfig):
    """(inv_freq or None, factor on the rotated dims, softmax scale)."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = m.yarn
    if y is None:
        return None, 1.0, scale
    mall = yarn_mscale(y.factor, y.mscale_all_dim)
    return (jnp.asarray(yarn_inv_freq(m.qk_rope_head_dim, cfg.rope_theta,
                                      y)),
            yarn_mscale(y.factor, y.mscale) / mall, scale * mall * mall)


def _rms(x, scale):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + 1e-6)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _mla_tp_shard(absorbed, q_nope, q_rope, w_uk, w_uv, ckv, kr, kv_len,
                  h: int):
    """Run the absorbed-decode attention, split over query heads when a
    serving TP context is active (identity dispatch otherwise). Inputs
    with a head axis (q_nope/q_rope dim 2, w_uk/w_uv dim 1) split over
    tp; the latent streams stay replicated. The per-shard output head
    block is pinned back to replicated — an exact concat — before the
    shared wo projection (DESIGN.md §14)."""
    tp_ctx = current_serve_tp()
    if tp_ctx is None:
        return absorbed(q_nope, q_rope, w_uk, w_uv, ckv, kr, kv_len)
    mesh, tp_axes = tp_ctx
    ts = 1
    for a in tp_axes:
        ts *= mesh.shape[a]
    if ts == 1 or h % ts:
        return absorbed(q_nope, q_rope, w_uk, w_uv, ckv, kr, kv_len)
    from jax.sharding import NamedSharding, PartitionSpec as P
    tp = tp_axes[0] if len(tp_axes) == 1 else tp_axes
    f = jax.shard_map(absorbed, mesh=mesh,
                      in_specs=(P(None, None, tp, None),
                                P(None, None, tp, None),
                                P(None, tp, None), P(None, tp, None),
                                P(), P(), P()),
                      out_specs=P(None, None, tp, None),
                      axis_names=set(tp_axes), check_vma=False)
    out = f(q_nope, q_rope, w_uk, w_uv, ckv, kr, kv_len)
    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, P()))


def apply_mla(p, x, cfg: ArchConfig, *, positions, cache=None,
              cache_index=None, return_cache=False, page_table=None):
    """Latent attention under the name scope ``repro.attn.mla``
    (projections and attention; backward ops carry it too)."""
    with jax.named_scope("repro.attn.mla"):
        return _apply_mla(p, x, cfg, positions=positions, cache=cache,
                          cache_index=cache_index, return_cache=return_cache,
                          page_table=page_table)


def _apply_mla(p, x, cfg: ArchConfig, *, positions, cache, cache_index,
               return_cache, page_table):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    nope, rp, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    inv_freq, rope_mul, scale = _mla_rope(cfg)

    def rope(t):
        t = apply_rope(t, positions, cfg.rope_theta, inv_freq=inv_freq)
        return t if rope_mul == 1.0 else t * jnp.asarray(rope_mul, t.dtype)

    if m.q_lora_rank is None:
        q = jnp.einsum("bsd,de->bse", x, p["w_q"])
    else:
        cq = _rms(jnp.einsum("bsd,dl->bsl", x, p["w_dq"]), p["q_norm"])
        q = jnp.einsum("bsl,le->bse", cq, p["w_uq"])
    q = q.reshape(b, s, h, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:])

    ckv_new = _rms(jnp.einsum("bsd,dl->bsl", x, p["w_dkv"]), p["kv_norm"])
    kr_new = rope(jnp.einsum("bsd,dr->bsr", x, p["w_kr"])[:, :, None, :]
                  )[:, :, 0, :]

    if cache is not None and cache_index is not None:
        # absorbed decode: score in latent space, never materialize K/V.
        # The latent cache pages exactly like KV: one (rank,)/(rope,) row
        # per token (jnp gather path; TPU kernel coverage is GQA's).
        if "ckv_pages" in cache:
            lens = cache_index
            ps = cache["ckv_pages"].shape[1]
            ckv_p = _paged_append(cache["ckv_pages"], ckv_new[:, 0],
                                  page_table, lens, ps)
            kr_p = _paged_append(cache["kr_pages"], kr_new[:, 0],
                                 page_table, lens, ps)
            ckv = _paged_read(ckv_p, page_table)     # (B, Pmax*PS, rank)
            kr = _paged_read(kr_p, page_table)
            kv_len = lens + 1
            new_cache = {"ckv_pages": ckv_p, "kr_pages": kr_p}
        elif jnp.ndim(cache_index):
            idx = cache_index
            ckv = cache["ckv"].at[jnp.arange(b), idx].set(
                ckv_new[:, 0].astype(cache["ckv"].dtype))
            kr = cache["kr"].at[jnp.arange(b), idx].set(
                kr_new[:, 0].astype(cache["kr"].dtype))
            kv_len = idx + 1
            new_cache = {"ckv": ckv, "kr": kr}
        else:
            idx = cache_index
            ckv = jax.lax.dynamic_update_slice_in_dim(
                cache["ckv"], ckv_new, idx, axis=1)
            kr = jax.lax.dynamic_update_slice_in_dim(
                cache["kr"], kr_new, idx, axis=1)
            kv_len = jnp.broadcast_to(idx + 1, (b,))
            new_cache = {"ckv": ckv, "kr": kr}
        t = ckv.shape[1]
        cdt = x.dtype

        def _absorbed(qn, qr, wuk, wuv, ckv_, kr_, kl):
            q_abs = jnp.einsum("bshn,lhn->bshl", qn, wuk)
            s_ = (jnp.einsum("bshl,btl->bhst", q_abs, ckv_)
                  + jnp.einsum("bshr,btr->bhst", qr, kr_)
                  ).astype(jnp.float32) * scale
            mask = jnp.arange(t)[None, :] < kl[:, None]
            s_ = jnp.where(mask[:, None, None, :], s_, NEG_INF)
            w = jax.nn.softmax(s_, axis=-1).astype(cdt)
            out_lat = jnp.einsum("bhst,btl->bshl", w, ckv_)
            return jnp.einsum("bshl,lhv->bshv", out_lat, wuv)

        # serving TP (DESIGN.md §14): MLA's latent pools are rank-
        # compressed and headless (replicated); the absorbed-decode
        # *compute* splits over query heads instead — per-head math has
        # no cross-head reduction until wo, so the split and the gather
        # back to replicated are both exact
        out = _mla_tp_shard(_absorbed, q_nope, q_rope, p["w_uk"],
                            p["w_uv"], ckv, kr, kv_len, h)
    else:
        # train / prefill: materialize per-head K,V (flash-compatible)
        t = s
        k_nope = jnp.einsum("btl,lhn->bthn", ckv_new, p["w_uk"])
        v = jnp.einsum("btl,lhv->bthv", ckv_new, p["w_uv"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_new[:, :, None, :], (b, t, h, rp))],
            axis=-1)
        q_full = constrain(jnp.concatenate([q_nope, q_rope], axis=-1),
                           "heads4")
        k = constrain(k, "heads4")
        v = constrain(v, "heads4")
        out = attention_math(q_full, k, v, causal=True, scale=scale)
        new_cache = {"ckv": ckv_new, "kr": kr_new} if return_cache else None

    y = jnp.einsum("bse,ed->bsd",
                   out.reshape(b, s, h * vd).astype(x.dtype), p["wo"])
    return y, new_cache
