"""Pallas TPU flash-decode over a paged KV cache (single query per seq).

The serving path stores KV in fixed-size physical pages
(``repro.serve.kv_cache``); at decode each sequence holds a page table
mapping logical pages to physical ones. Pools are head-major,
``(N, Hkv, PS, D)``, so one KV head of one page is a ``(PS, D)`` tile
whose last two dims are whole array dims — the only block shape the
TPU lowering accepts when Hkv is below the 8-row sublane tile (a
sequence-major ``(N, PS, Hkv, D)`` pool would need a ``(PS, 1, D)``
block with a second-minor extent of 1 against Hkv). The kernel grids over
**(B, Hkv, Pmax)** with a ``(G, D)`` query block per KV head
(``G = H // Hkv``): all query heads that share a KV head score against
one fetched page, so each page is moved HBM->VMEM **once per KV head**
instead of once per query head — an ``H/Hkv``-fold cut in the dominant
bandwidth term of the (memory-bound) decode. Accumulation is the same
online softmax as ``flash_attention.py``; the (G, T) score rows never
leave VMEM and no gathered/contiguous copy of the cache is ever
materialized.

Page indirection uses scalar prefetch (``pltpu.PrefetchScalarGridSpec``):
the page table and lengths are prefetched to SMEM so each KV BlockSpec's
index_map can pick the *physical* page for grid step (b, kv, p). The page
walk is additionally bounded by each sequence's **actual** used pages
``ceil(kv_len / PS)`` rather than the static Pmax: for p past the used
count the index_map clamps to the last used page — consecutive identical
block indices make the Pallas pipeline skip the copy, so trailing
all-masked pages cost neither DMA nor (via ``pl.when``) compute. Length
masking handles the ragged last page; for causal self-decode the query is
at position kv_len-1, so the length mask is exactly the causal mask
(cross-attention decode passes the memory length instead — same mask).

TPU is the target; correctness is validated on CPU via ``interpret=True``
against ``ref.ref_paged_decode_attention`` (tests/test_kernels_decode.py),
and ``tests/test_tpu_compile.py`` compiles it for a v5e chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def tp_paged_decode(q, k_pages, v_pages, page_table, kv_lens, *,
                    mesh, tp_axes=("model",), impl: str = "auto"):
    """Tensor-parallel grouped paged decode (DESIGN.md §14).

    q: (B, H, D); k_pages/v_pages: (N, Hkv, PS, D|Dv) sharded over the
    kv-head dim per ``dist.sharding.cache_specs``; page_table/kv_lens
    replicated. Invokes the grouped decode kernel per shard through
    shard_map — each shard runs the full ``(B, Hkv/tp, Pmax)`` grid on
    its contiguous KV-head block, which carries its G query heads with
    it (H/tp = G * Hkv/tp, so the head grouping is preserved exactly).
    GQA has no cross-KV-head reduction, so the head-split is *bit-exact*;
    the output is then pinned back to replicated — an exact concat — so
    the downstream ``wo`` projection runs identically to the replicated
    engine and token streams match it bit for bit.

    Falls back to the unsharded dispatcher when the tp extent is 1 or
    does not divide both H and Hkv (same trim-to-fit philosophy as
    ``MeshRules.fit``).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.kernels import ops as K     # lazy: ops imports this module

    b, h, d = q.shape
    hkv = k_pages.shape[1]
    tp_axes = tuple(tp_axes)
    ts = 1
    for a in tp_axes:
        ts *= mesh.shape[a]
    if ts == 1 or h % ts or hkv % ts:
        return K.paged_decode_attention(q, k_pages, v_pages, page_table,
                                        kv_lens, impl=impl)
    tp = tp_axes[0] if len(tp_axes) == 1 else tp_axes

    def body(q_, kp_, vp_, tbl_, l_):
        return K.paged_decode_attention(q_, kp_, vp_, tbl_, l_, impl=impl)

    # manual over every mesh axis (axes the specs omit are replicated): a
    # Mosaic kernel cannot be partitioned automatically over a leftover
    # auto axis, even one of size 1
    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(None, tp, None), P(None, tp, None, None),
                                P(None, tp, None, None), P(None, None),
                                P(None)),
                      out_specs=P(None, tp, None), check_vma=False)
    out = f(q, k_pages, v_pages, page_table, kv_lens)
    # exact gather boundary: concatenating the per-shard head blocks is
    # bit-exact, and the replicated wo matmul that follows then matches
    # the unsharded engine's reduction order
    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, P()))


def _pages_used(ln, ps: int):
    """Pages holding a length-``ln`` sequence, floored at 1 so the clamp
    ``min(p, used-1)`` always names a fetchable (masked) page."""
    return jnp.maximum(pl.cdiv(ln, ps), 1)


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, page_size: int,
            num_pages: int):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ln = len_ref[b]

    # page-walk early exit: pages past ceil(len/PS) are revisits of the
    # last used page (no DMA) and contribute nothing — skip the FLOPs too
    @pl.when(p < _pages_used(ln, page_size))
    def _accum():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (PS, D)
        v = v_ref[0, 0].astype(jnp.float32)            # (PS, Dv)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = pos < ln                     # ragged last page + causal
        s = jnp.where(valid, s, NEG_INF)     # (G, PS) via broadcast

        m_prev = m_ref[...]                            # (G, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # explicit re-mask: on an all-masked page m_new is still NEG_INF
        # and exp(s - m_new) would be 1, not 0 (the kv_len == 0 case)
        pr = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # (G, PS)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(pr, axis=1, keepdims=True)
        pv = jax.lax.dot_general(pr, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(p == num_pages - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_flash_decode(q, k_pages, v_pages, page_table, kv_lens, *,
                       interpret: bool = False):
    """q: (B,H,D); k_pages: (N,Hkv,PS,D); v_pages: (N,Hkv,PS,Dv);
    page_table: (B,Pmax) int32; kv_lens: (B,) int32. Returns (B,H,Dv).

    KV heads are grouped: head h reads KV head h // (H // Hkv), i.e. the
    (G, D) query block for KV head kv holds heads [kv*G, (kv+1)*G) —
    exactly the layout ``jnp.repeat(kv, G, axis=heads)`` expands to.
    Page-table entries past a sequence's length may be -1 or stale; they
    are clamped to 0 and masked, so the pool's page 0 doubles as the null
    page, and the walk early-exits after ceil(kv_len / PS) pages anyway.
    """
    b, h, d = q.shape
    n, hkv, ps, dv = v_pages.shape
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    pmax = page_table.shape[1]
    scale = d ** -0.5

    tbl = jnp.maximum(page_table, 0).astype(jnp.int32)
    lens = kv_lens.astype(jnp.int32)
    qg = q.reshape(b, hkv, g, d)
    kern = functools.partial(_kernel, scale=scale, page_size=ps,
                             num_pages=pmax)

    def kv_map(b_, h_, p_, tbl_, l_):
        # clamp the walk to the pages actually resident: for p >= used the
        # block index equals the previous step's, so the copy is elided
        p_eff = jnp.minimum(p_, _pages_used(l_[b_], ps) - 1)
        return (tbl_[b_, p_eff], h_, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, pmax),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda b_, h_, p_, tbl_, l_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, ps, d), kv_map),
            pl.BlockSpec((1, 1, ps, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda b_, h_, p_, tbl_, l_: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dv), jnp.float32),
        ],
    )

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dv), q.dtype),
        interpret=interpret,
        **kwargs,
    )(tbl, lens, qg, k_pages, v_pages)
    return out.reshape(b, h, dv)
