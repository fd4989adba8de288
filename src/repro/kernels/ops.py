"""Public jit'd wrappers for the Pallas kernels.

``impl="auto"`` runs the compiled kernel when the default backend is a
TPU and the portable jnp form on any other backend (the CPU test path;
the dry-run/roofline path never routes through Pallas, see DESIGN.md §6).
``impl="interpret"`` runs the kernel in Pallas interpret mode and
``impl="ref"`` the oracle, on any backend. Nothing falls back silently:
on a TPU, "auto" always compiles the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import cge_norms as _cn
from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "impl"))
def flash_attention(q, k, v, *, causal: bool = True, impl: str = "auto"):
    """q,k: (B,H,S,D); v: (B,H,T,Dv)."""
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return _ref.ref_flash_attention(q, k, v, causal=causal)
    interpret = impl == "interpret" or not _on_tpu()
    return _fa.flash_attention(q, k, v, causal=causal, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("impl",))
def paged_decode_attention(q, k_pages, v_pages, page_table, kv_lens, *,
                           impl: str = "auto"):
    """Single-query attention over paged KV (serving decode hot path).
    q: (B,H,D); k_pages/v_pages: (N,Hkv,PS,D/Dv); page_table: (B,Pmax);
    kv_lens: (B,). Returns (B,H,Dv).

    Both implementations are KV-head grouped (head h reads KV head
    h // (H/Hkv), group lanes contiguous): the kernel grids over
    (B, Hkv, Pmax) so each page is fetched once per KV head and
    early-exits the walk after ceil(kv_len/PS) pages; the oracle scores
    the (B, Hkv, G, D) query against the un-repeated gathered KV."""
    from repro.kernels import decode_attention as _da
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return _ref.ref_paged_decode_attention(q, k_pages, v_pages,
                                               page_table, kv_lens)
    interpret = impl == "interpret" or not _on_tpu()
    return _da.paged_flash_decode(q, k_pages, v_pages, page_table, kv_lens,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("impl",))
def block_sq_norms(x, *, impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return _ref.ref_block_sq_norms(x)
    interpret = impl == "interpret" or not _on_tpu()
    return _cn.block_sq_norms(x, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("impl",))
def masked_scale(x, scale, *, impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return _ref.ref_masked_scale(x, scale)
    interpret = impl == "interpret" or not _on_tpu()
    return _cn.masked_scale(x, scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("f", "impl"))
def masked_cge_reduce(g, received, *, f: int = 0, impl: str = "auto"):
    """CGE aggregate over the (n, P) gradient ledger: per-agent norms +
    keep-set + masked sum fused (paper eq. (18))."""
    from repro.kernels import agg as _agg
    if impl == "ref":
        return _ref.ref_masked_cge_reduce(g, received, f)
    if impl == "auto" and not _on_tpu():
        return _agg.masked_cge_dot(g, received, f)   # matvec production form
    interpret = impl == "interpret" or not _on_tpu()
    return _agg.masked_cge_reduce(g, received, f, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("f", "impl"))
def trimmed_mean_tiled(g, received, *, f: int = 0, impl: str = "auto"):
    """Coordinate-wise trimmed mean over the (n, P) ledger via running
    min/max extraction (no materialized sorted copy for small f). Unlike
    the other ops, the non-TPU "auto" path is NOT the sort oracle but the
    portable jnp form of the same extraction algorithm — the win is
    algorithmic, not Pallas-specific (impl="ref" still forces the sort)."""
    from repro.kernels import agg as _agg
    if impl == "ref":
        return _ref.ref_trimmed_mean(g, received, f)
    if impl == "auto" and not _on_tpu():
        return _agg.trimmed_mean_running(g, received, f)
    interpret = impl == "interpret" or not _on_tpu()
    return _agg.trimmed_mean_tiled(g, received, f, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("impl",))
def dequant_accum(q, scale, received, *, impl: str = "auto"):
    """int8 payload x per-agent scale, masked f32 accumulation (the
    quantized rule's server-side reduction)."""
    from repro.kernels import agg as _agg
    if impl == "ref":
        return _ref.ref_dequant_accum(q, scale, received)
    if impl == "auto" and not _on_tpu():
        # matvec production form: fold scale+mask into one weight vector
        w = scale.astype(jnp.float32) * received.astype(jnp.float32)
        return w @ q.astype(jnp.float32)
    interpret = impl == "interpret" or not _on_tpu()
    return _agg.dequant_accum(q, scale, received, interpret=interpret)


def tree_bucket(tree, width: int = 2048):
    """Flatten a gradient pytree into (n_buckets, width) rows (zero-padded)
    — the layout the CGE kernels consume."""
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.bfloat16)
                            for l in jax.tree.leaves(tree)])
    n = flat.size
    rows = -(-n // width)
    pad = rows * width - n
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, width), n
