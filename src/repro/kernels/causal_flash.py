"""Causal GQA flash attention with a backward pass: the training step's
and prefill's self-attention on TPU.

The kernel is the installed JAX's splash attention
(``jax.experimental.pallas.ops.tpu.splash_attention``): a forward kernel
and a fused backward kernel (dq, dk and dv) under one ``custom_vjp``, with
f32 softmax statistics, query heads grouped over ``Hkv`` KV heads
(``H % Hkv == 0``, so K and V are never repeated to ``H``) and the
all-masked blocks above the causal diagonal skipped in all three. The
(S, S) score matrix never leaves VMEM.

This module only adapts the model's layout: q (B, S, H, D), k and v
(B, S, Hkv, D) go head-major, q takes the ``D**-0.5`` scale (splash does
not scale), and the per-sequence kernel is ``vmap``'d over B. The
kernel object (block sizes and the causal mask's block tables) is built
once per shape and cached; its tables are concrete arrays, so a cached
kernel is safe to reuse across traces.

Validated on CPU via ``interpret=True`` against
``ref.ref_causal_gqa_attention`` (tests/test_kernels_flash.py), and
``tests/test_tpu_compile.py`` compiles forward and backward for a v5e
chip.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental.pallas.ops.tpu import splash_attention as splash


def block_sizes(s: int) -> splash.BlockSizes:
    """Tiles as a function of the shape: square blocks (block_q =
    block_kv = the KV compute tile), the largest of 512, 256 and 128 rows
    that divides S, and the fused backward (one kernel computes dq, dk
    and dv from one recompute of each score block). On a v5e at the
    training shape (24, 1024, 14/2, 64) this beat 128-1024 blocks, 256
    compute tiles and the separate dq and dkv kernels (PERF.md, §6)."""
    b = next(c for c in (512, 256, 128) if s % c == 0)
    return splash.BlockSizes(block_q=b, block_kv=b, block_q_dkv=b,
                             block_kv_dkv=b, use_fused_bwd_kernel=True)


@functools.lru_cache(maxsize=None)
def _kernel(s: int, h: int, interpret: bool):
    mask = splash.MultiHeadMask([splash.CausalMask((s, s))] * h)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha_single_device(
            mask, block_sizes=block_sizes(s), interpret=interpret)


def causal_gqa_flash(q, k, v, *, interpret: bool = False):
    """q: (B, S, H, D); k, v: (B, S, Hkv, D), H % Hkv == 0, S a multiple
    of 128. Returns (B, S, H, D) in q.dtype."""
    b, s, h, d = q.shape
    kernel = _kernel(s, h, interpret)
    qt = (q * d ** -0.5).transpose(0, 2, 1, 3)         # weak-typed: keeps dtype
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = jax.vmap(kernel)(qt, kt, vt)                 # (B, H, S, D)
    return out.transpose(0, 2, 1, 3)
