"""Pallas TPU flash attention (forward).

Blockwise online-softmax: grid (B, H, S/bq, T/bk); m/l/acc accumulate in
VMEM scratch across the (arbitrary-semantics) kv grid dimension, so the
(S,T) score matrix never leaves VMEM. Block shapes are MXU-aligned
(multiples of 128 on the matmul dims).

TPU is the target; correctness is validated on CPU via ``interpret=True``
against the pure-jnp oracle in ``ref.py`` (see tests/test_kernels_flash.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, scale: float, block_q: int, block_k: int,
            num_kv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == num_kv - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q,k: (B,H,S,D); v: (B,H,S,Dv). Returns (B,H,S,Dv)."""
    b, h, s, d = q.shape
    dv = v.shape[-1]
    t = k.shape[2]
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    nq, nk = s // block_q, t // block_k
    scale = d ** -0.5

    kern = functools.partial(_kernel, causal=causal, scale=scale,
                             block_q=block_q, block_k=block_k, num_kv=nk)

    grid = (b, h, nq, nk)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
        pl.BlockSpec((1, 1, block_k, dv), lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
    ]
    out_spec = pl.BlockSpec((1, 1, block_q, dv),
                            lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    scratch_shapes = [
        pltpu.VMEM((block_q,), jnp.float32),
        pltpu.VMEM((block_q,), jnp.float32),
        pltpu.VMEM((block_q, dv), jnp.float32),
    ]

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        **kwargs,
    )(q, k, v)
