"""Pallas TPU kernel: fused per-bucket squared norms + masked scaling.

The CGE filter's first phase needs ||g_j||^2 over a (possibly huge)
gradient. On TPU we bucket the flattened gradient into (n_buckets, bucket)
rows and reduce each row in VMEM (one pass, fp32 accumulation, no
materialized f32 upcast of the bf16 gradient). The second phase scales the
gradient by a per-agent keep/drop weight — fused into the same pass shape.

Validated in interpret mode against ``ref.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _norm_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    o_ref[0, 0] = jnp.sum(x * x)


def block_sq_norms(x, *, block: int = 2048, interpret: bool = False):
    """x: (n_buckets, width) -> (n_buckets,) fp32 squared norms.

    Grid: (n_buckets, width/block); per-bucket partial sums accumulate into
    the same output element (revisited across the inner grid dim).
    """
    n, w = x.shape
    block = min(block, w)
    assert w % block == 0, (w, block)
    nb = w // block

    def kernel(x_ref, o_ref):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            o_ref[0, 0] = jnp.zeros((), jnp.float32)

        xb = x_ref[...].astype(jnp.float32)
        o_ref[0, 0] = o_ref[0, 0] + jnp.sum(xb * xb)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        grid=(n, nb),
        in_specs=[pl.BlockSpec((1, block), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(x)
    return out[:, 0]


def masked_scale(x, scale, *, block: int = 2048, interpret: bool = False):
    """x: (n_buckets, width), scale: (n_buckets,) -> x * scale[:, None].

    The CGE phase-2 masked contribution (keep/drop weights per bucket),
    fused so dropped buckets never leave VMEM at full precision.
    """
    n, w = x.shape
    block = min(block, w)
    assert w % block == 0
    nb = w // block

    def kernel(x_ref, s_ref, o_ref):
        o_ref[...] = (x_ref[...].astype(jnp.float32)
                      * s_ref[0, 0]).astype(o_ref.dtype)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid=(n, nb),
        in_specs=[
            pl.BlockSpec((1, block), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, w), x.dtype),
        interpret=interpret,
        **kwargs,
    )(x, scale.reshape(n, 1))
