"""Pallas TPU kernels for the non-trivial GradAgg rules (DESIGN.md §6/§11).

All three operate on the device-resident ``(n, P)`` f32 gradient ledger
tiled along P (the agent axis n is small — tens of agents — and rides
whole in every block):

- :func:`masked_cge_reduce`   per-agent norms + CGE keep-set + masked sum
  in one ``pallas_call`` (two sequential grid phases over the same
  tiles); the keep-set math is ``gradagg.cge_mask_from_norms`` semantics
  (stable rank over received-masked norms) re-expressed rank-wise so no
  sort runs on device.
- :func:`trimmed_mean_tiled`  coordinate-wise trimmed mean via f rounds
  of running min/max extraction over the agent axis — for small f this
  replaces ``jnp.sort``'s materialized (n, P) sorted copy with O(f)
  reduction sweeps of the tile held in VMEM.
- :func:`dequant_accum`       int8 payload x per-agent scale accumulated
  in f32 (the quantized rule's server-side reduction; the int8 stack is
  read once, never materialized dequantized).

Validated against the ``gradagg`` oracles in interpret mode
(``tests/test_kernels_agg.py``); dispatched via ``kernels/ops.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e30          # matches gradagg.BIG (received-masking sentinel)
TILE = 2048         # column tile; ledgers allocate P rounded up to it


def _pad_cols(x, tile: int):
    """Zero-pad the last axis to a tile multiple (padding columns are
    harmless for every rule: zero squared-norm contribution, and callers
    slice the output back to P). A ledger is allocated at a tile multiple
    (``core.ledger.ledger_width``), so on the server path this is free."""
    pad = (-x.shape[-1]) % tile
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x


def _seq_params(interpret: bool, ndims: int):
    if not interpret:
        return {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * ndims)}
    return {}


# ---------------------------------------------------------------------------
# CGE: norms + keep-set + masked sum, one pass structure


def masked_cge_reduce(g, received, f: int, *, tile: int = TILE,
                      interpret: bool = False):
    """g: (n, P) f32, received: (n,) bool -> (P,) f32 — sum of the m-f
    smallest-norm received gradients (CGE filter, paper eq. (18)).

    Grid (2, P/tile), fully sequential: phase 0 accumulates per-agent
    squared norms tile-by-tile into a revisited (n, 1) output block
    (resident in VMEM the whole call); phase 1 derives the keep-set once
    per tile — rank(i) = #{j : key_j < key_i or (key_j == key_i and
    j < i)} reproduces the stable argsort of ``cge_mask_from_norms``
    without sorting — and writes the masked sum. The stack streams from
    HBM twice but no sorted/f32-upcast copy is ever materialized.
    """
    n, p = g.shape
    g2 = _pad_cols(g, tile)
    nt = g2.shape[1] // tile
    recv = received.reshape(n, 1).astype(jnp.float32)

    def kernel(recv_ref, g_ref, o_ref, nsq_ref):
        ph = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when((ph == 0) & (j == 0))
        def _init():
            nsq_ref[...] = jnp.zeros_like(nsq_ref)

        @pl.when(ph == 0)
        def _norms():
            x = g_ref[...].astype(jnp.float32)
            nsq_ref[...] += jnp.sum(x * x, axis=1, keepdims=True)
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(ph == 1)
        def _reduce():
            rx = recv_ref[...] > 0                        # (n, 1)
            # rank the f32 sqrt-norm, not the squared norm: the oracle
            # keys on jnp.linalg.norm, and two distinct nsq values can
            # round to the same f32 norm — squared-norm ranking would
            # break such a tie differently and flip the m-f cut
            key = jnp.where(rx, jnp.sqrt(nsq_ref[...]), jnp.inf)[:, 0]
            m = jnp.sum(rx.astype(jnp.int32))
            ii = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            jj = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
            a, b = key[:, None], key[None, :]
            before = (b < a) | ((b == a) & (jj < ii))
            rank = jnp.sum(before.astype(jnp.int32), axis=1)
            keep = ((rank < m - f) & rx[:, 0]).astype(jnp.float32)
            o_ref[...] = jnp.sum(
                g_ref[...].astype(jnp.float32) * keep[:, None],
                axis=0, keepdims=True)

    out, _ = pl.pallas_call(
        kernel,
        grid=(2, nt),
        in_specs=[
            pl.BlockSpec((n, 1), lambda ph, j: (0, 0)),
            pl.BlockSpec((n, tile), lambda ph, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile), lambda ph, j: (0, j)),
            pl.BlockSpec((n, 1), lambda ph, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, g2.shape[1]), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
        **_seq_params(interpret, 2),
    )(recv, g2)
    return out[0, :p]


# ---------------------------------------------------------------------------
# coordinate-wise trimmed mean via running min/max extraction


def _running_cut(lo, hi, f: int):
    """Sum of the f smallest + f largest entries per column of ``lo``/
    ``hi`` (received-masked to +/-BIG), extracted one occurrence per
    round, first occurrence by agent id — exactly sort semantics under
    duplicates. Pure jnp: shared by the Pallas kernel body and the
    portable twin so the tie-break logic exists once."""
    n = lo.shape[0]
    ids = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 0)
    cut = jnp.zeros(lo.shape[1:], lo.dtype)
    for _ in range(f):                                    # static, small f
        mn = jnp.min(lo, axis=0)
        mx = jnp.max(hi, axis=0)
        cut += mn + mx
        first_mn = jnp.min(jnp.where(lo == mn[None, :], ids, n), axis=0)
        lo = jnp.where(ids == first_mn[None, :], BIG, lo)
        first_mx = jnp.min(jnp.where(hi == mx[None, :], ids, n), axis=0)
        hi = jnp.where(ids == first_mx[None, :], -BIG, hi)
    return cut


def trimmed_mean_tiled(g, received, f: int, *, tile: int = TILE,
                       interpret: bool = False):
    """g: (n, P) f32, received: (n,) bool -> (P,) f32 — per coordinate,
    drop the f largest and f smallest received values, average the rest
    (Yin et al.). For small f, f rounds of (min, max) extraction over
    the agent axis replace the full per-coordinate sort:

        trimmed_sum = sum(received) - sum_{k<f} k-th min - k-th max

    Extraction removes exactly one occurrence per round (first by agent
    id), matching sort semantics under duplicates. Coordinates with
    m - 2f <= 0 yield 0, exactly like the oracle's empty keep window.
    """
    n, p = g.shape
    g2 = _pad_cols(g, tile)
    nt = g2.shape[1] // tile
    recv = received.reshape(n, 1).astype(jnp.float32)

    def kernel(recv_ref, g_ref, o_ref):
        rx = recv_ref[...] > 0                            # (n, 1)
        x = g_ref[...].astype(jnp.float32)                # (n, tile)
        m = jnp.sum(rx.astype(jnp.int32))
        ssum = jnp.sum(jnp.where(rx, x, 0.0), axis=0)
        cut = _running_cut(jnp.where(rx, x, BIG),
                           jnp.where(rx, x, -BIG), f)
        cnt = m - 2 * f
        num = jnp.where(cnt > 0, ssum - cut, 0.0)
        o_ref[...] = (num / jnp.maximum(cnt, 1).astype(jnp.float32))[None]

    out = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((n, 1), lambda j: (0, 0)),
            pl.BlockSpec((n, tile), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, g2.shape[1]), jnp.float32),
        interpret=interpret,
        **_seq_params(interpret, 1),
    )(recv, g2)
    return out[0, :p]


def masked_sum_dot(g, received):
    """Masked agent-axis sum as a (n,) @ (n, P) matvec — the BLAS/MXU
    row reduction is severalfold faster than mask-multiply + reduce on
    every backend and is the production form of the sum/mean device
    twins (same math as ``gradagg.agg_sum``; accumulation order differs,
    so the f64 host reference stays the conformance bit stream). HIGHEST
    precision: a TPU's default f32 matmul rounds its inputs to bf16."""
    return jnp.dot(received.astype(jnp.float32), g.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def row_norms(g):
    """Per-agent (row) L2 norms of a flat ledger block, f32 accumulation.
    Row-local, so it is exact on a dp-sharded ledger's ``(n_loc, P)``
    block — the sharded CGE path computes these locally and all-reduces
    only the (n,) norm vector (DESIGN.md §14)."""
    gf = g.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(gf * gf, axis=1))


def masked_cge_dot(g, received, f: int):
    """Portable production form of the CGE reduction: per-agent norms,
    the shared ``cge_mask_from_norms`` keep-set, then the masked matvec
    — the non-TPU twin of :func:`masked_cge_reduce`."""
    from repro.core.gradagg import cge_mask_from_norms  # shared keep-set
    keep = cge_mask_from_norms(row_norms(g), received, f)
    return masked_sum_dot(g, keep)


def trimmed_mean_running(g, received, f: int):
    """Portable jnp twin of :func:`trimmed_mean_tiled` — the same f
    rounds of min/max extraction, vectorized over the full P axis. This
    is the production non-TPU form of the rule for the fused device
    path: for small f it replaces ``jnp.sort``'s materialized (n, P)
    sorted copy with O(f) reduction sweeps, which is the algorithmic win
    independent of Pallas. The sort-based oracle stays the conformance
    ground truth (``ref.ref_trimmed_mean``)."""
    rx = received[:, None]
    x = g.astype(jnp.float32)
    m = jnp.sum(received.astype(jnp.int32))
    ssum = jnp.sum(jnp.where(rx, x, 0.0), axis=0)
    cut = _running_cut(jnp.where(rx, x, BIG), jnp.where(rx, x, -BIG), f)
    cnt = m - 2 * f
    num = jnp.where(cnt > 0, ssum - cut, 0.0)
    return num / jnp.maximum(cnt, 1).astype(jnp.float32)


# ---------------------------------------------------------------------------
# int8 dequantize + masked accumulate


def dequant_accum(q, scale, received, *, tile: int = TILE,
                  interpret: bool = False):
    """q: (n, P) int8, scale: (n,) f32, received: (n,) bool -> (P,) f32.

    The quantized rule's reduction: per-agent symmetric-int8 payloads
    times their scale, accumulated in f32 over the received set. The
    int8 stack is read once; the dequantized f32 copy never leaves
    VMEM. Scale and mask fold into one per-agent weight on the host
    side (tiny (n,) math).
    """
    n, p = q.shape
    q2 = _pad_cols(q, tile)
    nt = q2.shape[1] // tile
    w = (scale.astype(jnp.float32)
         * received.astype(jnp.float32)).reshape(n, 1)

    def kernel(w_ref, q_ref, o_ref):
        o_ref[...] = jnp.sum(
            q_ref[...].astype(jnp.float32) * w_ref[...],
            axis=0, keepdims=True)

    out = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((n, 1), lambda j: (0, 0)),
            pl.BlockSpec((n, tile), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, q2.shape[1]), jnp.float32),
        interpret=interpret,
        **_seq_params(interpret, 1),
    )(w, q2)
    return out[0, :p]
