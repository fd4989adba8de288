"""shard_map-side SPMD collectives — twins of ``repro.core.gradagg``.

Every function runs inside a (full-)manual shard_map body whose data-
parallel axes are ``axes`` (e.g. ``("data",)`` or ``("pod", "data")``).
One *agent* = one dp-mesh coordinate; ``agent_index`` linearizes the dp
coordinates in row-major order, matching the agent ordering of the
reference rules and of ledgers/error trees with a leading n_agents axis
sharded over dp.

Parity with the reference engine is enforced by
``tests/helpers/parity_checks.py`` (every registry rule, 8 virtual
devices, masked ``received`` sets with |S^t| = n - r).

Design note: CGE needs the *norm order* of all agents but never the
gradients themselves, so it all-reduces one scalar per agent and reuses
``gradagg.cge_mask_from_norms`` — the keep-set math exists once.
Trimmed-mean genuinely needs the per-coordinate order statistics, so it
is the one rule that all-gathers the full per-agent stack (DESIGN.md §3
documents the n-times-memory cost).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import gradagg

PyTree = Any


# ---------------------------------------------------------------------------
# axis bookkeeping


def _axes(axes) -> Tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def axis_count(axes) -> int:
    """Number of agents = product of the dp axis sizes (static int)."""
    n = 1
    for a in _axes(axes):
        n *= jax.lax.psum(1, a)
    return n


def agent_index(axes):
    """Row-major linear agent index of this shard over the dp axes."""
    axes = _axes(axes)
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


def psum_all(x, axes):
    for a in _axes(axes):
        x = jax.lax.psum(x, a)
    return x


def _per_agent(x, axes):
    """Scatter this agent's scalar into an (n,) vector replicated on all
    shards (one all-reduce; no all-gather)."""
    n = axis_count(axes)
    onehot = (jnp.arange(n) == agent_index(axes))
    return psum_all(jnp.where(onehot, x, jnp.zeros_like(x)), axes)


def _gather_stack(x, axes):
    """All-gather a local leaf into an (n, ...) stack in agent order."""
    axes = _axes(axes)
    shape = x.shape
    for a in reversed(axes):
        x = jax.lax.all_gather(x, a)
    return x.reshape((-1,) + shape)


# ---------------------------------------------------------------------------
# norms


def tree_sq_norm(tree: PyTree):
    """Local squared L2 norm of a pytree (float32 accumulation)."""
    return sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
               for l in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# aggregation collectives


def masked_psum(tree: PyTree, w, axes) -> PyTree:
    """SPMD twin of ``agg_sum``: scale the local gradient by this agent's
    mask weight ``w`` (0.0 drops it from S^t) and all-reduce. The bulk
    aggregation costs exactly one psum regardless of the mask."""
    return jax.tree.map(
        lambda g: psum_all(g.astype(jnp.float32) * w, axes), tree)


def cge_psum(tree: PyTree, received, f: int, axes) -> Tuple[PyTree, Any]:
    """SPMD twin of ``agg_cge`` (paper eq. (18)): two phases —
    (1) all-reduce one scalar norm + received flag per agent,
    (2) every shard computes the identical keep-set from the norm order
        and the masked bulk psum aggregates the kept gradients.
    Returns (aggregate, keep (n,) bool replicated)."""
    my_norm = jnp.sqrt(tree_sq_norm(tree))
    norms = _per_agent(my_norm, axes)
    rx = _per_agent(received.astype(jnp.float32), axes) > 0
    keep = gradagg.cge_mask_from_norms(norms, rx, f)
    w = keep[agent_index(axes)].astype(jnp.float32)
    return masked_psum(tree, w, axes), keep


def trimmed_mean_all(tree: PyTree, received, f: int, axes) -> PyTree:
    """SPMD twin of ``agg_trimmed_mean``: gathers the full (n, ...) stack
    (coordinate-wise order statistics need every agent's value) and runs
    the reference rule on it — already a mean over the kept entries."""
    rx = _per_agent(received.astype(jnp.float32), axes) > 0
    stacked = jax.tree.map(
        lambda g: _gather_stack(g.astype(jnp.float32), axes), tree)
    return gradagg.tree_agg(partial(gradagg.agg_trimmed_mean, f=f),
                            stacked, rx)


# ---------------------------------------------------------------------------
# sharded-ledger helpers (DESIGN.md §14)
#
# The dp-sharded GradLedger stores each shard's n/dp agent rows as a
# local ``(n_loc, P)`` block; ``ledger_all_rows`` rebuilds the full
# row-major ``(n, ...)`` array inside a shard_map body. The rebuild is a
# zero-pad + ONE psum: every summand is either the original row bits or
# exact 0.0, and ``x + 0.0`` is exact in IEEE-754, so the reconstruction
# is *bit-identical* to the unsharded array — which is what lets the
# ``combine="gather"`` conformance mode of the sharded ledger reproduce
# the PR 4 single-buffer device path bit for bit. (Shard-local partial
# reductions + psum are NOT bit-identical — f32 addition is
# non-associative — which is why they are the tolerance-checked
# ``combine="partial"`` production mode instead.)


def shard_row_slice(axes, n: int) -> Tuple[Any, int]:
    """(first row index, row count) of this shard's ledger block."""
    n_shards = axis_count(axes)
    if n % n_shards:
        raise ValueError(f"n_agents={n} not divisible by {n_shards} shards")
    n_loc = n // n_shards
    return agent_index(axes) * n_loc, n_loc


def ledger_all_rows(x_loc, axes, n: int):
    """Rebuild the full row-major ``(n, ...)`` array from this shard's
    ``(n_loc, ...)`` row block (bit-exact: one psum of a zero-padded
    block, no all-gather)."""
    row0, n_loc = shard_row_slice(axes, n)
    if x_loc.shape[0] != n_loc:
        raise ValueError(
            f"local row block has {x_loc.shape[0]} rows, want {n_loc}")
    full = jnp.zeros((n,) + x_loc.shape[1:], x_loc.dtype)
    full = jax.lax.dynamic_update_slice_in_dim(full, x_loc, row0, axis=0)
    return psum_all(full, axes)


def quantized_psum(tree: PyTree, w, err: PyTree, axes
                   ) -> Tuple[PyTree, PyTree]:
    """SPMD twin of ``agg_quantized`` with error feedback: add the carried
    residual, quantize the whole local gradient to int8 against one
    per-agent scale (wire format: 1 byte/param + one f32 scale), psum the
    dequantized masked contributions, and keep the new residual locally.
    Masked-out agents (w == 0) fold the whole unsent gradient-plus-residual
    into the carried residual, so no information is dropped.
    Returns (aggregate, new_err)."""
    leaves, treedef = jax.tree.flatten(tree)
    err_leaves = jax.tree.leaves(err)
    x = [g.astype(jnp.float32) + e.astype(jnp.float32)
         for g, e in zip(leaves, err_leaves)]
    amax = jnp.max(jnp.stack([jnp.max(jnp.abs(l)) for l in x]))
    scale = jnp.maximum(amax / 127.0, 1e-30)
    agg, new_err = [], []
    for l in x:
        q = jnp.clip(jnp.round(l / scale), -127.0, 127.0)
        deq = q * scale
        agg.append(psum_all(deq * w, axes))
        new_err.append(jnp.where(w > 0, l - deq, l))
    return (jax.tree.unflatten(treedef, agg),
            jax.tree.unflatten(treedef, new_err))
