"""In-graph activation sharding: logical constraint points for the model.

The model files never name mesh axes. They call ``constrain(x, kind)``
at layout-critical points with a *logical* kind ("act", "ffn", "heads4",
"hd_tp", "logits"); an ambient ``act_policy`` context maps
each kind to a PartitionSpec over the active (dp, tp) axes, with
per-dimension divisibility fallback (an axis that does not divide the
dimension is dropped rather than poisoning the partitioner). With no
policy active — CPU smoke tests, the reference engine, shard_map bodies
on the general path — every constrain is the identity, so the same model
code runs unsharded (DESIGN.md §2).

Kinds (x layout -> pinned dims):
- ``act``        (B, S, D)      batch over dp
- ``ffn``        (B, S, F)      batch over dp, hidden F over tp
- ``heads4``     (B, S, H, hd)  batch over dp, heads over tp
- ``hd_tp``      (B, S, H, hd)  batch over dp, head_dim over tp (decode
                 cache layout: score contraction becomes a partial dot)
- ``logits``     (B, S, V)      batch over dp, vocab over tp
"""
from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

PyTree = Any
Axes = Union[None, str, Tuple[str, ...]]

_STATE = threading.local()


def _norm_axes(axes: Axes) -> Tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


class _Policy:
    __slots__ = ("dp", "tp", "sizes")

    def __init__(self, dp: Axes, tp: Axes,
                 sizes: Optional[Dict[str, int]] = None):
        self.dp = _norm_axes(dp)
        self.tp = _norm_axes(tp)
        self.sizes = dict(sizes) if sizes else None

    def fit(self, axes: Tuple[str, ...], dim: int):
        """Largest suffix-trimmed axis group whose size divides ``dim``.
        Unknown sizes are assumed divisible (production meshes pass
        ``sizes`` explicitly)."""
        axes = tuple(axes)
        while axes:
            if self.sizes is None:
                break
            prod = 1
            for a in axes:
                prod *= self.sizes.get(a, 1)
            if prod and dim % prod == 0:
                break
            axes = axes[:-1]
        if not axes:
            return None
        return axes[0] if len(axes) == 1 else axes


class act_policy:
    """Context manager installing the logical->mesh activation mapping."""

    def __init__(self, dp: Axes, tp: Axes,
                 sizes: Optional[Dict[str, int]] = None):
        self._policy = _Policy(dp, tp, sizes)

    def __enter__(self):
        stack = getattr(_STATE, "stack", None)
        if stack is None:
            stack = _STATE.stack = []
        stack.append(self._policy)
        return self._policy

    def __exit__(self, *exc):
        _STATE.stack.pop()
        return False


def current_policy() -> Optional[_Policy]:
    stack = getattr(_STATE, "stack", None)
    return stack[-1] if stack else None


def _spec_for(kind: str, shape: Tuple[int, ...], pol: _Policy):
    nd = len(shape)
    dims: list = [None] * nd
    if nd == 0:
        return P()
    dims[0] = pol.fit(pol.dp, shape[0])
    if kind == "ffn" and nd >= 3:
        dims[-1] = pol.fit(pol.tp, shape[-1])
    elif kind == "heads4" and nd == 4:
        dims[2] = pol.fit(pol.tp, shape[2])
    elif kind == "hd_tp" and nd == 4:
        dims[-1] = pol.fit(pol.tp, shape[-1])
    elif kind == "logits" and nd >= 2:
        dims[-1] = pol.fit(pol.tp, shape[-1])
    # "act": dp on the leading dim only
    return P(*dims)


def constrain(x, kind: str):
    """Pin ``x`` to the active policy's layout for ``kind`` (identity when
    no policy is active)."""
    pol = current_policy()
    if pol is None:
        return x
    return jax.lax.with_sharding_constraint(x, _spec_for(kind, x.shape, pol))


def strip_leading(specs: PyTree) -> PyTree:
    """Drop the leading (scan-stacked) dim of every PartitionSpec leaf:
    specs for ``(n_periods, ...)``-stacked params become the specs of one
    scan iteration's slice."""
    return jax.tree.map(lambda s: P(*tuple(s)[1:]), specs,
                        is_leaf=lambda s: isinstance(s, P))


def constrain_tree(tree: PyTree, specs: PyTree) -> PyTree:
    """Pin every leaf of ``tree`` to the matching PartitionSpec leaf. Used
    for the manual ZeRO-3 storage->compute gathers (the transpose of these
    constraints reduce-scatters the gradients back; DESIGN.md §2)."""
    return jax.tree.map(
        lambda x, s: x if s is None else
        jax.lax.with_sharding_constraint(x, s),
        tree, specs, is_leaf=lambda s: s is None or isinstance(s, P))
