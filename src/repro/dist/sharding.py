"""Logical-axis -> mesh-axis resolution and PartitionSpec derivation.

``MeshRules`` names the mesh axes playing each *role* (fsdp / tp / ep /
dp) plus the axis sizes; ``tree_specs`` / ``batch_specs`` / ``cache_specs``
walk pytrees and emit PartitionSpecs with per-dimension divisibility
fallback: a candidate axis group whose size does not divide the dimension
is trimmed from the right (fsdp axes drop before tp axes) until it fits,
so no spec ever poisons the partitioner with an uneven split.

Two standard layouts (DESIGN.md §2):

- **storage** (default rules): ZeRO-3 — each weight's natural tp dim is
  sharded over ``tp_axes`` *and* ``fsdp_axes`` stacked on the same dim
  (e.g. ``P(None, None, ("model", "data"))``). Optimizer moments mirror
  their parameter, so the same rules apply to the whole train state.
- **compute** (``fsdp_axes=()``): plain tensor-parallel layout the matmuls
  run in; the manual gather storage->compute is a ``constrain_tree`` in
  the step (its transpose reduce-scatters gradients back).

Name-based placement:
- column weights (w_gate/w_up/wq/...): fan-out (last) dim <- tp+fsdp
- row weights (w_down/w_out/wo): fan-in <- tp, fan-out <- fsdp
- kv projections (wk/wv): fan-out <- fsdp only — repeat-KV layout keeps
  them replicated over tp (kv_heads never divide the model axis)
- MoE expert stacks (4D under "ffn"): expert dim <- ep, last <- fsdp
- embeddings ("tok"): vocab <- fsdp, d_model <- tp
- norms / biases / scalars: replicated
"""
from __future__ import annotations

import dataclasses
import fnmatch
import threading
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

PyTree = Any

# leaves replicated regardless of shape (norm scales, biases, timestamps)
_REPLICATED = {"scale", "bias", "b_in", "b_out", "bq", "bk", "bv",
               "q_norm", "kv_norm", "step", "ts", "u"}
_ROW = {"w_down", "w_out", "wo"}          # row-parallel second matrices
_KV = {"wk", "wv"}                        # repeat-KV projections
_EMBED_POS = {"pos", "enc_pos"}


def _default_sizes(multi_pod: bool) -> Dict[str, int]:
    sizes = {"data": 16, "model": 16}
    if multi_pod:
        sizes["pod"] = 2
    return sizes


@dataclasses.dataclass
class MeshRules:
    """Role -> mesh-axis mapping with divisibility-aware spec building."""
    fsdp_axes: Tuple[str, ...] = ("data",)
    tp_axes: Tuple[str, ...] = ("model",)
    ep_axes: Optional[Tuple[str, ...]] = None
    dp_axes: Optional[Tuple[str, ...]] = None
    axis_sizes: Optional[Dict[str, int]] = None
    multi_pod: bool = False
    overrides: Optional[Dict[str, P]] = None

    def __post_init__(self):
        self.fsdp_axes = tuple(self.fsdp_axes)
        self.tp_axes = tuple(self.tp_axes)
        if self.axis_sizes is None:
            self.axis_sizes = _default_sizes(self.multi_pod)
        if self.ep_axes is None:
            self.ep_axes = self.tp_axes
        self.ep_axes = tuple(self.ep_axes)
        if self.dp_axes is None:
            self.dp_axes = (("pod", "data")
                            if (self.multi_pod or "pod" in self.axis_sizes)
                            else ("data",))
        self.dp_axes = tuple(self.dp_axes)
        self.overrides = dict(self.overrides or {})

    # ------------------------------------------------------------------
    @property
    def dp(self) -> Tuple[str, ...]:
        return self.dp_axes

    def size(self, axes: Tuple[str, ...]) -> int:
        n = 1
        for a in axes:
            n *= self.axis_sizes.get(a, 1)
        return n

    def fit(self, axes: Tuple[str, ...], dim: int):
        """Trim ``axes`` from the right until their product divides ``dim``
        (fsdp drops before tp by construction of every caller's ordering).
        Returns a spec entry: None, a single axis name, or a tuple."""
        axes = tuple(axes)
        while axes and (self.size(axes) == 0 or dim % self.size(axes)):
            axes = axes[:-1]
        if not axes:
            return None
        return axes[0] if len(axes) == 1 else axes


# ---------------------------------------------------------------------------
# serving TP context (DESIGN.md §14)
#
# The model files never see a mesh. Like ``act_sharding.act_policy``, the
# serving engine installs an ambient (mesh, tp_axes) context around its
# jitted closures' *tracing*; the paged decode branches in
# ``models/attention.py`` consult it and route the attention math through
# the per-shard kernel wrapper in ``kernels/decode_attention.py``. With
# no context active — the replicated conformance engine — the model code
# is byte-identical to PR 5/6 behavior.

_SERVE_TP = threading.local()


class serve_tp:
    """Context manager installing the serving tensor-parallel mesh."""

    def __init__(self, mesh, tp_axes: Tuple[str, ...] = ("model",)):
        self._val = (mesh, tuple(tp_axes))

    def __enter__(self):
        stack = getattr(_SERVE_TP, "stack", None)
        if stack is None:
            stack = _SERVE_TP.stack = []
        stack.append(self._val)
        return self._val

    def __exit__(self, *exc):
        _SERVE_TP.stack.pop()
        return False


def current_serve_tp() -> Optional[Tuple[Any, Tuple[str, ...]]]:
    """(mesh, tp_axes) of the active serving TP context, or None."""
    stack = getattr(_SERVE_TP, "stack", None)
    return stack[-1] if stack else None


# ---------------------------------------------------------------------------
# path helpers


def _path_names(path) -> Tuple:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        elif hasattr(k, "name"):
            names.append(str(k.name))
        else:
            names.append(str(k))
    return tuple(names)


def _used(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


# ---------------------------------------------------------------------------
# parameter / state specs


def _param_spec(names: Tuple[str, ...], shape: Tuple[int, ...],
                rules: MeshRules) -> P:
    nd = len(shape)
    name = ""
    for n in reversed(names):
        if not n.isdigit():
            name = n
            break
    if nd == 0:
        return P()
    dims: list = [None] * nd
    if name in _REPLICATED:
        return P(*dims)
    is_expert = nd == 4 and "ffn" in names and name not in ("shared", "dense")
    if is_expert:
        # experts over EP; remaining big dim ZeRO'd over whatever is free
        dims[1] = rules.fit(rules.ep_axes, shape[1])
        free = tuple(a for a in rules.fsdp_axes if a not in _used(dims[1]))
        dims[-1] = rules.fit(free, shape[-1])
    elif name == "tok":
        dims[0] = rules.fit(rules.fsdp_axes, shape[0])
        if nd > 1:
            free = tuple(a for a in rules.tp_axes if a not in _used(dims[0]))
            dims[-1] = rules.fit(free, shape[-1])
    elif name in _EMBED_POS:
        dims[0] = rules.fit(rules.fsdp_axes, shape[0])
    elif nd >= 2 and name in _ROW:
        dims[-2] = rules.fit(rules.tp_axes, shape[-2])
        free = tuple(a for a in rules.fsdp_axes if a not in _used(dims[-2]))
        dims[-1] = rules.fit(free, shape[-1])
    elif nd >= 2 and name in _KV and ("mixer" in names or "cross" in names):
        # repeat-KV layout: never tp-shard the (small) kv fan-out
        dims[-1] = rules.fit(rules.fsdp_axes, shape[-1])
    elif nd >= 2:
        # column weights: fan-out over tp+fsdp stacked on one dim; fan-in
        # dims are never data-sharded (partitioner poison, see DESIGN.md §2)
        dims[-1] = rules.fit(rules.tp_axes + rules.fsdp_axes, shape[-1])
    return P(*dims)


def tree_specs(tree: PyTree, rules: MeshRules) -> PyTree:
    """PartitionSpec tree for a param / train-state pytree. Optimizer
    moments and ledgers are classified by the same trailing path names as
    the parameters they mirror."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = []
    for path, leaf in leaves:
        names = _path_names(path)
        spec = None
        joined = "/".join(names)
        for pat, ov in rules.overrides.items():
            if fnmatch.fnmatch(joined, pat):
                spec = ov
                break
        if spec is None:
            spec = _param_spec(names, tuple(leaf.shape), rules)
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# batch / cache specs


def batch_specs(rules: MeshRules, batch: PyTree) -> PyTree:
    """Global-batch inputs: leading (batch) dim over the dp axes, the rest
    replicated; indivisible batch dims fall back to replicated."""
    def spec(leaf):
        shape = tuple(leaf.shape)
        if not shape:
            return P()
        dims = [None] * len(shape)
        dims[0] = rules.fit(rules.dp_axes, shape[0])
        return P(*dims)

    return jax.tree.map(spec, batch)


def cache_specs(rules: MeshRules, cache: PyTree,
                n_query_heads: Optional[int] = None) -> PyTree:
    """Decode/prefill KV & SSM caches.

    Dense caches, layout ``(n_periods, batch, ...)``: batch over dp, the
    trailing (head_dim / state) dim over tp so long caches fit per
    device; the scan-stacked leading dim stays replicated.

    Paged pools (leaf names ``*_pages``, DESIGN.md §14): the page pool is
    a *global* resource indexed by the shared page table — never batch-
    sharded. GQA ``k_pages``/``v_pages`` ``(n_periods, N, n_kv, PS, hd)``
    shard the kv-head dim over tp (attention has no cross-kv-head
    reduction, so the tp split is exact and the grouped decode kernel's
    ``(B, Hkv, Pmax)`` grid splits per shard); MLA latent pools
    (``ckv_pages``/``kr_pages``) replicate — they are rank-compressed
    (that is MLA's point) and carry no head axis; the compute shards
    over query heads instead.

    The kv-head split must mirror ``tp_paged_decode``'s dispatch exactly:
    the kernel takes its sharded path only when the *full* tp extent
    divides both Hkv and the query-head count H, else it falls back to
    the unsharded dispatcher — and tp-sharded pools under a fallback
    kernel would silently all-gather every decode step. Pass
    ``n_query_heads`` (the model's H) so the predicate can match; when
    unknown (None) only the Hkv condition applies."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache)
    specs = []
    for path, leaf in leaves:
        names = _path_names(path)
        name = names[-1] if names else ""
        shape = tuple(leaf.shape)
        dims = [None] * len(shape)
        if name.endswith("_pages"):
            if name in ("k_pages", "v_pages") and len(shape) >= 3:
                ts = rules.size(rules.tp_axes)
                if (rules.tp_axes and shape[-3] % ts == 0
                        and (n_query_heads is None
                             or n_query_heads % ts == 0)):
                    dims[-3] = (rules.tp_axes[0]
                                if len(rules.tp_axes) == 1
                                else rules.tp_axes)
            specs.append(P(*dims))
            continue
        if len(shape) >= 2:
            dims[1] = rules.fit(rules.dp_axes, shape[1])
        if len(shape) >= 3:
            dims[-1] = rules.fit(rules.tp_axes, shape[-1])
        specs.append(P(*dims))
    return jax.tree_util.tree_unflatten(treedef, specs)
