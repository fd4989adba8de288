"""Device-resident gradient ledger + cached flat layouts (DESIGN.md §11).

The host reference engine rebuilds a fresh ``(n, P)`` float64 stack every
iteration and runs the GradAgg rule op-by-op in eager mode — correct (it
is the conformance reference) but the slowest layer of the server once P
reaches LeNet size. This module is the device twin:

- :class:`FlatLayout`    leaf offsets/shapes/dtypes of a gradient pytree,
                         computed ONCE per (treedef, shapes) and cached —
                         ``tree_agg``'s per-call offset recomputation and
                         the SPMD stale ledger's per-leaf buffers both
                         collapse onto it.
- :class:`GradLedger`    a persistent ``(n_agents, P)`` f32 device buffer;
                         uploads land via an in-place (donated) scatter
                         ``.at[idx].set`` instead of per-step host
                         stacking.
- :func:`make_aggregate_apply`  ONE jit fusing rule -> step-size scale ->
                         ``project_ball``, with the iterate donated, so
                         the server's iteration is a single device
                         dispatch instead of a numpy pipeline.

Donation contract: the iterate (and the scatter's destination buffer)
are donated on every backend, so updates are in place; callers must not
hold references to ``GradLedger.data`` across an ``upload``.

Width contract: a ledger row is ``ledger_width(P)`` wide — P rounded up
to the aggregation kernels' column tile (``kernels.agg.TILE``), zero in
the padding columns. The padding is allocated once, so the kernels never
copy an ``(n, P)`` stack to pad it per call; every rule maps zero columns
to zero, and the fused step slices its aggregate back to P.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.agg import TILE

PyTree = Any


class FlatLayout:
    """Cached flat view of a gradient pytree.

    Offsets, sizes, shapes and dtypes are computed once per model (per
    (treedef, per-agent shapes, dtypes) key via :func:`layout_of`), not
    per step — flatten/unflatten become pure reshape/concat with static
    slicing, jit-friendly and allocation-minimal.
    """

    def __init__(self, treedef, shapes, dtypes):
        self.treedef = treedef
        self.shapes = tuple(tuple(s) for s in shapes)
        self.dtypes = tuple(jnp.dtype(d) for d in dtypes)
        self.sizes = tuple(int(np.prod(s)) if s else 1 for s in self.shapes)
        off = np.concatenate([[0], np.cumsum(self.sizes)])
        self.offsets = tuple(int(o) for o in off[:-1])
        self.total = int(off[-1])

    # -- flatten ---------------------------------------------------------
    def flatten(self, tree: PyTree) -> jnp.ndarray:
        """Pytree (per-agent leaf shapes) -> (P,) f32."""
        leaves = self.treedef.flatten_up_to(tree)
        return jnp.concatenate(
            [jnp.reshape(l, (-1,)).astype(jnp.float32) for l in leaves])

    def flatten_stack(self, tree: PyTree) -> jnp.ndarray:
        """Pytree with a leading agent axis on every leaf -> (n, P) f32."""
        leaves = self.treedef.flatten_up_to(tree)
        n = leaves[0].shape[0]
        return jnp.concatenate(
            [jnp.reshape(l, (n, -1)).astype(jnp.float32) for l in leaves],
            axis=1)

    # -- unflatten -------------------------------------------------------
    def unflatten(self, flat: jnp.ndarray, dtype=None) -> PyTree:
        """(P,) -> pytree; leaves cast back to their stored dtypes (or a
        uniform ``dtype`` override)."""
        out = []
        for shape, dt, off, sz in zip(self.shapes, self.dtypes,
                                      self.offsets, self.sizes):
            leaf = flat[off:off + sz].reshape(shape)
            out.append(leaf.astype(dtype or dt))
        return jax.tree.unflatten(self.treedef, out)

    def unflatten_stack(self, flat: jnp.ndarray, dtype=None) -> PyTree:
        """(n, P) -> pytree with the leading agent axis restored."""
        n = flat.shape[0]
        out = []
        for shape, dt, off, sz in zip(self.shapes, self.dtypes,
                                      self.offsets, self.sizes):
            leaf = flat[:, off:off + sz].reshape((n,) + shape)
            out.append(leaf.astype(dtype or dt))
        return jax.tree.unflatten(self.treedef, out)


_LAYOUTS: Dict[Tuple, FlatLayout] = {}


def ledger_dim(dim_or_layout_or_tree) -> int:
    """Per-agent flat width P of a ledger, from an int, a
    :class:`FlatLayout`, or a gradient pytree."""
    if isinstance(dim_or_layout_or_tree, FlatLayout):
        return dim_or_layout_or_tree.total
    if isinstance(dim_or_layout_or_tree, (int, np.integer)):
        return int(dim_or_layout_or_tree)
    return layout_of(dim_or_layout_or_tree).total


def ledger_width(dim_or_layout_or_tree) -> int:
    """Allocated row width: P rounded up to the kernels' column tile."""
    p = ledger_dim(dim_or_layout_or_tree)
    return -(-p // TILE) * TILE


def ledger_zeros(n_agents: int, dim_or_layout_or_tree,
                 sharding=None) -> jnp.ndarray:
    """The canonical flat ``(n, ledger_width(P))`` f32 ledger buffer,
    created in place on ``sharding``'s devices (the default device when
    None). Every ledger in the repo — :class:`GradLedger`,
    :class:`ShardedGradLedger`, and the SPMD stale path's per-step buffer
    in ``launch/train.py`` — is built through this one helper, so the
    layout contract exists once."""
    return jnp.zeros((int(n_agents), ledger_width(dim_or_layout_or_tree)),
                     jnp.float32, device=sharding)


def layout_of(tree: PyTree, stacked: bool = False) -> FlatLayout:
    """The cached :class:`FlatLayout` of ``tree``. With ``stacked=True``
    the leaves carry a leading agent axis that the layout strips (the
    layout always describes the per-agent flat vector)."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(l.shape[1:] if stacked else l.shape)
                   for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    key = (treedef, shapes, dtypes)
    layout = _LAYOUTS.get(key)
    if layout is None:
        layout = _LAYOUTS[key] = FlatLayout(treedef, shapes, dtypes)
    return layout


# ---------------------------------------------------------------------------
# the persistent device ledger


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(buf, idx, rows):
    """Write ``rows (k, P)`` into the first P columns of rows ``idx``."""
    return buf.at[idx, :rows.shape[1]].set(rows)


class GradLedger:
    """Persistent ``(n_agents, ledger_width(P))`` f32 device buffer of
    per-agent gradients. One instance lives for the whole server run;
    uploads are in-place row scatters (the buffer is donated), so the
    server never re-stacks or re-uploads the full ledger."""

    def __init__(self, n_agents: int, dim_or_layout, sharding=None):
        if isinstance(dim_or_layout, FlatLayout):
            self.layout: Optional[FlatLayout] = dim_or_layout
        else:
            self.layout = None
        self.n_agents = int(n_agents)
        self.dim = ledger_dim(dim_or_layout)
        self.sharding = sharding
        self.data = ledger_zeros(self.n_agents, self.dim, sharding)

    def upload(self, idx, rows) -> None:
        """Scatter ``rows (k, P)`` into agent rows ``idx (k,)``."""
        idx = np.asarray(idx, np.int32).reshape(-1)
        if idx.size == 0:
            return
        rows = jnp.asarray(rows, jnp.float32).reshape(idx.size, self.dim)
        self.data = _scatter_rows(self.data, jnp.asarray(idx), rows)

    def upload_row(self, j: int, row) -> None:
        self.upload(np.array([j], np.int32),
                    np.asarray(row, np.float32)[None])

    def upload_tree(self, j: int, tree: PyTree) -> None:
        """Scatter one agent's gradient pytree through the cached layout
        (leaf offsets precomputed — no per-call layout work)."""
        if self.layout is None:
            raise ValueError("ledger was built without a FlatLayout")
        self.upload_row(j, self.layout.flatten(tree))

    def front_for_aggregate(self) -> jnp.ndarray:
        """The buffer the fused aggregate should consume this iteration.
        Single-buffer ledger: the live buffer itself (the double-buffered
        :class:`ShardedGradLedger` overrides this with the swap)."""
        return self.data

    # -- checkpointing ---------------------------------------------------
    def host(self) -> np.ndarray:
        """Host f32 ``(n, P)`` copy (snapshot form; restoring it is
        bit-exact)."""
        return np.asarray(self.data[:, :self.dim])

    def _padded(self, arr) -> np.ndarray:
        arr = np.asarray(arr, np.float32)
        return np.pad(arr, ((0, 0), (0, ledger_width(self.dim) - self.dim)))

    def load(self, arr) -> None:
        # straight from host memory to the ledger's devices: a sharded
        # ledger may be larger than any one device holds
        self.data = jax.device_put(self._padded(arr), self.sharding)


class ShardedGradLedger(GradLedger):
    """Double-buffered ``(n, P)`` ledger sharded over the dp axes: each
    shard holds its ``n/dp`` agent rows (``PartitionSpec((dp...), None)``,
    row-major agent order — the same linearization as
    ``collectives.agent_index``).

    Double-buffer swap protocol (DESIGN.md §14). Invariant: the buffer
    uploads currently target (``bufs[cur]``) contains *every* upload ever
    made, so ``host()`` is exact at any instant, including mid-swap.

    - ``upload``              scatters into ``bufs[cur]`` and logs the
                              (idx, rows) pair in ``pending``.
    - ``front_for_aggregate`` returns ``bufs[cur]`` as the aggregation
                              front, replays ``pending`` into the *other*
                              buffer (catching it up off the upload
                              critical path), and makes that other buffer
                              the new upload target.

    After a swap, in-flight uploads scatter into the back buffer while
    the fused aggregate+apply reads the front — on accelerator backends
    the two dispatch streams overlap, so uploads never serialize behind
    aggregation. Donation rules: the scatter donates its destination
    buffer (in-place row writes, both buffers); the fused aggregate jit
    donates ONLY the iterate ``x`` — never the ledger, which the back
    buffer may still be replaying from.
    """

    def __init__(self, n_agents: int, dim_or_layout, *, mesh, axes):
        from jax.sharding import NamedSharding, PartitionSpec
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        n_shards = 1
        for a in axes:
            n_shards *= mesh.shape[a]
        if int(n_agents) % n_shards:
            raise ValueError(
                f"n_agents={int(n_agents)} not divisible by the "
                f"{n_shards}-way dp sharding over axes {axes}")
        self.mesh = mesh
        self.axes = axes
        self.spec = PartitionSpec(axes if len(axes) > 1 else axes[0], None)
        # bufs must exist before super().__init__ assigns self.data
        # (the assignment routes through the property setter below)
        self._bufs: list = [None, None]
        self._cur = 0
        super().__init__(n_agents, dim_or_layout,
                         sharding=NamedSharding(mesh, self.spec))
        # the two slots must be *independent* device buffers: _scatter_rows
        # donates its destination, so aliased slots would have the first
        # upload invalidate the other buffer and the next pending replay
        # would read a deleted array. Both are created in place on their
        # shards, never staged whole on one device.
        self._bufs[1 - self._cur] = ledger_zeros(self.n_agents, self.dim,
                                                 self.sharding)
        self._pending: list = []
        self.swaps = 0

    # ``data`` stays the public name of the authoritative buffer
    @property
    def data(self) -> jnp.ndarray:
        return self._bufs[self._cur]

    @data.setter
    def data(self, value) -> None:
        self._bufs[self._cur] = value

    def upload(self, idx, rows) -> None:
        idx = np.asarray(idx, np.int32).reshape(-1)
        if idx.size == 0:
            return
        rows = jnp.asarray(rows, jnp.float32).reshape(idx.size, self.dim)
        idx = jnp.asarray(idx)
        self._bufs[self._cur] = _scatter_rows(self._bufs[self._cur],
                                              idx, rows)
        self._pending.append((idx, rows))

    def front_for_aggregate(self) -> jnp.ndarray:
        front = self._bufs[self._cur]
        back = 1 - self._cur
        for idx, rows in self._pending:
            self._bufs[back] = _scatter_rows(self._bufs[back], idx, rows)
        self._pending.clear()
        self._cur = back
        self.swaps += 1
        return front

    def load(self, arr) -> None:
        """Restore both buffers (a snapshot is a settled ledger — no
        pending uploads survive a restore)."""
        host = self._padded(arr)
        # release the old pair first: at a model's width two pairs of
        # ledger buffers do not fit beside each other
        self._bufs = [None, None]
        # two independent copies — never alias the slots (donation,
        # above). The copy is made on the devices: a CPU put of one host
        # array twice may hand back the same zero-copy buffer
        first = jax.device_put(host, self.sharding)
        self._bufs = [first, jnp.copy(first)]
        self._pending.clear()


# ---------------------------------------------------------------------------
# the fused server iteration


@functools.lru_cache(maxsize=None)
def make_aggregate_apply(rule: str, f: int, gamma: float) -> Callable:
    """One fused jit for the server iteration over a resident ledger:

        x' = project_ball(x - eta * GradAgg(g, received), gamma)

    Signature: ``(x (P,) f32, g (n, W) f32, received (n,) bool, eta)``
    with ``W = ledger_width(P)``. The rule is the registry's
    ``bind_device`` twin (Pallas kernels on TPU, jnp elsewhere); its
    ``(W,)`` aggregate is sliced to P, and the iterate is donated. The
    host f64 reference path stays the conformance/golden bit stream —
    this is the opt-in ``EngineConfig.agg_backend="device"`` fast path.

    Cached per (rule, f, gamma): server restore/reconfigure rebuilds the
    engine, and a fresh closure per build would defeat jit's cache and
    recompile the fused step every time.
    """
    from repro.core import gradagg            # projection exists once
    from repro.dist.registry import get_rule  # lazy: dist sits above core
    dev = get_rule(rule).bind_device(f)

    def step(x, g, received, eta):
        agg = dev(g, received)[:x.shape[0]].astype(jnp.float32)
        return gradagg.project_ball(x - jnp.float32(eta) * agg, gamma)

    return jax.jit(step, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def make_sharded_aggregate_apply(rule: str, f: int, gamma: float,
                                 mesh, axes: Tuple[str, ...], n_agents: int,
                                 combine: str = "gather") -> Callable:
    """Sharded twin of :func:`make_aggregate_apply` over a dp-sharded
    ledger (DESIGN.md §14). Same signature and same fused structure —
    rule -> step-size scale -> ``project_ball`` in ONE jit — but the rule
    runs inside a shard_map body on each shard's ``(n_loc, P)`` row block
    via the registry's ``bind_sharded`` twin; the iterate and mask stay
    replicated and the post-psum update is computed identically on every
    shard. Donates only the iterate: the ledger buffer belongs to the
    double-buffer protocol and is never consumed in place.
    """
    from jax.sharding import PartitionSpec as P
    from repro.core import gradagg
    from repro.dist.registry import get_rule
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    agg_loc = get_rule(rule).bind_sharded(f, axes=axes, n=n_agents,
                                          combine=combine)
    row_spec = P(axes if len(axes) > 1 else axes[0], None)

    def body(x, g_loc, received, eta):
        agg = agg_loc(g_loc, received)[:x.shape[0]].astype(jnp.float32)
        return gradagg.project_ball(x - eta * agg, gamma)

    # manual over every mesh axis (the rest replicate): the rule's Pallas
    # kernels cannot be partitioned automatically over an auto axis
    smap = jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), row_spec, P(), P()),
                         out_specs=P(), check_vma=False)

    def step(x, g_loc, received, eta):
        return smap(x, g_loc, received, jnp.float32(eta))

    return jax.jit(step, donate_argnums=(0,))
