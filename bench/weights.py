"""Weights made by the harness, in the layout of a configuration's family.

The benchmark makes its weights itself, from ``--seed``, on the device,
in one jitted call, in the dtype they are served in. The program gets
them through its normal entry points; the plain reference makes them
again from the same seed, so it takes nothing that the program made.

A family module (``bench/families/<name>.py``) says what the weights
are: ``shapes(arch)`` gives the tree's shapes and ``init(path, x)``
each leaf's initial values from a standard normal draw. Each leaf
draws from its own key, the run's weights key folded with the leaf's
index in the sorted order of the tree's paths. ``make_params`` checks
that the tree has the program's shapes (``repro.models.model``), so a
change on either side fails here rather than measuring another model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _paths(tree[k], f"{prefix}/{k}")
    elif isinstance(tree, tuple) and tree and not isinstance(tree[0], int):
        for i, t in enumerate(tree):
            yield from _paths(t, f"{prefix}/{i}")
    else:
        yield prefix, tree


def _build(tree, fn, prefix=""):
    if isinstance(tree, dict):
        return {k: _build(v, fn, f"{prefix}/{k}") for k, v in tree.items()}
    if isinstance(tree, tuple) and tree and not isinstance(tree[0], int):
        return tuple(_build(t, fn, f"{prefix}/{i}")
                     for i, t in enumerate(tree))
    return fn(prefix, tree)


@functools.lru_cache(maxsize=8)
def _maker(family, arch):
    shp = family.shapes(arch)
    dtype = jnp.dtype(arch.param_dtype)
    index = {p: i for i, (p, _) in enumerate(_paths(shp))}

    def leaf(key, path, shape):
        x = jax.random.normal(key, shape, jnp.float32)
        return family.init(path, x).astype(dtype)

    def make(key):
        return _build(shp, lambda p, s: leaf(
            jax.random.fold_in(key, index[p]), p, s))
    return jax.jit(make)


def seed_key(seed: int, stream: int):
    """A JAX key for one named stream of a run's seed."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), stream)


WEIGHTS_STREAM = 1


def make_params(family, arch, seed: int):
    """The cell's weights, on the default device, from ``seed``."""
    params = _maker(family, arch)(seed_key(seed, WEIGHTS_STREAM))
    check_layout(arch, params)
    return params


def check_layout(arch, params) -> None:
    from repro.models.model import init_model
    want = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), arch,
                                             max_pos=8))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter layout changed: "
                         f"{jax.tree.map(lambda a: a.shape, want)}")
