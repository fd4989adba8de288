"""Weights and configurations for the Qwen2 family, made by the harness.

The benchmark makes its weights itself, from ``--seed``, on the device,
in one jitted call, in the dtype they are served in. The program gets
them through its normal entry points; the plain reference makes them
again from the same seed, so it takes nothing that the program made.

The tree is the program's parameter layout (``repro.models.model``):
layers stacked on a leading axis, one pattern position. ``arch_config``
checks that the registry's configuration has the sizes of the file, and
``make_params`` that its tree has the program's shapes, so a change on
either side fails here rather than measuring another model.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

EMBED_STD = 0.02
BIAS_STD = 0.02
NORM_STD = 0.1

# configuration-file key -> ArchConfig field
ARCH_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "num_hidden_layers": "n_layers",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
             "vocab_size": "vocab_size", "rope_theta": "rope_theta",
             "tie_word_embeddings": "tie_embeddings"}


def arch_config(cfg: dict, resize: bool = False):
    """The program's ``ArchConfig`` for a configuration file, checked
    against the file's sizes. ``resize`` (the harness's tests only) takes
    the file's sizes instead of checking them."""
    from repro.configs.registry import get_config
    arch = get_config(cfg["registry"])
    if resize:
        arch = dataclasses.replace(arch, **{f: cfg[k]
                                            for k, f in ARCH_KEYS.items()})
    for key, field in ARCH_KEYS.items():
        if getattr(arch, field) != cfg[key]:
            raise ValueError(f"{cfg['name']}: registry {field}="
                             f"{getattr(arch, field)!r}, configuration "
                             f"file {key}={cfg[key]!r}")
    if (arch.param_dtype, arch.compute_dtype) != (cfg["dtype"],) * 2:
        raise ValueError(f"{cfg['name']}: registry dtypes "
                         f"{arch.param_dtype}/{arch.compute_dtype}, "
                         f"file {cfg['dtype']}")
    if arch.layer_pattern != ("attn",) or not arch.qkv_bias:
        raise ValueError(f"{cfg['name']}: not a Qwen2 dense GQA layout")
    return arch


def sizes(arch) -> dict:
    return dict(d=arch.d_model, h=arch.n_heads, hkv=arch.n_kv_heads,
                hd=arch.resolved_head_dim, ff=arch.d_ff, L=arch.n_layers,
                V=arch.vocab_size)


def shapes(arch) -> dict:
    s = sizes(arch)
    d, h, hkv, hd, ff, L, V = (s[k] for k in ("d", "h", "hkv", "hd", "ff",
                                              "L", "V"))
    return {
        "embed": {"tok": (V, d)},
        "blocks": ({
            "norm1": {"scale": (L, d)},
            "norm2": {"scale": (L, d)},
            "mixer": {"wq": (L, d, h * hd), "wk": (L, d, hkv * hd),
                      "wv": (L, d, hkv * hd), "wo": (L, h * hd, d),
                      "bq": (L, h * hd), "bk": (L, hkv * hd),
                      "bv": (L, hkv * hd)},
            "ffn": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                    "w_down": (L, ff, d)},
        },),
        "norm_f": {"scale": (d,)},
    }


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    x = jax.random.normal(key, shape, jnp.float32)
    if name == "tok":
        x = x * EMBED_STD
    elif name == "scale":
        x = 1.0 + NORM_STD * x
    elif name.startswith("b"):
        x = x * BIAS_STD
    else:                                   # (L, fan_in, fan_out) matmul
        x = x * shape[-2] ** -0.5
    return x.astype(dtype)


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
def _maker(arch):
    shp = shapes(arch)
    dtype = jnp.dtype(arch.param_dtype)
    index = {p: i for i, (p, _) in enumerate(_paths(shp))}

    def make(key):
        return _build(shp, lambda p, s: _leaf(
            jax.random.fold_in(key, index[p]), p, s, dtype))
    return jax.jit(make)


def seed_key(seed: int, stream: int):
    """A JAX key for one named stream of a run's seed."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), stream)


WEIGHTS_STREAM = 1


def make_params(arch, seed: int):
    """The cell's weights, on the default device, from ``seed``."""
    params = _maker(arch)(seed_key(seed, WEIGHTS_STREAM))
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
