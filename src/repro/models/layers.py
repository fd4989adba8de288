"""Shared model layers: norms, RoPE/M-RoPE, MLPs, embeddings.

Pure functional JAX: params are nested dicts of arrays; every init function
is traceable (works under ``jax.eval_shape`` so the dry-run never allocates).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.act_sharding import constrain

# ---------------------------------------------------------------------------
# init helpers


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(rng, d_in: int, d_out: int, dtype, scale: float = 1.0):
    std = scale / (d_in ** 0.5)
    return (jax.random.normal(rng, (d_in, d_out), jnp.float32) * std).astype(dtype)


def embed_init(rng, vocab: int, d: int, dtype):
    return (jax.random.normal(rng, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms


def init_norm(cfg: ArchConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), _dtype(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), _dtype(cfg))
    return p


def apply_norm(p, x, cfg: ArchConfig, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def apply_group_norm(x, n_groups: int, eps: float = 1e-6):
    """Head-wise group norm (RWKV6 wkv output norm), no learned params here."""
    b, t, h, d = x.shape
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               mrope_sections: Optional[Tuple[int, ...]] = None,
               inv_freq: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """x: (B,S,H,D). positions: (B,S) int, or (3,B,S) for M-RoPE.
    ``inv_freq`` (D/2,) replaces theta's frequencies (YaRN)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta) if inv_freq is None else inv_freq  # (d/2,)
    if positions.ndim == 3:                          # M-RoPE
        assert mrope_sections is not None
        sec_ids = jnp.repeat(
            jnp.arange(len(mrope_sections)),
            jnp.array(mrope_sections),
            total_repeat_length=d // 2)              # (d/2,) in {0,1,2}
        # each frequency index takes its position from section row sec_ids[i]
        oh = jax.nn.one_hot(sec_ids, positions.shape[0], dtype=jnp.float32)
        pos = jnp.einsum("rbs,dr->bsd", positions.astype(jnp.float32), oh)
        freqs = pos * inv                            # (B,S,d/2)
    else:
        freqs = positions[..., None].astype(jnp.float32) * inv  # (B,S,d/2)
    cos = jnp.cos(freqs)[:, :, None, :]
    sin = jnp.sin(freqs)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP


def init_mlp(rng, cfg: ArchConfig, d_ff: Optional[int] = None):
    d, dt = cfg.d_model, _dtype(cfg)
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    if cfg.act == "swiglu":
        return {"w_gate": dense_init(ks[0], d, ff, dt),
                "w_up": dense_init(ks[1], d, ff, dt),
                "w_down": dense_init(ks[2], ff, d, dt)}
    return {"w_in": dense_init(ks[0], d, ff, dt),
            "b_in": jnp.zeros((ff,), dt),
            "w_out": dense_init(ks[1], ff, d, dt),
            "b_out": jnp.zeros((d,), dt)}


def apply_mlp(p, x, cfg: ArchConfig):
    if cfg.act == "swiglu":
        g = constrain(jnp.einsum("...d,df->...f", x, p["w_gate"]), "ffn")
        u = constrain(jnp.einsum("...d,df->...f", x, p["w_up"]), "ffn")
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        return jnp.einsum("...f,fd->...d", h, p["w_down"])
    h = jnp.einsum("...d,df->...f", x, p["w_in"]) + p["b_in"]
    h = constrain(jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype), "ffn")
    return jnp.einsum("...f,fd->...d", h, p["w_out"]) + p["b_out"]


# ---------------------------------------------------------------------------
# embeddings / unembedding


def init_embed(rng, cfg: ArchConfig):
    dt = _dtype(cfg)
    ks = jax.random.split(rng, 3)
    p = {"tok": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], cfg.d_model, cfg.vocab_size, dt)
    if cfg.rope == "learned":
        # decoder learned positions; encoder positions for enc-dec frontends
        p["pos"] = (jax.random.normal(ks[2], (8192, cfg.d_model), jnp.float32)
                    * 0.01).astype(dt)
    return p


def embed_tokens(p, tokens, cfg: ArchConfig):
    x = jnp.take(p["tok"], tokens, axis=0)
    if cfg.tie_embeddings:
        # standard embedding scale for tied weights
        x = x * jnp.asarray(1.0, x.dtype)
    return x.astype(jnp.dtype(cfg.compute_dtype))


def unembed(p, x, cfg: ArchConfig):
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x, p["tok"])
    return jnp.einsum("...d,dv->...v", x, p["unembed"])
