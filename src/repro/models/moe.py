"""Mixture-of-Experts: token-choice routing, drop-free, over the experts
this layer holds.

The router scores every one of ``num_experts`` experts and each token
picks its top ``top_k`` (softmax scores; renormalised over the k when
``norm_topk_prob``; times ``routed_scaling_factor``). The layer holds
``n_held`` experts, ``first_held`` onwards: one chip's share under expert
parallelism, or all of them. It computes only the (token, expert) pairs
whose expert it holds, and never drops one: a chunk of C tokens sorts
its pairs by expert into a static buffer of C * min(k, held) rows,
which holds every held pair whatever the routing, and the expert MLPs
run as grouped
matmuls over it (``kernels.ops.grouped_matmul``) whose work follows the
number of pairs routed, not the buffer. What experts held elsewhere
would add is left out; the shared experts are added once.

The balance loss is DeepSeek-V2's sequence-wise one, alpha * sum_i
f_i P_i per row with f_i = E / (k * S) * (the row's picks of expert i)
and P_i the row's mean score of expert i, averaged over rows by the
row's loss weight (Algorithm 1's agent mask), so that a masked row
adds nothing to the loss or its gradient.

Name scopes, which reach the device trace's op metadata:
``repro.moe.route`` (router, top-k, balance loss, sort),
``repro.moe.experts`` (gather and the grouped matmuls, forward and
backward) and ``repro.moe.combine``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.dist.act_sharding import current_policy
from repro.dist.sharding import current_serve_tp
from repro.kernels import ops
from repro.models.layers import dense_init, init_mlp, apply_mlp, _dtype

ROW_ALIGN = 256               # buffer rows: a whole number of kernel tiles
CHUNK_TOKENS = 4096           # tokens through the held experts at a time


def init_moe(rng, cfg: ArchConfig, moe: MoEConfig):
    d, dt = cfg.d_model, _dtype(cfg)
    e, ff = moe.n_held, moe.d_ff_expert
    ks = jax.random.split(rng, 6)

    def stack(k, d_in, d_out):
        std = 1.0 / (d_in ** 0.5)
        return (jax.random.normal(k, (e, d_in, d_out), jnp.float32)
                * std).astype(dt)

    p = {
        "router": dense_init(ks[0], d, moe.num_experts, dt),
        "w_gate": stack(ks[1], d, ff),
        "w_up": stack(ks[2], d, ff),
        "w_down": stack(ks[3], ff, d),
    }
    if moe.num_shared_experts:
        # shared experts fused into one dense SwiGLU of width n_shared*ff
        p["shared"] = init_mlp(ks[4], cfg, d_ff=moe.num_shared_experts * ff)
    if moe.dense_residual:
        p["dense"] = init_mlp(ks[5], cfg, d_ff=cfg.d_ff)
    return p


def route(router, x, moe: MoEConfig):
    """x: (T, d). Returns the scores over all experts (T, E) and each
    token's top-k weights and expert ids (T, k)."""
    rdt = jnp.dtype(moe.router_dtype)
    logits = jnp.einsum("td,de->te", x.astype(rdt), router.astype(rdt),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(scores, moe.top_k)
    if moe.norm_topk_prob:
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-20)
    return scores, top_w * moe.routed_scaling_factor, top_i


def balance_loss(scores, top_i, row_w, moe: MoEConfig):
    """scores (B, S, E), top_i (B, S, k), row_w (B,): the sequence-wise
    balance loss, averaged over rows by ``row_w``."""
    b, s, e = scores.shape
    picks = jnp.sum(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=(1, 2))
    f = picks * (e / (moe.top_k * s))                          # (B, E)
    per_row = jnp.sum(f * jnp.mean(scores, axis=1), axis=-1)   # (B,)
    return moe.aux_coef * jnp.sum(per_row * row_w) / jnp.maximum(
        jnp.sum(row_w), 1e-9)


def buffer_rows(tokens: int, moe: MoEConfig) -> int:
    """Rows of the static pair buffer: no token has more held pairs than
    min(k, held), rounded up to whole tiles."""
    r = tokens * min(moe.top_k, moe.n_held)
    return -(-r // ROW_ALIGN) * ROW_ALIGN


def dispatch(top_i, top_w, moe: MoEConfig, rows: int):
    """Sort the held (token, expert) pairs by expert into ``rows`` buffer
    rows. Returns each row's token (R,), the chunk's length T for a row
    past the pairs (out of range: gathers read zeros there and scatters
    drop it), the row's routing weight (R,), and the pairs per held
    expert (H,)."""
    t, k = top_i.shape
    h = moe.n_held
    local = top_i - moe.first_held
    held = (local >= 0) & (local < h)
    key = jnp.where(held, local, h).reshape(-1)            # (T*k,)
    order = jnp.argsort(key, stable=True)                  # held first
    sizes = jnp.bincount(key, length=h + 1)[:h].astype(jnp.int32)
    order = jnp.pad(order, (0, max(rows - t * k, 0)))[:rows]
    valid = jnp.arange(rows) < jnp.sum(sizes)
    tok = jnp.where(valid, order // k, t).astype(jnp.int32)
    weight = jnp.where(valid, jnp.take(top_w.reshape(-1), order), 0.0)
    return tok, weight, sizes


def _experts(p, xs, sizes, kernel: bool):
    """SwiGLU of each held expert over its rows of xs (R, d)."""
    impl = "auto" if kernel else "ref"
    g = ops.grouped_matmul(xs, p["w_gate"], sizes, impl=impl)
    u = ops.grouped_matmul(xs, p["w_up"], sizes, impl=impl)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xs.dtype) * u
    return ops.grouped_matmul(h, p["w_down"], sizes, impl=impl)


def _routed(p, xt, top_w, top_i, moe: MoEConfig, kernel: bool):
    """The held experts' part of the output for one chunk of tokens
    xt (C, d), and the pairs each held expert took (H,)."""
    t, d = xt.shape
    with jax.named_scope("repro.moe.route"):
        tok, weight, sizes = dispatch(top_i, top_w, moe,
                                      buffer_rows(t, moe))
    with jax.named_scope("repro.moe.experts"):
        xs = jnp.take(xt, tok, axis=0, mode="fill", fill_value=0)
        ys = _experts(p, xs, sizes, kernel)
    with jax.named_scope("repro.moe.combine"):
        out = jnp.zeros((t, d), jnp.float32).at[tok].add(
            ys.astype(jnp.float32) * weight[:, None], mode="drop")
    return out.astype(xt.dtype), sizes


def apply_moe(p, x, cfg: ArchConfig, moe: MoEConfig,
              weights: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B,S,d) -> (B,S,d) and the layer's readings: ``balance`` (the
    weighted balance loss), ``held_pairs`` (pairs routed to held experts)
    and ``held_max_load`` (the largest held expert's pairs over the
    mean). ``weights`` (B,S): the loss weights; a row's balance loss
    counts by their mean (all rows alike when None).

    Tokens pass the held experts ``CHUNK_TOKENS`` at a time when their
    count is a multiple of it (each chunk rematerialised in the
    backward), so the pair buffers stay small."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    # the Pallas kernel runs unpartitioned: not under a sharding policy
    kernel = current_policy() is None and current_serve_tp() is None
    with jax.named_scope("repro.moe.route"):
        scores, top_w, top_i = route(p["router"], xt, moe)
        row_w = (jnp.ones((b,), jnp.float32) if weights is None else
                 jnp.mean(weights.astype(jnp.float32), axis=1))
        bal = balance_loss(scores.reshape(b, s, -1),
                           top_i.reshape(b, s, -1), row_w, moe)
    n = t // CHUNK_TOKENS if t % CHUNK_TOKENS == 0 else 1
    out, sizes = jax.lax.map(
        jax.checkpoint(lambda a: _routed(p, *a, moe, kernel),
                       prevent_cse=False),
        (xt.reshape(n, t // n, d), top_w.reshape(n, t // n, -1),
         top_i.reshape(n, t // n, -1)))
    out = out.reshape(b, s, d)
    if moe.num_shared_experts:
        out = out + apply_mlp(p["shared"], x, cfg)
    if moe.dense_residual:
        out = out + apply_mlp(p["dense"], x, cfg)
    load = jnp.sum(sizes, axis=0).astype(jnp.float32)          # (H,)
    pairs = jnp.sum(load)
    aux = {"balance": bal, "held_pairs": pairs,
           "held_max_load": jnp.max(load) * moe.n_held
           / jnp.maximum(pairs, 1.0)}
    return out, aux
