"""Plain reference of the DeepSeek-V2 decoder (DeepSeek-V2-Lite's
layout), in float32 ``jax.numpy``, for one chip's share of an
expert-parallel deployment.

Follows the published architecture (transformers
``DeepseekV2ForCausalLM``, DeepSeek-V2 paper arXiv:2405.04434):

- RMSNorm (eps from the configuration) before attention and MLP, and a
  final RMSNorm;
- latent attention without query compression: q = x W_q, split into
  a no-rope and a rope part per head; the latent c = RMSNorm(x W_dkv)
  gives per-head keys c W_uk and values c W_uv; one rope key x W_kr
  shared by the heads; the rope parts rotated with YaRN's frequencies
  (``rope_scaling``: factor, original_max_position_embeddings, beta_fast,
  beta_slow) and scaled by mscale(mscale) / mscale(mscale_all_dim); the
  softmax scale (nope + rope)^-0.5 times mscale(mscale_all_dim)^2;
  causal;
- ``first_k_dense_replace`` layers with a SwiGLU MLP, then MoE layers: a
  softmax router over all ``router_experts`` experts, greedy top-k, the
  top-k scores renormalised only when ``norm_topk_prob``, times
  ``routed_scaling_factor``; each held expert applied to every token,
  times its routing weight, which is zero where the token did not pick
  it; plus the shared experts, one SwiGLU of n_shared times the expert
  width;
- an untied LM head over the configuration's slice of the vocabulary;
- the loss: mean weighted cross-entropy plus, for each MoE layer, the
  sequence-wise balance loss alpha * sum_i f_i P_i with f_i = E / (k S) *
  (the row's picks of expert i) and P_i the row's mean score of expert
  i, averaged over the rows by the mean of their weights.

Every matmul runs at ``Precision.HIGHEST``. It imports nothing of the
program and uses only the weights ``bench/weights.py`` makes from the
seed, upcast from the dtype they are served in. Attention is computed a
row and a block of queries at a time, and the MoE layer an expert at a
time, so that it fits one chip at the configuration's widths.

Departures from the published description:

- the rotary dims are half-split (dims i and i + rope/2 rotate
  together); the published code interleaves the pairs of ``q_pe`` and
  ``k_pe`` first, which on random weights is a permutation of weight
  columns;
- one chip's share: the experts held here are the only routed experts
  computed, and what the others would add is left out (the router still
  scores all of them); the vocabulary is the chip's slice;
- everything stays in float32 (the program rounds activations to
  bfloat16 between operations, and its optimizer casts the update to
  bfloat16 before applying it).

``quant=True`` is the low-precision control: every matmul's operands,
the attention's included, are rounded to int8, symmetric, per row of the
left operand and per output column of the right one, with
straight-through gradients.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
Q_BLOCK = 512                 # query positions scored at a time
CE_CHUNK = 256                # positions of vocabulary-wide logits at a time


def fake_int8(x, axis: int):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(a, w, quant: bool):
    """a (..., k) @ w (k, n) in float32."""
    if quant:
        a, w = fake_int8(a, -1), fake_int8(w, 0)
    return jnp.einsum("...k,kn->...n", a, w, precision=HI)


def rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def swiglu(x, w, quant: bool):
    g = mm(x, w["w_gate"], quant)
    return mm(jax.nn.silu(g) * mm(x, w["w_up"], quant), w["w_down"], quant)


# ---------------------------------------------------------------------------
# YaRN, after the published modeling code's yarn_* functions


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(c: dict) -> np.ndarray:
    dim, base = c["rp"], c["theta"]
    orig = c["yarn_orig"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(c["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(c["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / c["factor"]
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def rope(x, c: dict):
    """x: (S, H, rp); positions 0..S-1; half-split rotation."""
    s, _, d = x.shape
    inv = jnp.asarray(yarn_inv_freq(c))
    f = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    m = yarn_get_mscale(c["factor"], c["mscale"]) / yarn_get_mscale(
        c["factor"], c["mscale_all_dim"])
    cos, sin = (jnp.cos(f) * m)[:, None], (jnp.sin(f) * m)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# ---------------------------------------------------------------------------
# one sequence


def attention(x, mx, c: dict, quant: bool):
    """Latent attention over one sequence x (S, d)."""
    s = x.shape[0]
    h, nope, rp, vd = c["h"], c["nope"], c["rp"], c["v"]
    q = mm(x, mx["w_q"], quant).reshape(s, h, nope + rp)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], c)], -1)
    lat = rms(mm(x, mx["w_dkv"], quant), mx["kv_norm"], c["eps"])
    k_nope = mm(lat, mx["w_uk"].reshape(lat.shape[-1], -1), quant
                ).reshape(s, h, nope)
    v = mm(lat, mx["w_uv"].reshape(lat.shape[-1], -1), quant
           ).reshape(s, h, vd)
    k_pe = rope(mm(x, mx["w_kr"], quant)[:, None, :], c)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (s, h, rp))], -1)
    if quant:
        q, k, v = fake_int8(q, -1), fake_int8(k, -1), fake_int8(v, 0)
    mall = yarn_get_mscale(c["factor"], c["mscale_all_dim"])
    scale = (nope + rp) ** -0.5 * mall * mall
    nb = s // Q_BLOCK if s % Q_BLOCK == 0 else 1

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * (s // nb), s // nb, 0)
        sc = jnp.einsum("shd,thd->hst", qb, k, precision=HI) * scale
        pos = i * (s // nb) + jnp.arange(s // nb)
        causal = jnp.arange(s)[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], sc, NEG_INF), axis=-1)
        if quant:
            p = fake_int8(p, -1)
        return jnp.einsum("hst,thd->shd", p, v, precision=HI)

    o = jax.lax.map(jax.checkpoint(block), jnp.arange(nb))
    return mm(o.reshape(s, h * vd), mx["wo"], quant)


def moe(x, fp, c: dict, quant: bool):
    """The MoE MLP over one sequence x (S, d): the held experts' part and
    the shared experts, and the row's balance loss."""
    e, k = c["experts"], c["top_k"]
    scores = jax.nn.softmax(mm(x, fp["router"], quant), axis=-1)  # (S, E)
    top_w, top_i = jax.lax.top_k(scores, k)
    if c["norm_topk"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    top_w = top_w * c["scaling"]
    picks = jax.nn.one_hot(top_i, e, dtype=jnp.float32)          # (S,k,E)
    weight = jnp.einsum("sk,ske->se", top_w, picks, precision=HI)
    held = c["first_held"] + jnp.arange(c["held"])

    def expert(y, j):
        w = {n: fp[n][j] for n in ("w_gate", "w_up", "w_down")}
        return y + weight[:, held[j], None] * swiglu(x, w, quant), None

    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                        jnp.arange(c["held"]))
    y = y + swiglu(x, fp["shared"], quant)
    f = jnp.sum(picks, axis=(0, 1)) * e / (k * x.shape[0])
    balance = c["alpha"] * jnp.sum(f * jnp.mean(scores, axis=0))
    return y, balance


def layer(x, lp, c: dict, quant: bool, dense: bool):
    """One decoder layer on one sequence x (S, d), float32 params."""
    x = x + attention(rms(x, lp["norm1"]["scale"], c["eps"]), lp["mixer"],
                      c, quant)
    y = rms(x, lp["norm2"]["scale"], c["eps"])
    if dense:
        return x + swiglu(y, lp["ffn"], quant), 0.0
    out, balance = moe(y, lp["ffn"], c, quant)
    return x + out, balance


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def hidden(params, tokens, c: dict, quant: bool):
    """Final-normed hidden states (S, d) of one sequence and its balance
    loss summed over the MoE layers."""
    x = params["embed"]["tok"][tokens].astype(jnp.float32)

    def stack(dense):
        def body(carry, lp):
            x, bal = carry
            x, b = layer(x, f32(lp), c, quant, dense)
            return (x, bal + b), None
        return jax.checkpoint(body)

    carry = (x, jnp.zeros((), jnp.float32))
    carry, _ = jax.lax.scan(stack(True), carry, params["lead"])
    (x, bal), _ = jax.lax.scan(stack(False), carry, params["blocks"][0])
    return rms(x, params["norm_f"]["scale"].astype(jnp.float32),
               c["eps"]), bal


def logits(params, x, quant: bool):
    return mm(x, params["embed"]["unembed"].astype(jnp.float32), quant)


def consts(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    y = cfg["rope_scaling"]
    dep = cfg["deployment"]
    held = cfg["n_routed_experts"]
    return {"h": cfg["num_attention_heads"], "nope": cfg["qk_nope_head_dim"],
            "rp": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]), "factor": float(y["factor"]),
            "yarn_orig": int(y["original_max_position_embeddings"]),
            "beta_fast": float(y["beta_fast"]),
            "beta_slow": float(y["beta_slow"]),
            "mscale": float(y["mscale"]),
            "mscale_all_dim": float(y["mscale_all_dim"]),
            "experts": int(dep["router_experts"]),
            "top_k": int(cfg["num_experts_per_tok"]), "held": int(held),
            "first_held": int(dep["chip"]) * held,
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "scaling": float(cfg["routed_scaling_factor"]),
            "alpha": float(cfg["aux_loss_alpha"])}


# ---------------------------------------------------------------------------
# training: Algorithm 1's masked loss, clipped gradient and AdamW


def row_loss(p32, tokens, targets, weights, scale_ce, scale_bal, c: dict,
             quant: bool):
    """One row's share of the loss: its weighted cross-entropy sum over
    ``scale_ce`` and its balance loss times its weight over
    ``scale_bal``. The vocabulary-wide logits are made a chunk of
    positions at a time."""
    x, bal = hidden(p32, tokens, c, quant)
    n = x.shape[0] // CE_CHUNK if x.shape[0] % CE_CHUNK == 0 else 1
    xs = x.reshape(n, -1, x.shape[-1])

    def chunk(acc, blk):
        xc, tc, wc = blk
        lg = logits(p32, xc, quant)
        ce = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, tc[:, None], axis=-1)[:, 0]
        return acc + jnp.sum(ce * wc), None

    total, _ = jax.lax.scan(jax.checkpoint(chunk), jnp.zeros((), x.dtype),
                            (xs, targets.reshape(n, -1),
                             weights.reshape(n, -1)))
    return total / scale_ce + bal * jnp.mean(weights) / scale_bal


def grads_and_loss(params, tokens, targets, weights, c: dict, quant: bool):
    """The loss and its gradient, in float32, one row at a time (rows of
    weight 0 add nothing and are left out by the caller)."""
    p32 = f32(params)
    scale_ce = jnp.maximum(jnp.sum(weights), 1.0)
    scale_bal = jnp.maximum(jnp.sum(jnp.mean(weights, axis=1)), 1e-9)
    vg = jax.value_and_grad(
        lambda p, t, y, w: row_loss(p, t, y, w, scale_ce, scale_bal, c,
                                    quant))

    def body(acc, row):
        l, g = vg(p32, *row)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p32))
    (loss, grads), _ = jax.lax.scan(body, zero, (tokens, targets, weights))
    return loss, grads


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@functools.partial(jax.jit, static_argnames=("c", "quant", "opt"),
                   donate_argnums=(0, 1, 2))
def train_step(params, m, v, step, tokens, targets, weights, *, c, quant,
               opt):
    """One step: loss, gradient clipped to the global norm, AdamW without
    weight decay, parameters kept in their own dtype. ``opt`` is a tuple
    of (name, value) pairs: lr, b1, b2, eps, clip. Returns the new
    params, moments, the loss and the clipped gradient's leaf norms."""
    o = dict(opt)
    loss, g = grads_and_loss(params, tokens, targets, weights, dict(c),
                             quant)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(
        1.0, o["clip"] / jnp.maximum(norm, 1e-9)), g)
    t = step.astype(jnp.float32) + 1.0
    c1, c2 = 1.0 - o["b1"] ** t, 1.0 - o["b2"] ** t
    m = jax.tree.map(lambda a, x: o["b1"] * a + (1 - o["b1"]) * x, m, g)
    v = jax.tree.map(lambda a, x: o["b2"] * a + (1 - o["b2"]) * x * x, v, g)
    params = jax.tree.map(
        lambda p, a, b: (p.astype(jnp.float32) - o["lr"] * (a / c1) / (
            jnp.sqrt(b / c2) + o["eps"])).astype(p.dtype), params, m, v)
    return params, m, v, loss, leaf_norms(g)


def zeros_f32(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def frozen(c: dict):
    """A dict as a hashable static argument."""
    return tuple(sorted(c.items()))
