"""Plain reference of the Qwen2 decoder, in float32 ``jax.numpy``.

Follows the published architecture (transformers ``Qwen2ForCausalLM``):
RMSNorm (eps from the configuration), q/k/v projections with biases,
rotary embeddings on half-split head dims (theta from the
configuration), grouped-query causal attention scaled by head_dim^-0.5,
SwiGLU MLP, final RMSNorm, LM head tied to the embedding. Every matmul
runs at ``Precision.HIGHEST``. It imports nothing of the program and uses
only the weights ``bench/weights.py`` makes from the seed, upcast from
the dtype they are served in.

Departures from the program, which computes in bfloat16: everything here
stays in float32 (the program rounds activations to bfloat16 between
operations, and its optimizer casts the update to bfloat16 before
applying it).

``quant=True`` is the low-precision control: every matmul's operands,
the attention's included, are rounded to int8, symmetric, per row of the
left operand and per output column of the right one, with
straight-through gradients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
NEG_INF = -1e30


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


def rope(x, theta: float):
    """x: (S, H, D); positions 0..S-1; half-split rotation."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    f = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(f)[:, None], jnp.sin(f)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(x, lp, c: dict, quant: bool):
    """One decoder layer on one sequence x (S, d), float32 params."""
    s = x.shape[0]
    h, hkv, hd = c["h"], c["hkv"], c["hd"]
    mx, ffn = lp["mixer"], lp["ffn"]
    y = rms(x, lp["norm1"]["scale"], c["eps"])
    q = (mm(y, mx["wq"], quant) + mx["bq"]).reshape(s, h, hd)
    k = (mm(y, mx["wk"], quant) + mx["bk"]).reshape(s, hkv, hd)
    v = (mm(y, mx["wv"], quant) + mx["bv"]).reshape(s, hkv, hd)
    q, k = rope(q, c["theta"]), rope(k, c["theta"])
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    if quant:
        q, k = fake_int8(q, -1), fake_int8(k, -1)
    sc = jnp.einsum("shd,thd->hst", q, k, precision=HI) * hd ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], sc, NEG_INF), axis=-1)
    if quant:
        p, v = fake_int8(p, -1), fake_int8(v, 0)
    o = jnp.einsum("hst,thd->shd", p, v, precision=HI).reshape(s, h * hd)
    x = x + mm(o, mx["wo"], quant)
    y = rms(x, lp["norm2"]["scale"], c["eps"])
    g = mm(y, ffn["w_gate"], quant)
    u = mm(y, ffn["w_up"], quant)
    return x + mm(jax.nn.silu(g) * u, ffn["w_down"], quant)


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def hidden(params, tokens, c: dict, quant: bool):
    """Final-normed hidden states (S, d) of one sequence."""
    x = params["embed"]["tok"][tokens].astype(jnp.float32)

    def body(x, lp):
        return layer(x, f32(lp), c, quant), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["blocks"][0])
    return rms(x, params["norm_f"]["scale"].astype(jnp.float32), c["eps"])


def logits(params, x, quant: bool):
    return mm(x, params["embed"]["tok"].astype(jnp.float32).T, quant)


def consts(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    h = cfg["num_attention_heads"]
    return {"h": h, "hkv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or cfg["hidden_size"] // h,
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"])}


# ---------------------------------------------------------------------------
# serving: the gap of each served token below the reference's best


@functools.partial(jax.jit, static_argnames=("c", "n_out", "control"))
def served_gaps(params, tokens, start, served, *, c, n_out: int,
                control: bool):
    """``tokens`` (L,): prompt then served tokens, right-padded; the
    served token i was produced at position ``start + i``. Returns the
    reference's best logit minus its logit of each served token, (n_out,)
    and, with ``control``, the same gap for the token that the int8
    control puts first at each position."""
    cd = dict(c)
    x = hidden(params, tokens, cd, False)
    xs = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    ref = logits(params, xs, False)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    gaps = best - got
    if not control:
        return gaps, jnp.zeros_like(gaps)
    xq = hidden(params, tokens, cd, True)
    lq = logits(params, jax.lax.dynamic_slice_in_dim(xq, start, n_out, 0),
                True)
    pick = jnp.argmax(lq, axis=-1)
    ctrl = best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return gaps, ctrl


# ---------------------------------------------------------------------------
# training: Algorithm 1's masked loss, clipped gradient and AdamW


CE_CHUNK = 256


def row_loss_sum(p32, tokens, targets, weights, c: dict, quant: bool):
    """Sum over one row of weight * cross-entropy; the vocabulary-wide
    logits are made a chunk of positions at a time."""
    x = hidden(p32, tokens, c, quant)
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
    return total


def grads_and_loss(params, tokens, targets, weights, c: dict, quant: bool):
    """Mean weighted cross-entropy over the rows and its gradient, in
    float32, one row at a time (rows of weight 0 add nothing and are left
    out by the caller)."""
    p32 = f32(params)
    total_w = jnp.maximum(jnp.sum(weights), 1.0)
    vg = jax.value_and_grad(
        lambda p, t, y, w: row_loss_sum(p, t, y, w, c, quant))

    def body(acc, row):
        l, g = vg(p32, *row)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p32))
    (lsum, gsum), _ = jax.lax.scan(body, zero, (tokens, targets, weights))
    return lsum / total_w, jax.tree.map(lambda g: g / total_w, gsum)


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
