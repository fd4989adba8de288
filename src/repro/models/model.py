"""Model assembly: any ArchConfig -> init / train-forward / prefill / decode.

Layers are scanned over *periods* of the repeating ``layer_pattern`` (dense
archs: period 1; Jamba: period 8). Params and KV/SSM caches carry a leading
``n_periods`` axis so the whole stack is a single ``lax.scan`` — compact HLO,
fast 512-device dry-run compiles. The period body is rematerialized
(``jax.checkpoint``) under a configurable policy.

``first_k_dense`` leading attention layers with a dense MLP (DeepSeek's
``first_k_dense_replace``) run first, as a scan of their own under the
same remat policy: params ``lead``, and in a decode cache one more entry
after the pattern positions'.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.act_sharding import constrain, constrain_tree, strip_leading
from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as S

PyTree = Any


# ---------------------------------------------------------------------------
# init


def _init_layer(rng, cfg: ArchConfig, kind: str, moe: bool,
                cross: bool = False):
    ks = jax.random.split(rng, 4)
    p: Dict[str, PyTree] = {"norm1": L.init_norm(cfg),
                            "norm2": L.init_norm(cfg)}
    if kind == "attn":
        p["mixer"] = (A.init_mla(ks[0], cfg) if cfg.attention == "mla"
                      else A.init_gqa(ks[0], cfg))
    elif kind == "mamba":
        p["mixer"] = S.init_mamba(ks[0], cfg)
    elif kind == "rwkv":
        p["mixer"] = S.init_rwkv(ks[0], cfg)
    else:
        raise ValueError(kind)

    if kind == "rwkv":
        p["ffn"] = S.init_rwkv_channel(ks[1], cfg)
    elif moe:
        p["ffn"] = M.init_moe(ks[1], cfg, cfg.moe)
    else:
        p["ffn"] = L.init_mlp(ks[1], cfg)

    if cross:
        p["norm_x"] = L.init_norm(cfg)
        p["cross"] = A.init_gqa(ks[2], cfg, cross=True)
    return p


def _stack_layers(per_period):
    """[period0_params, period1_params, ...] -> leaves stacked on axis 0."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_period)


def init_model(rng, cfg: ArchConfig, max_pos: int = 32768):
    ks = jax.random.split(rng, 8)
    params: Dict[str, PyTree] = {"embed": L.init_embed(ks[0], cfg)}
    if cfg.rope == "learned":
        params["embed"]["pos"] = (jax.random.normal(
            ks[5], (max_pos, cfg.d_model), jnp.float32) * 0.01
        ).astype(jnp.dtype(cfg.param_dtype))

    if cfg.first_k_dense:
        params["lead"] = _stack_layers([
            _init_layer(jax.random.fold_in(ks[6], li), cfg, "attn", False)
            for li in range(cfg.first_k_dense)])
    period = cfg.period
    blocks = []
    for pos in range(period):
        per_period = []
        for pi in range(cfg.n_periods):
            idx = pi * period + pos
            per_period.append(_init_layer(
                jax.random.fold_in(ks[1], idx), cfg, cfg.layer_pattern[pos],
                cfg.moe_on_layer(idx), cross=cfg.encoder_decoder))
        blocks.append(_stack_layers(per_period))
    params["blocks"] = tuple(blocks)
    params["norm_f"] = L.init_norm(cfg)

    if cfg.encoder_decoder:
        enc = []
        for li in range(cfg.encoder_layers):
            enc.append(_init_layer(jax.random.fold_in(ks[2], li), cfg,
                                   "attn", False))
        params["encoder"] = _stack_layers(enc)
        params["enc_norm_f"] = L.init_norm(cfg)
        params["enc_pos"] = (jax.random.normal(
            ks[3], (cfg.encoder_seq, cfg.d_model), jnp.float32) * 0.01
        ).astype(jnp.dtype(cfg.param_dtype))
    return params


# ---------------------------------------------------------------------------
# caches


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               abstract: bool = False):
    """Decode cache pytree; leading n_periods axis per pattern position,
    then (``first_k_dense``) the leading layers' with a leading axis of
    their count."""
    dt = jnp.dtype(cfg.compute_dtype)
    P = cfg.n_periods
    hd = cfg.resolved_head_dim

    def z(shape, d=dt):
        if abstract:
            return jax.ShapeDtypeStruct(shape, d)
        return jnp.zeros(shape, d)

    def attn(n):
        if cfg.attention == "mla":
            m = cfg.mla
            return {"ckv": z((n, batch, max_len, m.kv_lora_rank)),
                    "kr": z((n, batch, max_len, m.qk_rope_head_dim))}
        return {"k": z((n, batch, max_len, cfg.n_kv_heads, hd)),
                "v": z((n, batch, max_len, cfg.n_kv_heads, hd))}

    blocks = []
    for pos, kind in enumerate(cfg.layer_pattern):
        if kind == "attn":
            mix = attn(P)
            ffn = {}
        elif kind == "mamba":
            di = cfg.ssm.expand * cfg.d_model
            mix = {"h": z((P, batch, di, cfg.ssm.d_state), jnp.float32),
                   "conv": z((P, batch, cfg.ssm.d_conv - 1, di))}
            ffn = {}
        elif kind == "rwkv":
            h = cfg.d_model // cfg.rwkv.head_dim
            mix = {"wkv": z((P, batch, h, cfg.rwkv.head_dim,
                             cfg.rwkv.head_dim), jnp.float32),
                   "tm_x": z((P, batch, cfg.d_model))}
            ffn = {"cm_x": z((P, batch, cfg.d_model))}
        else:
            raise ValueError(kind)
        blk = {"mixer": mix, "ffn": ffn}
        if cfg.encoder_decoder:
            blk["cross"] = {"ck": z((P, batch, cfg.encoder_seq,
                                     cfg.n_kv_heads, hd)),
                            "cv": z((P, batch, cfg.encoder_seq,
                                     cfg.n_kv_heads, hd))}
        blocks.append(blk)
    if cfg.first_k_dense:
        blocks.append({"mixer": attn(cfg.first_k_dense), "ffn": {}})
    return tuple(blocks)


# ---------------------------------------------------------------------------
# layer application


def _apply_mixer(p, x, kind, cfg, *, positions, cache, cache_index,
                 return_cache, page_table=None):
    if kind == "attn":
        if cfg.attention == "mla":
            return A.apply_mla(p, x, cfg, positions=positions, cache=cache,
                               cache_index=cache_index,
                               return_cache=return_cache,
                               page_table=page_table)
        return A.apply_gqa(p, x, cfg, positions=positions, cache=cache,
                           cache_index=cache_index,
                           return_cache=return_cache,
                           page_table=page_table)
    if kind == "mamba":
        return S.apply_mamba(p, x, cfg, cache=cache,
                             return_cache=return_cache)
    if kind == "rwkv":
        return S.apply_rwkv_time(p, x, cfg, cache=cache,
                                 return_cache=return_cache)
    raise ValueError(kind)


def _apply_layer(p, x, kind, cfg, *, moe, positions, loss_weights=None,
                 cache=None, cache_index=None, return_cache=False,
                 enc_out=None, page_table=None):
    """Returns (x, aux, new_cache); aux holds an MoE layer's readings
    (``repro.models.moe.apply_moe``), else nothing."""
    mix_cache = cache["mixer"] if cache else None
    h = L.apply_norm(p["norm1"], x, cfg)
    y, new_mix = _apply_mixer(p["mixer"], h, kind, cfg, positions=positions,
                              cache=mix_cache, cache_index=cache_index,
                              return_cache=return_cache,
                              page_table=page_table)
    x = constrain(x + y, "act")

    new_cross = None
    if enc_out is not None or (cache and "cross" in cache and cfg.encoder_decoder):
        hx = L.apply_norm(p["norm_x"], x, cfg)
        cross_cache = cache["cross"] if cache else None
        if cache is not None and cache_index is not None:
            y, new_cross = A.apply_gqa(p["cross"], hx, cfg, kv_x=None,
                                       cache=cross_cache, positions=None,
                                       causal=False)
        else:
            y, new_cross = A.apply_gqa(p["cross"], hx, cfg, kv_x=enc_out,
                                       positions=None, causal=False,
                                       return_cache=return_cache)
        x = constrain(x + y, "act")

    aux = {}
    h = L.apply_norm(p["norm2"], x, cfg)
    new_ffn = {}
    if kind == "rwkv":
        ffn_cache = cache["ffn"] if cache else None
        y, new_ffn_c = S.apply_rwkv_channel(p["ffn"], h, cfg,
                                            cache=ffn_cache,
                                            return_cache=return_cache)
        new_ffn = new_ffn_c or {}
    elif moe:
        y, aux = M.apply_moe(p["ffn"], h, cfg, cfg.moe, loss_weights)
    else:
        y = L.apply_mlp(p["ffn"], h, cfg)
    x = constrain(x + y, "act")

    new_cache = None
    if return_cache or (cache is not None and cache_index is not None):
        new_cache = {"mixer": new_mix or {}, "ffn": new_ffn}
        if cfg.encoder_decoder:
            new_cache["cross"] = new_cross or (cache["cross"] if cache else {})
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# encoder (whisper)


def _apply_encoder(params, enc_embed, cfg: ArchConfig):
    x = enc_embed.astype(jnp.dtype(cfg.compute_dtype))
    x = x + params["enc_pos"][None, :x.shape[1]].astype(x.dtype)

    def body(h, lp):
        y = L.apply_norm(lp["norm1"], h, cfg)
        y, _ = A.apply_gqa(lp["mixer"], y, cfg, positions=None, causal=False)
        h = h + y
        y = L.apply_norm(lp["norm2"], h, cfg)
        h = h + L.apply_mlp(lp["ffn"], y, cfg)
        return h, None

    # drop cross-attn params the stacked encoder layers don't use
    enc_params = {k: v for k, v in params["encoder"].items()
                  if k not in ("norm_x", "cross")}
    x, _ = jax.lax.scan(lambda h, lp: body(h, lp), x, enc_params)
    return L.apply_norm(params["enc_norm_f"], x, cfg)


# ---------------------------------------------------------------------------
# forward


def _add_aux(acc, a):
    """Fold one layer's readings into the running ones."""
    out = dict(acc)
    for k, v in a.items():
        out[k] = jnp.maximum(acc[k], v) if k == "held_max_load" \
            else acc[k] + v
    return out


def _positions_for(cfg: ArchConfig, b: int, s: int, offset=0):
    off = jnp.asarray(offset, jnp.int32)
    if off.ndim:                                     # per-sequence offsets
        pos = jnp.arange(s, dtype=jnp.int32)[None] + off[:, None]
    else:
        pos = jnp.arange(s, dtype=jnp.int32)[None] + off  # (B,S) via bcast
    pos = jnp.broadcast_to(pos, (b, s))
    if cfg.rope == "mrope":
        return jnp.broadcast_to(pos[None], (3, b, s))
    return pos


def apply_model(params, tokens, cfg: ArchConfig, *,
                enc_embed=None, cache=None, cache_index=None,
                mode: str = "train", loss_weights=None,
                remat_policy: str = "full",
                logits_chunk: int = 0,
                param_specs=None, page_table=None):
    """Returns (logits, aux, new_cache). ``aux``: ``balance``, the MoE
    balance loss summed over layers (0 without experts), and for a model
    with experts the step's ``held_pairs`` (summed over layers) and
    ``held_max_load`` (the largest over layers). ``loss_weights`` (B, S)
    weight the rows of the balance loss (``repro.models.moe``).

    mode: "train" (no cache), "prefill" (returns populated cache),
          "decode" (tokens (B,1), cache + cache_index required;
          cache_index may be scalar or (B,) per-sequence lengths, and
          with a paged cache ``page_table`` (B, Pmax) routes attention
          KV through the page pools — see repro.serve.kv_cache).

    Decode is scan-safe end to end: ``cache``, ``cache_index`` and the
    tokens may all be carries of an outer ``lax.scan`` (the serving
    engine's decode superstep, DESIGN.md §12) — positions, learned/rope
    embeddings and the paged appends are computed from the traced
    per-sequence lengths, never from host state.
    """
    b, s = tokens.shape
    decode = mode == "decode"
    prefill = mode == "prefill"
    offset = cache_index if decode else 0
    positions = _positions_for(cfg, b, s, offset)

    if param_specs is not None:
        # manual ZeRO-3: gather non-block params from the storage layout
        # (FSDP over "data") into the TP compute layout; block params are
        # gathered per scan iteration inside period_body. The transpose of
        # these constraints reduce-scatters the gradients back.
        params = dict(params)
        for key in ("embed", "norm_f", "enc_norm_f", "enc_pos", "encoder"):
            if key in params and key in param_specs:
                params[key] = constrain_tree(params[key], param_specs[key])
        blk_specs = [strip_leading(ps) for ps in param_specs["blocks"]]
        lead_specs = ([strip_leading(param_specs["lead"])]
                      if "lead" in param_specs else None)
    else:
        blk_specs = lead_specs = None

    x = constrain(L.embed_tokens(params["embed"], tokens, cfg), "act")
    if cfg.rope == "learned":
        ptab = params["embed"]["pos"]
        if decode and jnp.ndim(cache_index):
            pe = ptab[cache_index][:, None]          # (B, 1, d)
        elif decode:
            pe = jax.lax.dynamic_slice_in_dim(ptab, cache_index, 1)[None]
        else:
            pe = ptab[None, :s]
        x = x + pe.astype(x.dtype)

    enc_out = None
    if cfg.encoder_decoder:
        if enc_embed is not None:
            enc_out = _apply_encoder(params, enc_embed, cfg)
        # decode mode: cross K/V comes from cache

    pattern = cfg.layer_pattern

    def stack_body(kinds, moes, specs):
        """The scan body over one stack: a period of ``kinds`` layers."""
        def body(carry, xs):
            x, aux = carry
            x = constrain(x, "act")
            if cache is not None:
                blk_params, blk_caches = xs
            else:
                blk_params, blk_caches = xs, [None] * len(kinds)
            if specs is not None:
                blk_params = tuple(
                    constrain_tree(bp, bs)
                    for bp, bs in zip(blk_params, specs))
            new_caches = []
            for pos, kind in enumerate(kinds):
                x, a, nc = _apply_layer(
                    blk_params[pos], x, kind, cfg, moe=moes[pos],
                    positions=positions, loss_weights=loss_weights,
                    cache=blk_caches[pos] if cache is not None else None,
                    cache_index=cache_index if decode else None,
                    return_cache=prefill, enc_out=enc_out,
                    page_table=page_table if decode else None)
                aux = _add_aux(aux, a)
                new_caches.append(nc)
            out_caches = tuple(new_caches) if (decode or prefill) else None
            return (x, aux), out_caches

        if mode == "train" and remat_policy != "none":
            policy = None
            if remat_policy == "dots":
                policy = \
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            return jax.checkpoint(body, policy=policy, prevent_cse=False)
        return body

    aux = {"balance": jnp.zeros((), jnp.float32)}
    if cfg.moe is not None:
        aux.update(held_pairs=jnp.zeros((), jnp.float32),
                   held_max_load=jnp.zeros((), jnp.float32))
    lead_cache = None
    if cfg.first_k_dense:
        lead = (params["lead"],)
        xs = (lead, (cache[len(pattern)],)) if cache is not None else lead
        (x, aux), lead_cache = jax.lax.scan(
            stack_body(("attn",), (False,), lead_specs), (x, aux), xs)
        cache = cache[:len(pattern)] if cache is not None else None

    blocks = params["blocks"]
    body = stack_body(pattern, tuple(cfg.moe_on_layer(pos)
                                     for pos in range(len(pattern))),
                      blk_specs)
    xs = (blocks, cache) if cache is not None else blocks
    (x, aux), new_cache = jax.lax.scan(body, (x, aux), xs)
    if lead_cache is not None:
        new_cache = new_cache + lead_cache
    x = constrain(L.apply_norm(params["norm_f"], x, cfg), "act")
    if logits_chunk and not decode:
        logits = None  # computed chunked inside the loss (see lm_loss_chunked)
        return x, aux, new_cache
    logits = constrain(L.unembed(params["embed"], x, cfg), "logits")
    return logits, aux, new_cache


# ---------------------------------------------------------------------------
# losses


def lm_loss(logits, targets, weights, aux=0.0):
    """Weighted token cross-entropy plus ``aux`` (the model's balance
    loss, already scaled by its coefficient). weights carries padding
    *and* the paper's Algorithm-1 agent mask (masked agents' tokens get
    weight 0).

    Sharding-friendly: the gold logit is a fused one-hot contraction (an
    iota-compare-select fused into the vocab reduction) instead of
    ``take_along_axis`` — a gather along a tensor-sharded vocab dim would
    force an all-gather of the fp32 logits.
    """
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    shifted = lf - m
    logz = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    vocab_iota = jnp.arange(logits.shape[-1], dtype=jnp.int32)
    onehot = (targets[..., None] == vocab_iota)
    gold = jnp.sum(jnp.where(onehot, lf, 0.0), axis=-1)
    xent = logz - gold
    w = weights.astype(jnp.float32)
    loss = jnp.sum(xent * w) / jnp.maximum(jnp.sum(w), 1.0)
    return loss + aux


def classifier_loss(logits, labels, weights):
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    w = weights.astype(jnp.float32)
    return jnp.sum((logz - gold) * w) / jnp.maximum(jnp.sum(w), 1.0)


# ---------------------------------------------------------------------------
# parameter counting


@functools.lru_cache(maxsize=64)
def _param_shapes(cfg: ArchConfig, max_pos: int = 32768):
    return jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg, max_pos=max_pos))


def count_params(cfg: ArchConfig, active_only: bool = False,
                 max_pos: int = 32768) -> int:
    import math as _math
    shapes = _param_shapes(cfg, max_pos)
    total = sum(_math.prod(l.shape) for l in jax.tree.leaves(shapes))
    if active_only and cfg.moe is not None:
        moe = cfg.moe
        n_moe = sum(1 for i in range(cfg.n_layers - cfg.first_k_dense)
                    if cfg.moe_on_layer(i))
        per_expert = 3 * cfg.d_model * moe.d_ff_expert
        inactive = n_moe * max(moe.n_held - moe.top_k, 0) * per_expert
        total -= inactive
    return total
