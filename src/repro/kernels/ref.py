"""Pure-jnp oracles for the Pallas kernels (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ref_flash_attention(q, k, v, *, causal: bool = True):
    """q,k: (B,H,S,D); v: (B,H,T,Dv) -> (B,H,S,Dv). fp32 softmax."""
    d = q.shape[-1]
    s_ = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * (d ** -0.5)
    if causal:
        sq, t = q.shape[2], k.shape[2]
        mask = jnp.arange(t)[None, :] <= jnp.arange(sq)[:, None]
        s_ = jnp.where(mask[None, None], s_, NEG_INF)
    w = jax.nn.softmax(s_, axis=-1)
    return jnp.einsum("bhst,bhtv->bhsv", w,
                      v.astype(jnp.float32)).astype(q.dtype)


def ref_paged_decode_attention(q, k_pages, v_pages, page_table, kv_lens):
    """Single-query attention over a paged KV cache (pure-jnp oracle).

    q: (B, H, D) — one query token per sequence.
    k_pages: (N, Hkv, PS, D); v_pages: (N, Hkv, PS, Dv) — the head-major
        physical page pool (N pages of PS tokens each), KV heads grouped
        (H % Hkv == 0).
    page_table: (B, Pmax) int32 — logical page p of sequence b lives in
        physical page page_table[b, p]; entries past the sequence may be
        any *valid* index (they are masked by kv_lens).
    kv_lens: (B,) int32 — valid tokens per sequence; for causal self-decode
        the query sits at position kv_lens-1, so the length mask *is* the
        causal mask; for cross-attention kv_lens is the memory length.

    Returns (B, H, Dv) in q.dtype with an fp32 softmax.

    Grouped math, mirroring the kernel: the query is reshaped to
    (B, Hkv, G, D) and contracted against the *un-repeated* (B, Hkv, T, ·)
    gathered KV — head h of the flat output is group lane h % G of KV head
    h // G, the layout ``jnp.repeat(kv, G, axis=heads)`` expands to. This
    is also the production CPU path (``kernels/ops`` routes non-TPU "auto"
    here), so skipping the H-fold KV materialization matters beyond
    aesthetics.
    """
    b, h, d = q.shape
    hkv, ps = k_pages.shape[1], k_pages.shape[2]
    g = h // hkv
    dv = v_pages.shape[-1]
    tbl = jnp.maximum(page_table, 0)
    pmax = tbl.shape[1]
    t = pmax * ps

    def gather(pool):                      # (B, Pmax, Hkv, PS, .) -> (B, Hkv, T, .)
        x = pool[tbl].transpose(0, 2, 1, 3, 4)
        return x.reshape(b, hkv, t, -1).astype(jnp.float32)

    k, v = gather(k_pages), gather(v_pages)
    qg = q.reshape(b, hkv, g, d)
    s_ = jnp.einsum("bkgd,bktd->bkgt", qg.astype(jnp.float32), k) \
        * (d ** -0.5)
    mask = jnp.arange(t)[None, :] < kv_lens[:, None]          # (B, T)
    s_ = jnp.where(mask[:, None, None, :], s_, NEG_INF)
    w = jax.nn.softmax(s_, axis=-1)
    # all-masked rows (kv_len == 0) produce a uniform softmax; zero them
    w = jnp.where(jnp.any(mask, axis=1)[:, None, None, None], w, 0.0)
    out = jnp.einsum("bkgt,bktv->bkgv", w, v)
    return out.reshape(b, h, dv).astype(q.dtype)


def ref_masked_cge_reduce(g, received, f: int):
    """CGE aggregate oracle: exactly ``gradagg.agg_cge`` in f32 (the
    keep-set math exists once — ``cge_mask_from_norms``)."""
    from repro.core import gradagg
    return gradagg.agg_cge(g.astype(jnp.float32), received, f)


def ref_trimmed_mean(g, received, f: int):
    """Coordinate-wise trimmed-mean oracle: ``gradagg.agg_trimmed_mean``
    in f32 (full sort; the kernel's running min/max must match it)."""
    from repro.core import gradagg
    return gradagg.agg_trimmed_mean(g.astype(jnp.float32), received, f)


def ref_dequant_accum(q, scale, received):
    """q: (n, P) int8, scale: (n,) f32 -> (P,) f32 masked dequant sum."""
    w = scale.astype(jnp.float32) * received.astype(jnp.float32)
    return jnp.sum(q.astype(jnp.float32) * w[:, None], axis=0)


def ref_block_sq_norms(x):
    """x: (n, w) -> (n,) fp32 squared norms."""
    xf = x.astype(jnp.float32)
    return jnp.sum(xf * xf, axis=1)


def ref_masked_scale(x, scale):
    return (x.astype(jnp.float32) * scale[:, None]).astype(x.dtype)
