"""Serving steps: prefill (builds the KV/SSM cache) and decode (one token).

Inference has no gradient aggregation, but the paper's waiting rule very
much applies to serving: a replicated deployment fans each request out to
n model replicas and proceeds with the first n-r completions
(``repro.serve.dispatch``, DESIGN.md §9) — Algorithm 1's S^t set with
requests in place of gradients. The serving memory/scheduling substrate
(paged KV/SSM cache, continuous batching) lives in ``repro.serve``;
``greedy_generate`` below is the small driver over it that examples and
tests use. These steps are what decode_32k / long_500k / prefill_32k
dry-run and roofline.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.model import apply_model


def _ctx(dp, tp, sizes=None):
    import contextlib
    from repro.dist.act_sharding import act_policy
    return act_policy(dp, tp, sizes) if dp is not None \
        else contextlib.nullcontext()


def make_prefill_step(cfg: ArchConfig, dp=None, tp=None,
                      sizes=None) -> Callable:
    def prefill(params, batch):
        with _ctx(dp, tp, sizes):
            logits, _, cache = apply_model(
                params, batch["tokens"], cfg, mode="prefill",
                enc_embed=batch.get("enc_embed"), remat_policy="none")
        last = logits[:, -1]
        next_tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return prefill


def make_decode_step(cfg: ArchConfig, temperature: float = 0.0, dp=None,
                     tp=None, sizes=None) -> Callable:
    def decode(params, batch):
        with _ctx(dp, tp, sizes):
            logits, _, cache = apply_model(
                params, batch["tokens"], cfg, mode="decode",
                cache=batch["cache"], cache_index=batch["pos"],
                remat_policy="none")
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, cache

    return decode


def greedy_generate(params, cfg: ArchConfig, prompt, max_len: int,
                    steps: int, page_size: int = 8,
                    superstep_k: int = 8, mesh=None, rules=None):
    """CPU-scale generation driver on the paged serving engine.

    Returns ``prompt`` extended with exactly ``steps`` new tokens per row.
    The first token comes from the prefill logits (the old driver redid a
    full train-mode forward for it and dropped the final decode's token);
    equal-length prompts admit as one group, so the whole batch costs one
    prefill plus ``steps - 1`` decode iterations, grouped into
    ``ceil((steps - 1) / superstep_k)`` device-resident supersteps
    (``superstep_k=1`` forces the per-token host loop). A ``mesh`` (plus
    optional ``MeshRules``) runs the engine tensor-parallel — KV pools
    sharded over the kv-head dim, the decode kernel per-shard — with a
    token stream identical to the replicated engine (DESIGN.md §14).
    """
    import numpy as np
    from repro.serve import PagedCacheConfig, ServeEngine

    b, s0 = prompt.shape
    total = s0 + steps
    if total > max_len:
        raise ValueError(f"prompt {s0} + steps {steps} > max_len {max_len}")
    per_seq = -(-total // page_size)
    ccfg = PagedCacheConfig(num_slots=b, page_size=page_size,
                            num_pages=b * per_seq + 1,
                            max_pages_per_seq=per_seq)
    engine = ServeEngine(params, cfg, ccfg, superstep_k=superstep_k,
                         mesh=mesh, rules=rules)
    rids = [engine.submit(np.asarray(prompt[i]), steps) for i in range(b)]
    out = engine.run()
    new = jnp.asarray(np.stack([out[rid] for rid in rids]))
    return jnp.concatenate([prompt, new], axis=1)
