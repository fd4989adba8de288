"""End-to-end serving engine: token parity with a train-mode greedy
rollout (paging + continuous batching are exact, not approximate), the
greedy_generate regression (prefill logits reused, off-by-one fixed, call
counts pinned), mid-stream admission/slot reuse, and paged-vs-dense
decode-step logit parity for the MLA and hybrid arch families.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.launch.serve import greedy_generate
from repro.models.model import apply_model, init_model
from repro.serve import PagedCacheConfig, ServeEngine


def _rollout(params, cfg, prompt, steps):
    """Greedy argmax rollout via full train-mode forwards (no cache)."""
    seq = prompt[None] if prompt.ndim == 1 else prompt
    for _ in range(steps):
        logits, _, _ = apply_model(params, seq, cfg, mode="train")
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt], axis=1)
    return np.asarray(seq)


def _setup(arch, seed=0, max_pos=64):
    cfg = get_config(arch).reduced()
    params = init_model(jax.random.PRNGKey(seed), cfg, max_pos=max_pos)
    return cfg, params


# -- token parity -------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "rwkv6-3b",
                                  "deepseek-v2-236b"])
def test_engine_ragged_matches_rollout(arch):
    """Three requests, different prompt lengths and budgets, one shared
    2-slot engine: each stream must equal its isolated greedy rollout."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(3)
    prompts = [np.asarray(rng.integers(0, cfg.vocab_size, s), np.int32)
               for s in (5, 9, 3)]
    budgets = [4, 3, 5]
    ccfg = PagedCacheConfig(num_slots=2, page_size=4, num_pages=24,
                            max_pages_per_seq=8)
    eng = ServeEngine(params, cfg, ccfg)
    rids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    out = eng.run()
    assert eng.sched.peak_active <= 2 and eng.stats["admitted"] == 3
    for p, n, rid in zip(prompts, budgets, rids):
        ref = _rollout(params, eng.cfg, jnp.asarray(p), n)[0, p.size:]
        np.testing.assert_array_equal(out[rid], ref)
    # all pages returned after the last retire
    assert eng.kv.alloc.n_used == 0


def test_engine_rejects_rules_without_mesh():
    """rules= without mesh= used to be silently discarded, masking a
    misconfiguration — it must raise."""
    from repro.dist.sharding import MeshRules
    cfg = get_config("qwen2-0.5b").reduced()
    with pytest.raises(ValueError, match="rules= provided without mesh="):
        ServeEngine({}, cfg, rules=MeshRules(
            fsdp_axes=(), axis_sizes={"model": 2}))


def test_engine_midstream_admission_slot_reuse():
    """A request submitted while the engine is mid-decode is picked up at
    the next step and lands in a retired request's slot."""
    cfg, params = _setup("qwen2-0.5b")
    rng = np.random.default_rng(4)
    p1 = np.asarray(rng.integers(0, cfg.vocab_size, 6), np.int32)
    p2 = np.asarray(rng.integers(0, cfg.vocab_size, 4), np.int32)
    ccfg = PagedCacheConfig(num_slots=1, page_size=4, num_pages=16,
                            max_pages_per_seq=8)
    eng = ServeEngine(params, cfg, ccfg)
    r1 = eng.submit(p1, 3)
    eng.step()                               # admit + first decode
    r2 = eng.submit(p2, 4)                   # arrives mid-stream
    out = eng.run()
    for p, n, rid in ((p1, 3, r1), (p2, 4, r2)):
        ref = _rollout(params, eng.cfg, jnp.asarray(p), n)[0, p.size:]
        np.testing.assert_array_equal(out[rid], ref)
    assert eng.sched.finished[r2].slot == eng.sched.finished[r1].slot


# -- greedy_generate regression (the PR's driver bugfix) ----------------


def test_greedy_generate_counts_and_parity():
    """steps new tokens from exactly one prefill (whose logits supply the
    first token — no second train-mode forward) + steps-1 decode
    iterations grouped into budget-bounded supersteps, and the stream
    equals the train-mode greedy rollout (off-by-one fixed: the final
    decoded token lands)."""
    cfg, params = _setup("qwen2-0.5b")
    prompt = jax.random.randint(jax.random.PRNGKey(2), (3, 7), 0,
                                cfg.vocab_size)
    steps = 5
    out = greedy_generate(params, cfg, prompt, max_len=32, steps=steps)
    assert out.shape == (3, 7 + steps)
    np.testing.assert_array_equal(np.asarray(out),
                                  _rollout(params, cfg, prompt, steps))

    # call counts, via the engine greedy_generate drives: equal budgets
    # mean K = steps-1 fits one superstep — 2 host syncs for the whole
    # batch (prefill + one superstep boundary), not one per token
    ccfg = PagedCacheConfig(num_slots=3, page_size=8, num_pages=3 * 2 + 1,
                            max_pages_per_seq=2)
    eng = ServeEngine(params, cfg, ccfg, superstep_k=8)
    rids = [eng.submit(np.asarray(prompt[i]), steps) for i in range(3)]
    out2 = eng.run()
    assert eng.stats["prefill_calls"] == 1      # one batched prefill
    assert eng.stats["decode_steps"] == steps - 1
    assert eng.stats["supersteps"] == 1
    assert eng.stats["host_syncs"] == 2
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out2[rid], np.asarray(out[i, 7:]))

    # capped supersteps: K=2 splits the same stream into ceil(4/2)=2
    # boundaries without changing a single token
    eng2 = ServeEngine(params, cfg, ccfg, superstep_k=2)
    rids2 = [eng2.submit(np.asarray(prompt[i]), steps) for i in range(3)]
    out3 = eng2.run()
    assert eng2.stats["supersteps"] == 2
    assert eng2.stats["decode_steps"] == steps - 1
    for i, rid in enumerate(rids2):
        np.testing.assert_array_equal(out3[rid], np.asarray(out[i, 7:]))


def test_greedy_generate_single_step_needs_no_decode():
    cfg, params = _setup("qwen2-0.5b")
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 4), 0,
                                cfg.vocab_size)
    ccfg = PagedCacheConfig(num_slots=2, page_size=4, num_pages=8,
                            max_pages_per_seq=2)
    eng = ServeEngine(params, cfg, ccfg)
    for i in range(2):
        eng.submit(np.asarray(prompt[i]), 1)
    eng.run()
    want = {"prefill_calls": 1, "decode_steps": 0, "supersteps": 0,
            "host_syncs": 1, "admitted": 2, "retired": 2,
            "table_uploads": 0}
    assert {k: eng.stats[k] for k in want} == want
    # the prefix/preemption machinery is dormant on the default path
    assert eng.stats["cache_hit_tokens"] == 0
    assert eng.stats["preemptions"] == 0 and eng.stats["cow_forks"] == 0


# -- MLA / hybrid / MoE families: logit-level paged-vs-dense parity -----


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "jamba-v0.1-52b"])
def test_paged_decode_step_matches_dense(arch):
    """One decode step through the full stack: the paged cache must give
    the same logits as the padded dense cache (layout equivalence)."""
    from repro.serve.kv_cache import PagedKVCache
    cfg, params = _setup(arch, seed=1)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 7), 0,
                                cfg.vocab_size)
    logits, _, dense = apply_model(params, prompt, cfg, mode="prefill")
    t1 = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)

    def pad(c):
        if c.ndim >= 3 and c.shape[2] == 7:
            pw = [(0, 0)] * c.ndim
            pw[2] = (0, 9)
            return jnp.pad(c, pw)
        return c

    ld, _, _ = apply_model(params, t1, cfg, mode="decode",
                           cache=jax.tree.map(pad, dense),
                           cache_index=jnp.int32(7), remat_policy="none")

    ccfg = PagedCacheConfig(num_slots=1, page_size=4, num_pages=8,
                            max_pages_per_seq=4)
    kv = PagedKVCache(cfg, ccfg)
    kv.admit(0, dense, 7, 12)
    lp, _, _ = apply_model(params, t1, cfg, mode="decode", cache=kv.cache,
                           cache_index=kv.kv_lens_dev,
                           page_table=kv.page_table_dev,
                           remat_policy="none")
    np.testing.assert_allclose(np.asarray(lp, np.float32),
                               np.asarray(ld, np.float32),
                               atol=1e-5, rtol=1e-5)


def test_prefill_shapes_bucket_by_page():
    """Attention-only archs right-pad prompts to a page multiple, so a
    mixed-length stream compiles at most max_pages_per_seq prefill
    shapes (and same-bucket admissions share one batched prefill) —
    with no effect on the tokens (causal prefixes ignore the pad)."""
    cfg, params = _setup("qwen2-0.5b")
    rng = np.random.default_rng(6)
    prompts = [np.asarray(rng.integers(0, cfg.vocab_size, s), np.int32)
               for s in (5, 7, 3)]                  # one page_size=8 bucket
    ccfg = PagedCacheConfig(num_slots=3, page_size=8, num_pages=16,
                            max_pages_per_seq=4)
    eng = ServeEngine(params, cfg, ccfg)
    rids = [eng.submit(p, 4) for p in prompts]
    out = eng.run()
    assert eng.stats["prefill_calls"] == 1          # one shared bucket
    for p, rid in zip(prompts, rids):
        ref = _rollout(params, eng.cfg, jnp.asarray(p), 4)[0, p.size:]
        np.testing.assert_array_equal(out[rid], ref)
    # recurrent state would absorb right-padding: rwkv buckets exactly
    cfg2, params2 = _setup("rwkv6-3b")
    eng2 = ServeEngine(params2, cfg2, ccfg)
    assert not eng2._pad_buckets


def test_moe_serving_is_drop_free():
    """The MoE layer drops no (token, expert) pair, so a request's tokens
    do not depend on its batch-mates: served beside two others in one
    batch, it decodes exactly as it does alone."""
    cfg, params = _setup("deepseek-v2-236b")
    ccfg = PagedCacheConfig(num_slots=3, page_size=4, num_pages=24,
                            max_pages_per_seq=4)
    rng = np.random.default_rng(7)
    prompts = [np.asarray(rng.integers(0, cfg.vocab_size, s), np.int32)
               for s in (6, 5, 7)]
    alone = ServeEngine(params, cfg, ccfg)
    rid = alone.submit(prompts[0], 5)
    want = alone.run()[rid]
    eng = ServeEngine(params, cfg, ccfg)
    rids = [eng.submit(p, 5) for p in prompts]
    out = eng.run()
    assert eng.sched.peak_active == 3
    np.testing.assert_array_equal(out[rids[0]], want)
