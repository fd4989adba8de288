"""Paged-cache invariants: allocator alloc/free, admission/eviction page
accounting, null-page reservation, and paged-vs-dense prefill round-trip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.model import apply_model, init_model
from repro.serve.kv_cache import (PageAllocator, PagedCacheConfig,
                                  PagedKVCache, pages_needed)


# -- allocator ----------------------------------------------------------


def test_allocator_basic_invariants():
    a = PageAllocator(8)
    assert a.n_free == 7                     # page 0 reserved
    p1 = a.alloc(3)
    assert len(set(p1)) == 3 and 0 not in p1
    p2 = a.alloc(4)
    assert not set(p1) & set(p2)
    assert a.n_free == 0
    a.check_invariants()
    a.free(p1)
    assert a.n_free == 3
    p3 = a.alloc(3)
    assert not set(p3) & set(p2)
    a.check_invariants()


def test_allocator_exhaustion_and_double_free():
    a = PageAllocator(4)
    pages = a.alloc(3)
    with pytest.raises(MemoryError):
        a.alloc(1)
    a.free(pages[:1])
    with pytest.raises(ValueError):
        a.free(pages[:1])                    # double free
    with pytest.raises(ValueError):
        a.free([0])                          # null page is foreign
    a.check_invariants()


def test_pages_needed():
    assert pages_needed(1, 8) == 1
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2


# -- paged cache --------------------------------------------------------


@pytest.fixture(scope="module")
def qwen_setup():
    cfg = get_config("qwen2-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg, max_pos=64)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 11), 0,
                                cfg.vocab_size)
    _, _, dense = apply_model(params, prompt, cfg, mode="prefill")
    return cfg, dense


def test_admit_evict_page_accounting(qwen_setup):
    cfg, dense = qwen_setup
    ccfg = PagedCacheConfig(num_slots=2, page_size=4, num_pages=16,
                            max_pages_per_seq=8)
    kv = PagedKVCache(cfg, ccfg)
    free0 = kv.alloc.n_free
    kv.admit(0, dense, 11, 20)               # 5 pages
    assert kv.alloc.n_free == free0 - pages_needed(20, 4)
    assert int(kv.kv_lens[0]) == 11
    with pytest.raises(ValueError):
        kv.admit(0, dense, 11, 20)           # slot occupied
    kv.evict(0)
    assert kv.alloc.n_free == free0
    assert int(kv.kv_lens[0]) == 0
    assert (kv.page_table[0] == 0).all()     # back to the null page
    with pytest.raises(ValueError):
        kv.evict(0)                          # double evict
    kv.alloc.check_invariants()
    # slot reuse after eviction
    kv.admit(0, dense, 11, 20)
    kv.evict(0)


def test_admit_rejects_oversized(qwen_setup):
    cfg, dense = qwen_setup
    ccfg = PagedCacheConfig(num_slots=1, page_size=4, num_pages=32,
                            max_pages_per_seq=3)
    kv = PagedKVCache(cfg, ccfg)
    assert not kv.can_admit(13)              # 4 pages > table width 3
    with pytest.raises(ValueError):
        kv.admit(0, dense, 11, 13)


def test_paged_scatter_roundtrip(qwen_setup):
    """admit() scatters the prefill KV into pages; gathering it back must
    reproduce the dense cache exactly (ragged last page included)."""
    cfg, dense = qwen_setup
    ccfg = PagedCacheConfig(num_slots=2, page_size=4, num_pages=16,
                            max_pages_per_seq=8)
    kv = PagedKVCache(cfg, ccfg)
    kv.admit(1, dense, 11, 16)               # 11 = 2 full pages + 3 ragged
    for pos, kind in enumerate(cfg.layer_pattern):
        if kind != "attn":
            continue
        for name in kv.cache[pos]["mixer"]:
            got = kv.gather_dense(1, pos, name)
            want = dense[pos]["mixer"][name[: -len("_pages")]][:, 0]
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                atol=0, rtol=0)


def test_null_page_survives_idle_writes(qwen_setup):
    """Idle slots write into page 0 only; a live slot's pages are
    untouched by another slot's traffic (write isolation)."""
    cfg, dense = qwen_setup
    ccfg = PagedCacheConfig(num_slots=2, page_size=4, num_pages=16,
                            max_pages_per_seq=8)
    kv = PagedKVCache(cfg, ccfg)
    kv.admit(0, dense, 11, 12)
    before = {name: np.asarray(kv.gather_dense(0, pos, name))
              for pos, kind in enumerate(cfg.layer_pattern) if kind == "attn"
              for name in kv.cache[pos]["mixer"]}
    # slot 1 idle: its table rows are 0 -> appends land in the null page
    from repro.models.attention import _paged_append
    pos0 = next(i for i, k in enumerate(cfg.layer_pattern) if k == "attn")
    pool = kv.cache[pos0]["mixer"]["k_pages"][0]      # (N, n_kv, PS, hd)
    new = jnp.ones((2, pool.shape[1], pool.shape[3]), pool.dtype)
    out = _paged_append(pool, new, kv.page_table_dev,
                        jnp.asarray([11, 0], jnp.int32), 4, head_major=True)
    # write for the idle row hit page 0
    assert bool((out[0, :, 0] == 1).all())
    blocks = list(kv.cache)
    blk = dict(blocks[pos0])
    blk["mixer"] = dict(blk["mixer"], k_pages=out[None].repeat(
        kv.cache[pos0]["mixer"]["k_pages"].shape[0], axis=0))
    blocks[pos0] = blk
    kv.cache = tuple(blocks)
    after = np.asarray(kv.gather_dense(0, pos0, "k_pages"))
    # slot 0's resident tokens are untouched by the idle slot's write
    np.testing.assert_array_equal(after, before["k_pages"])


def test_device_tables_cached_until_dirty(qwen_setup):
    """Perf regression (ISSUE 4): the decode-only steady state must not
    re-upload page tables/kv_lens every token — only admissions and
    evictions dirty the cached device mirrors; commit_token bumps the
    lengths with a device-side add."""
    cfg, dense = qwen_setup
    ccfg = PagedCacheConfig(num_slots=2, page_size=4, num_pages=16,
                            max_pages_per_seq=8)
    kv = PagedKVCache(cfg, ccfg)
    kv.admit(0, dense, 11, 20)
    kv.admit(1, dense, 11, 20)
    _ = kv.page_table_dev, kv.kv_lens_dev
    uploads0 = kv.table_uploads
    for _step in range(10):                  # pure decode stream
        tbl, lens = kv.page_table_dev, kv.kv_lens_dev
        np.testing.assert_array_equal(np.asarray(tbl), kv.page_table)
        np.testing.assert_array_equal(np.asarray(lens), kv.kv_lens)
        kv.commit_token([0, 1])
    assert kv.table_uploads == uploads0      # zero re-uploads in 10 tokens
    # the device lengths tracked the host bumps without a refresh
    np.testing.assert_array_equal(np.asarray(kv.kv_lens_dev), kv.kv_lens)
    kv.evict(1)                              # occupancy change -> dirty
    _ = kv.kv_lens_dev
    assert kv.table_uploads == uploads0 + 1
    np.testing.assert_array_equal(np.asarray(kv.kv_lens_dev), kv.kv_lens)
    # partial commit (slot set != occupancy) falls back to re-upload
    kv.admit(1, dense, 11, 20)
    _ = kv.kv_lens_dev
    kv.commit_token([0])
    np.testing.assert_array_equal(np.asarray(kv.kv_lens_dev), kv.kv_lens)
