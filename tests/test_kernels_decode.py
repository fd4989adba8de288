"""Paged flash-decode kernel vs the pure-jnp oracle (interpret=True on
CPU): GQA grouping, ragged last page, empty slots, causal self-decode and
cross-attention-length masking. Pools are head-major (N, Hkv, PS, D)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import paged_flash_decode
from repro.kernels.ref import ref_paged_decode_attention

SHAPES = [
    # (B, H, Hkv, D, Dv, page_size, pages_per_seq, num_pages)
    (1, 1, 1, 64, 64, 16, 2, 4),
    (2, 4, 2, 64, 64, 16, 3, 8),      # GQA grouping
    (3, 2, 2, 128, 64, 8, 4, 16),     # Dv != D (MLA-style), H == Hkv
    (2, 2, 1, 32, 32, 128, 2, 8),     # lane-width pages
    (2, 4, 2, 32, 32, 8, 1, 16),      # Pmax == 1 (init+accum+emit fused)
    (2, 8, 2, 32, 32, 8, 3, 8),       # wide group G=4
]


def _pool(rng, num_pages, ps, hkv, d, dv, dtype):
    k = jnp.asarray(rng.normal(size=(num_pages, hkv, ps, d)), dtype)
    v = jnp.asarray(rng.normal(size=(num_pages, hkv, ps, dv)), dtype)
    return k, v


def _table(rng, b, pmax, num_pages):
    # distinct physical pages per (seq, logical page), never page 0
    perm = rng.permutation(num_pages - 1)[: b * pmax] + 1
    return jnp.asarray(perm.reshape(b, pmax), jnp.int32)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ragged", [False, True])
def test_decode_matches_ref(shape, dtype, ragged):
    b, h, hkv, d, dv, ps, pmax, npg = shape
    assert b * pmax <= npg - 1
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    k_pages, v_pages = _pool(rng, npg, ps, hkv, d, dv, dtype)
    tbl = _table(rng, b, pmax, npg)
    if ragged:
        # ragged last page: lengths not multiples of page_size
        lens = jnp.asarray(rng.integers(1, pmax * ps, size=b), jnp.int32)
    else:
        # full pages ("non-causal" memory covering every page exactly)
        lens = jnp.full((b,), pmax * ps, jnp.int32)
    out = paged_flash_decode(q, k_pages, v_pages, tbl, lens, interpret=True)
    ref = ref_paged_decode_attention(q, k_pages, v_pages, tbl, lens)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_decode_matches_contiguous_attention():
    """Paging is layout only: gathering the pages back to a contiguous
    cache and running the model's plain_attention gives the same output
    (the causal self-decode case: query at position kv_len-1)."""
    from repro.models.attention import plain_attention
    rng = np.random.default_rng(1)
    b, h, d, ps, pmax, npg = 2, 2, 64, 8, 3, 8
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k_pages, v_pages = _pool(rng, npg, ps, h, d, d, jnp.float32)
    tbl = _table(rng, b, pmax, npg)
    lens = jnp.asarray([13, 24], jnp.int32)
    out = paged_flash_decode(q, k_pages, v_pages, tbl, lens, interpret=True)

    k = k_pages[tbl].transpose(0, 1, 3, 2, 4).reshape(b, pmax * ps, h, d)
    v = v_pages[tbl].transpose(0, 1, 3, 2, 4).reshape(b, pmax * ps, h, d)
    ref = plain_attention(q[:, None], k, v, causal=False, kv_len=lens)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_decode_empty_and_single_token_slots():
    """kv_len 0 (idle slot) yields zeros; kv_len 1 attends one token."""
    rng = np.random.default_rng(2)
    b, h, d, ps, pmax, npg = 2, 1, 32, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k_pages, v_pages = _pool(rng, npg, ps, h, d, d, jnp.float32)
    tbl = _table(rng, b, pmax, npg)
    lens = jnp.asarray([0, 1], jnp.int32)
    out = paged_flash_decode(q, k_pages, v_pages, tbl, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]), 0.0, atol=1e-7)
    # one valid token -> softmax weight 1 on it
    np.testing.assert_allclose(np.asarray(out[1]),
                               np.asarray(v_pages[tbl[1, 0], :, 0]),
                               atol=1e-6)


def test_decode_mixed_zero_and_ragged_lens():
    """One batch mixing kv_len 0 (idle slot), a mid-page ragged length
    and a full table — the grouped kernel's per-sequence early exit must
    not leak between rows (ISSUE 5)."""
    rng = np.random.default_rng(6)
    b, h, hkv, d, ps, pmax, npg = 3, 4, 2, 32, 4, 4, 16
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k_pages, v_pages = _pool(rng, npg, ps, hkv, d, d, jnp.float32)
    tbl = _table(rng, b, pmax, npg)
    lens = jnp.asarray([0, 6, pmax * ps], jnp.int32)
    out = paged_flash_decode(q, k_pages, v_pages, tbl, lens, interpret=True)
    ref = ref_paged_decode_attention(q, k_pages, v_pages, tbl, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out[0]), 0.0, atol=1e-7)


def test_decode_page_walk_early_exit_is_invisible():
    """Trailing pages past ceil(kv_len/PS) are clamped revisits of the
    last used page: widening the table with arbitrary (valid or -1)
    entries must change nothing — the walk is bounded by the sequence's
    actual used pages, not the static Pmax (ISSUE 5)."""
    rng = np.random.default_rng(7)
    b, h, hkv, d, ps, npg = 2, 4, 2, 32, 4, 32
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k_pages, v_pages = _pool(rng, npg, ps, hkv, d, d, jnp.float32)
    lens = jnp.asarray([5, 8], jnp.int32)         # 2 used pages each
    narrow = _table(rng, b, 2, npg)
    for fill in (-1, 3):               # garbage or live-looking entries
        wide = jnp.concatenate(
            [narrow, jnp.full((b, 6), fill, jnp.int32)], axis=1)
        o_narrow = paged_flash_decode(q, k_pages, v_pages, narrow, lens,
                                      interpret=True)
        o_wide = paged_flash_decode(q, k_pages, v_pages, wide, lens,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(o_wide), np.asarray(o_narrow),
                                   atol=1e-7)
        ref = ref_paged_decode_attention(q, k_pages, v_pages, wide, lens)
        np.testing.assert_allclose(np.asarray(o_wide), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_decode_ignores_stale_table_entries():
    """Entries past kv_len (-1 or garbage) must not affect the output."""
    rng = np.random.default_rng(3)
    b, h, d, ps, pmax, npg = 1, 2, 32, 4, 3, 8
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k_pages, v_pages = _pool(rng, npg, ps, h, d, d, jnp.float32)
    lens = jnp.asarray([6], jnp.int32)           # pages 0,1 used; page 2 not
    t1 = jnp.asarray([[3, 4, -1]], jnp.int32)
    t2 = jnp.asarray([[3, 4, 7]], jnp.int32)
    o1 = paged_flash_decode(q, k_pages, v_pages, t1, lens, interpret=True)
    o2 = paged_flash_decode(q, k_pages, v_pages, t2, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-7)


def test_ops_dispatcher_paged_decode():
    from repro.kernels import ops
    rng = np.random.default_rng(5)
    b, h, d, ps, pmax, npg = 2, 2, 32, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k_pages, v_pages = _pool(rng, npg, ps, h, d, d, jnp.float32)
    tbl = _table(rng, b, pmax, npg)
    lens = jnp.asarray([5, 8], jnp.int32)
    out = ops.paged_decode_attention(q, k_pages, v_pages, tbl, lens)
    ref = ref_paged_decode_attention(q, k_pages, v_pages, tbl, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
