"""The open-loop schedule and the seeded inputs: determinism, clipping,
and the same set of sizes for every seed."""
import json
import os

import numpy as np
import pytest

from bench import data

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


SERVE_MIXES = ["chat-poisson"]


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_schedule_is_deterministic(name):
    a = data.schedule(mix(name), 30.0, 2**31 + 77)
    b = data.schedule(mix(name), 30.0, 2**31 + 77)
    assert [(x.due, x.prompt_len, x.out_len) for x in a] == \
        [(x.due, x.prompt_len, x.out_len) for x in b]


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_lengths_are_clipped_and_arrivals_in_window(name):
    m = mix(name)
    s = data.schedule(m, 30.0, 5)
    p = np.array([x.prompt_len for x in s])
    o = np.array([x.out_len for x in s])
    assert p.min() >= m["prompt"]["min"] and p.max() <= m["prompt"]["max"]
    assert o.min() >= m["output"]["min"] and o.max() <= m["output"]["max"]
    due = np.array([x.due for x in s])
    assert np.all(np.diff(due) > 0) and 0 < due[0] and due[-1] < 30.0
    assert len(s) == round(m["rate_per_s"] * 30.0)
    # the median lands near the mix's, the clip aside
    assert abs(np.median(p) / m["prompt"]["median"] - 1) < 0.2


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_every_seed_gets_the_same_sizes_in_another_order(name):
    a = data.schedule(mix(name), 30.0, 1)
    b = data.schedule(mix(name), 30.0, 2)
    key = lambda s: sorted((x.prompt_len, x.out_len) for x in s)  # noqa
    assert key(a) == key(b)
    assert [x.prompt_len for x in a] != [x.prompt_len for x in b]
    gaps = lambda s: sorted(np.round(np.diff([0] + [x.due for x in s]), 9))  # noqa
    assert gaps(a)[:-1] != [] and len(gaps(a)) == len(gaps(b))


def test_prompts_differ_per_seed_and_share_no_prefix():
    a = data.prompt_tokens(3, [64, 64], 151_936)
    b = data.prompt_tokens(4, [64, 64], 151_936)
    assert not np.array_equal(a[0], b[0])
    assert not np.array_equal(a[0][:8], a[1][:8])
    assert all(x.dtype == np.int32 for x in a)


def test_markov_rows_are_seeded_and_in_vocab():
    a = data.markov_rows(data.rng_for(9, 3), 6, 50, 1000)
    b = data.markov_rows(data.rng_for(9, 3), 6, 50, 1000)
    c = data.markov_rows(data.rng_for(10, 3), 6, 50, 1000)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 1000
    assert len({r.tobytes() for r in a}) == 6          # rows all differ
