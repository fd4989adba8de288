"""Seeded inputs: token streams, request sizes and arrival schedules.

Everything here is a function of a seed and of a traffic file's numbers,
and runs on the host with numpy.

- ``markov_rows``: rows of an order-1 sparse Markov chain over the
  vocabulary, after ``repro.data.synthetic.markov_tokens`` (each state has
  a small set of successors, with some exploration), advanced for many
  rows at once.
- ``size_pool``: a traffic mix's request sizes, drawn from the mix's own
  ``pool_seed`` and clipped to its range, so that every run's seed gets
  the same set of sizes (and so the same shapes to warm, and the same
  work) in another order.
- ``schedule``: open-loop Poisson arrivals at the mix's rate for a
  window: the gaps are a fixed set too, shuffled by the run's seed, and
  scaled so that the window holds exactly ``round(rate * seconds)``
  arrivals.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """One named stream of a run's seed (any whole number)."""
    return np.random.default_rng([int(seed) % 2**63, stream])


# ---------------------------------------------------------------------------
# training tokens


def markov_rows(rng: np.random.Generator, rows: int, length: int,
                vocab: int, order_state: int = 64, fanout: int = 8,
                explore: float = 0.1) -> np.ndarray:
    """(rows, length) int32 tokens; every row its own chain, from a
    random start, over one successor table."""
    nxt = rng.integers(0, vocab, size=(order_state, fanout))
    out = np.empty((rows, length), np.int32)
    state = rng.integers(0, order_state, size=rows)
    for i in range(length):
        pick = nxt[state, rng.integers(0, fanout, size=rows)]
        wild = rng.integers(0, vocab, size=rows)
        tok = np.where(rng.random(rows) < explore, wild, pick)
        out[:, i] = tok
        state = tok % order_state
    return out


# ---------------------------------------------------------------------------
# serving requests


def clipped_lognormal(rng: np.random.Generator, n: int, spec: dict
                      ) -> np.ndarray:
    """``n`` whole numbers, lognormal with the spec's median and sigma,
    clipped to [min, max]."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass
class Arrival:
    due: float              # seconds from the window's start
    prompt_len: int
    out_len: int


def n_requests(traffic: dict, seconds: float) -> int:
    return max(1, int(round(traffic["rate_per_s"] * seconds)))


def size_pool(traffic: dict, n: int):
    """(prompt_lens, out_lens) of ``n`` requests, from the mix's
    ``pool_seed`` only."""
    rng = np.random.default_rng(traffic["pool_seed"])
    return (clipped_lognormal(rng, n, traffic["prompt"]),
            clipped_lognormal(rng, n, traffic["output"]))


def schedule(traffic: dict, seconds: float, seed: int) -> List[Arrival]:
    n = n_requests(traffic, seconds)
    prompts, outs = size_pool(traffic, n)
    gaps = np.random.default_rng(traffic["pool_seed"] + 1).exponential(
        1.0, size=n + 1)
    rng = rng_for(seed, 1)
    order = rng.permutation(n)
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    return [Arrival(float(t), int(prompts[i]), int(outs[i]))
            for t, i in zip(due, order)]


def prompt_tokens(seed: int, lens, vocab: int) -> List[np.ndarray]:
    """Independent uniform token ids for each prompt: no two share a
    prefix."""
    rng = rng_for(seed, 2)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]
