"""Property tests for the phi-accrual failure detector (DESIGN.md §16).

Pins the monotonicity contract the wall-clock monitor leans on:
suspicion only accrues during silence when there is an outstanding
expectation
(``last_sent > last_seen``), it never decreases while the silence
lasts, and a single observation resets it — across the window and
min_samples edges where the gap model switches from the prior to the
fitted normal.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serve.fleet import FleetConfig, FleetController, \
    PhiAccrualDetector

seeds = st.integers(min_value=0, max_value=2**31 - 1)
n_gaps = st.integers(min_value=0, max_value=40)       # spans min_samples
windows = st.integers(min_value=2, max_value=24)      # and window edges
min_samps = st.integers(min_value=1, max_value=8)
periods = st.floats(min_value=0.05, max_value=5.0).filter(lambda p: p > 0)


def _feed(det, rng, count, period):
    """Observe ``count`` arrivals with jittered ``period`` gaps; returns
    the time of the last arrival."""
    t = 0.0
    det.observe(t)
    for _ in range(count):
        t += period * (0.5 + rng.random())
        det.observe(t)
    return t


@settings(max_examples=60)
@given(seeds, n_gaps, windows, min_samps, periods)
def test_phi_non_decreasing_during_silence(seed, count, window,
                                           min_samples, period):
    """After the last arrival, phi(t) is non-negative and non-decreasing
    in t — silence only ever accrues suspicion. Holds on both sides of
    the min_samples edge (prior moments vs fitted moments)."""
    rng = np.random.default_rng(seed)
    det = PhiAccrualDetector(window=window, min_samples=min_samples,
                             init_interval=period)
    t_last = _feed(det, rng, count, period)
    prev = -1.0
    for k in range(30):
        phi = det.phi(t_last + 0.3 * period * k)
        assert phi >= 0.0
        assert phi >= prev - 1e-12, (k, phi, prev)
        prev = phi
    # suspicion eventually accrues for long-enough silence
    assert det.phi(t_last + 50.0 * period) > det.phi(t_last)


@settings(max_examples=60)
@given(seeds, n_gaps, windows, min_samps, periods)
def test_phi_resets_after_observe(seed, count, window, min_samples,
                                  period):
    """One fresh arrival drops phi back to zero at that instant, and the
    gap history window never exceeds its bound."""
    rng = np.random.default_rng(seed)
    det = PhiAccrualDetector(window=window, min_samples=min_samples,
                             init_interval=period)
    t_last = _feed(det, rng, count, period)
    t_quiet = t_last + 10.0 * period
    assert det.phi(t_quiet) > 0.0
    det.observe(t_quiet)
    assert det.phi(t_quiet) == 0.0
    assert len(det.gaps) <= window
    # time running backwards is clamped, not a negative gap
    det.observe(t_quiet - period)
    assert all(g >= 0.0 for g in det.gaps)
    assert det.phi(t_quiet) == 0.0


@settings(max_examples=40)
@given(seeds, n_gaps, periods)
def test_controller_phi_gated_on_outstanding_expectation(seed, count,
                                                         period):
    """FleetController.phi is zero — no matter how long the silence —
    unless something was sent after the replica was last seen. Silence
    you didn't probe is not evidence (DESIGN.md §16)."""
    rng = np.random.default_rng(seed)
    ctrl = FleetController(FleetConfig(n_replicas=2, heartbeat_period=period))
    t = 0.0
    ctrl.note_sent(0, t)
    ctrl.observe(0, t)
    for _ in range(count):
        t += period * (0.5 + rng.random())
        ctrl.note_sent(0, t)
        ctrl.observe(0, t)
    # nothing outstanding: last_sent <= last_seen -> phi stays 0 forever
    assert ctrl.phi(0, t + 100.0 * period) == 0.0
    # an unanswered send re-arms the detector
    ctrl.note_sent(0, t + period)
    assert ctrl.phi(0, t + 30.0 * period) > 0.0
    # replica 1 was never probed at all: no evidence, no suspicion
    assert ctrl.phi(1, t + 100.0 * period) == 0.0
