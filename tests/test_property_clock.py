"""Property tests for the virtual clock + Poisson loadgen (DESIGN.md §10).

Pins the properties the e2e harness leans on: seed-deterministic arrival
gaps, monotone non-decreasing times (including the translated ``start``
segments of requeued bursts), and insertion-order tie-breaks that hold
under arbitrary interleavings of schedule and pop.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.sim.clock import EventHeap, VirtualClock, poisson_arrivals

rates = st.floats(min_value=1e-3, max_value=50.0).filter(lambda r: r > 0)
seeds = st.integers(min_value=0, max_value=2**31 - 1)
counts = st.integers(min_value=0, max_value=40)


@settings(max_examples=60)
@given(rates, seeds, counts)
def test_poisson_seed_determinism(rate, seed, count):
    """Same (rate, seed, count) -> bit-identical times and payloads on a
    fresh clock; the stream is a pure function of its seed."""
    def draw():
        clock = VirtualClock()
        evs = poisson_arrivals(clock, rate, count, seed=seed,
                               make_payload=lambda i, rng:
                               rng.integers(0, 256, 4).tolist())
        return [(e.time, e.payload) for e in evs]
    assert draw() == draw()


@settings(max_examples=60)
@given(rates, seeds, counts)
def test_poisson_monotone_strictly_positive_gaps(rate, seed, count):
    clock = VirtualClock()
    evs = poisson_arrivals(clock, rate, count, seed=seed)
    times = [e.time for e in evs]
    assert len(times) == count
    assert all(t > 0.0 for t in times)
    assert all(a <= b for a, b in zip(times, times[1:]))


@settings(max_examples=60)
@given(rates, seeds, st.integers(min_value=1, max_value=30),
       st.floats(min_value=0.0, max_value=1e4))
def test_poisson_start_translates_without_redrawing(rate, seed, count,
                                                    start):
    """``start`` only translates the stream: the gap sequence is the
    same pure function of (seed, count) — the property that makes a
    requeued burst reproducible regardless of where the previous drain
    left ``clock.now``."""
    base = [e.time for e in
            poisson_arrivals(VirtualClock(), rate, count, seed=seed)]
    clock = VirtualClock()
    clock.now = 777.0                 # must be ignored when start is given
    moved = [e.time for e in
             poisson_arrivals(clock, rate, count, seed=seed, start=start)]
    np.testing.assert_allclose([t + start for t in base], moved,
                               rtol=0, atol=1e-9)


def test_poisson_rejects_degenerate_inputs():
    with pytest.raises(ValueError):
        poisson_arrivals(VirtualClock(), 0.0, 3, seed=0)
    with pytest.raises(ValueError):
        poisson_arrivals(VirtualClock(), -1.0, 3, seed=0)
    with pytest.raises(ValueError):
        poisson_arrivals(VirtualClock(), 1.0, -1, seed=0)
    assert poisson_arrivals(VirtualClock(), 1.0, 0, seed=0) == []


@settings(max_examples=60)
@given(st.lists(st.tuples(st.sampled_from([0.0, 1.0, 1.5, 2.0, 7.25]),
                          st.integers(min_value=0, max_value=99)),
                min_size=0, max_size=25))
def test_heap_ties_break_by_insertion_order(items):
    """Events sharing a time pop in insertion order — the deterministic
    total order the whole simulator's replayability rests on."""
    heap = EventHeap()
    for t, payload in items:
        heap.push(t, "ev", payload)
    popped = []
    while len(heap):
        popped.append(heap.pop())
    assert [(e.time, e.seq) for e in popped] \
        == sorted(((e.time, e.seq) for e in popped))
    # stable w.r.t. the original insertion sequence at equal times
    expected = sorted(range(len(items)), key=lambda i: (items[i][0], i))
    assert [e.payload for e in popped] == [items[i][1] for i in expected]


@settings(max_examples=60)
@given(st.lists(st.tuples(st.floats(min_value=0.0, max_value=10.0),
                          st.booleans()),
                min_size=1, max_size=30),
       seeds)
def test_heap_order_survives_interleaved_schedule_and_pop(ops, seed):
    """Interleaving schedule_at with pop_due never reorders equal-time
    events and never yields a time below a previously popped one once
    scheduling stays in the future (the harness's requeue pattern)."""
    rng = np.random.default_rng(seed)
    clock = VirtualClock()
    popped = []
    for t, do_pop in ops:
        # requeue pattern: new work lands at/after the current frontier
        clock.schedule_at(clock.now + t, "ev")
        if do_pop:
            horizon = clock.now + float(rng.uniform(0.0, 5.0))
            popped.extend(clock.advance_to(horizon))
    popped.extend(clock.advance_to(np.inf))
    keys = [(e.time, e.seq) for e in popped]
    assert keys == sorted(keys)
    assert len(popped) == len(ops)


@settings(max_examples=40)
@given(rates, seeds, st.integers(min_value=1, max_value=20))
def test_poisson_events_drain_in_arrival_order(rate, seed, count):
    """Scheduled arrivals pop from the clock in exactly the order the
    generator emitted them (times are strictly increasing with prob. 1,
    and ties — if any — fall back to insertion order)."""
    clock = VirtualClock()
    evs = poisson_arrivals(clock, rate, count, seed=seed,
                           make_payload=lambda i, rng: i)
    drained = []
    while True:
        ev = clock.next_event()
        if ev is None:
            break
        drained.append(ev.payload)
    assert drained == [e.payload for e in evs] == list(range(count))
