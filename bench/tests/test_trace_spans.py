"""The program's own spans (``repro.*``) reduced from a trace
(``bench/spans.py``): kept with their stats, given the idle gaps they
hold, read into per-layer numbers worked out by hand, and leaving every
key of ``bench/trace.py``'s reduction as it was."""
import os

import pytest
from jax.profiler import ProfileData

from bench import run, spans, trace

TESTS = os.path.dirname(os.path.abspath(__file__))
SYNTHETIC = run.load_module(os.path.join(TESTS, "test_trace_flops.py"),
                            "bench_test_trace_flops").SYNTHETIC


def ev(meta: int, start_us: float, dur_us: float, stats: str = "") -> str:
    return (f"    events {{ metadata_id: {meta} offset_ps: "
            f"{int(start_us * 1e6)} duration_ps: {int(dur_us * 1e6)} "
            f"{stats}}}\n")


def st(key: int, value) -> str:
    kind = "str_value" if isinstance(value, str) else "int64_value"
    v = f'"{value}"' if isinstance(value, str) else value
    return f"stats {{ metadata_id: {key} {kind}: {v} }} "


# The synthetic trace's host plane (window 0-100 us, bench.serve.step
# 0-60, bench.serve.wait 60-100; device idle 0-10, 40-70 and 80-100 us)
# gains one step of the serving engine on a line of its own:
# submit of rid 7 at 1 us and of rid 8 at 1.5 us; a step 2-58 holding
# admit 3-45 (n 2: schedule 3-4, one prefill of both 4-20, a page write
# for each, 20-30 and 30-44), decode 45-57 (k 8) and retire 57-58 (n 1);
# a submit of rid 9 at 62 that nothing serves in the window, and one of
# rid 10 at 99.5-100.5 that ends after it.
PROGRAM_LINE = (
    '  lines { id: 2 name: "engine" timestamp_ns: 0\n'
    + ev(11, 1, 0.2, st(1, 7)) + ev(11, 1.5, 0.3, st(1, 8))
    + ev(12, 2, 56)
    + ev(13, 3, 42, st(2, 2)) + ev(14, 3, 1)
    + ev(15, 4, 16, st(3, "7;8") + st(4, 128))
    + ev(16, 20, 10, st(1, 7)) + ev(16, 30, 14, st(1, 8))
    + ev(17, 45, 12, st(5, 8) + st(6, 2)) + ev(18, 57, 1, st(2, 1))
    + ev(11, 62, 1, st(1, 9)) + ev(11, 99.5, 1, st(1, 10))
    + "  }\n")
PROGRAM_META = "".join(
    f'  event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}\n'
    for k, n in [(11, "repro.serve.submit"), (12, "repro.serve.step"),
                 (13, "repro.serve.admit"), (14, "repro.serve.schedule"),
                 (15, "repro.serve.prefill"), (16, "repro.serve.page_write"),
                 (17, "repro.serve.decode"), (18, "repro.serve.retire")]
) + "".join(
    f'  stat_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}\n'
    for k, n in [(1, "rid"), (2, "n"), (3, "rids"), (4, "tokens"),
                 (5, "k"), (6, "active")])
HOST_END = '  event_metadata { key: 4 value { id: 4 name: "other.span" } }\n'
WITH_PROGRAM = SYNTHETIC.replace(HOST_END, PROGRAM_LINE + HOST_END
                                 + PROGRAM_META, 1)


def reduce(text: str) -> dict:
    return spans.reduce_profile(ProfileData.from_text_proto(text), chips=1)


@pytest.fixture(scope="module")
def reduced():
    assert WITH_PROGRAM != SYNTHETIC
    return reduce(WITH_PROGRAM)


def test_existing_keys_unchanged_by_program_spans(reduced):
    before = trace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    assert set(reduced) == set(before) | {"program_spans", "idle_by_phase",
                                          "wall_by_span"}
    for key in before:
        assert reduced[key] == before[key], key
    assert reduce(SYNTHETIC)["program_spans"] == []


def test_program_spans_inside_the_window_with_stats(reduced):
    evs = reduced["program_spans"]
    assert [s[2] for s in evs] == [
        "repro.serve.submit", "repro.serve.submit", "repro.serve.step",
        "repro.serve.admit", "repro.serve.schedule", "repro.serve.prefill",
        "repro.serve.page_write", "repro.serve.page_write",
        "repro.serve.decode", "repro.serve.retire", "repro.serve.submit"]
    assert evs[0][:2] == [pytest.approx(1_000), pytest.approx(1_200)]
    assert evs[5][3] == {"rids": "7;8", "tokens": 128}
    assert evs[8][3] == {"k": 8, "active": 2}
    assert [s[3]["rid"] for s in spans.span_events(
        reduced, "repro.serve.submit")] == [7, 8, 9]   # 10 ends outside
    assert spans.span_wall_ns(reduced, "repro.serve.page_write") == (
        2, pytest.approx(24_000))
    assert reduced["wall_by_span"]["bench.serve.step"] == [
        1, pytest.approx(60_000)]
    assert reduced["wall_by_span"]["repro.serve.step"] == [
        1, pytest.approx(56_000)]


def test_idle_by_phase(reduced):
    # the gap 0-10 us (middle 5) lies in the prefill 4-20, 40-70 (55) in
    # the decode 45-57, 80-100 (90) in the harness's wait only
    assert reduced["idle_by_phase"] == {
        "repro.serve.prefill": pytest.approx(10_000),
        "repro.serve.decode": pytest.approx(30_000),
        "bench.serve.wait": pytest.approx(20_000)}
    assert sum(reduced["idle_by_phase"].values()) == \
        pytest.approx(sum(reduced["idle_by_span"].values()))


def test_serving_readings(reduced):
    # rid 7 waits 1 -> 4 us, rid 8 1.5 -> 4 us; rid 9 is never served
    assert spans.queue_waits_ms(reduced) == pytest.approx([3e-3, 2.5e-3])
    assert spans.queue_wait_ms(reduced) == pytest.approx(2.75e-3)
    # 42 us of admission for 2 requests
    assert spans.admit_ms_per_req(reduced) == pytest.approx(21e-3)
    # 12 us for 8 iterations
    assert spans.decode_iter_ms(reduced) == pytest.approx(1.5e-3)
    assert spans.host_ms_per_step(reduced) is None


def test_train_reading():
    evs = []
    for i in range(3):
        t = i * 1e6
        evs += [[t, t + 9e5, "repro.train.step", {"step_num": i}],
                  [t, t + 1e4, "repro.train.select", {}],
                  [t + 1e4, t + 4e4, "repro.train.feed", {}],
                  [t + 4e4, t + 5e4, "repro.train.dispatch", {}],
                  [t + 5e4, t + 9e5, "repro.train.sync", {}]]
    r = {"program_spans": evs}
    # (10 + 30) us of select and feed a step
    assert spans.host_ms_per_step(r) == pytest.approx(0.04)
    assert spans.decode_iter_ms(r) is None


@pytest.mark.parametrize("name", sorted(spans.READINGS))
def test_no_program_spans_gives_nothing(name):
    for r in ({}, {"program_spans": []}, reduce(SYNTHETIC)):
        assert spans.READINGS[name](r) is None


def test_request_ids():
    assert spans.request_ids({"rid": 4}) == [4]
    assert spans.request_ids({"rids": 12}) == [12]
    assert spans.request_ids({"rids": "3;5;9", "tokens": 64}) == [3, 5, 9]
    assert spans.request_ids({"k": 8}) == []
