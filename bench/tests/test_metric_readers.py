"""Each per-layer metric's reader: found by its name in ``BENCHMARK.json``,
it reads what it needs from a reduced trace and the driver's counters,
and returns nothing where there is nothing to read."""
import json
import os

import pytest

from bench import run, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PEAKS = trace.peaks_for("TPU v5 lite")


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def reader(name):
    return run.load_module(os.path.join(ROOT, "bench", "metrics",
                                        name + ".py"), "m")


def reduced(ops=None, modules=None, busy=0.9e9, window=1e9):
    return {"window_ns": window, "busy_ns": busy, "ops": ops or {},
            "modules": modules or {}, "peaks": PEAKS}


def test_every_metric_has_a_reader_and_every_cell_one_metric():
    b = bench_json()
    names = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert hasattr(reader(m["name"]), "read"), m["name"]
        assert set(m["workloads"]) <= names
    for w in names:
        spec = run.cell_spec(w)
        assert spec.per_layer and len(spec.end_to_end) >= 2
        assert "setup_s" in {m["name"] for m in spec.end_to_end}


@pytest.mark.parametrize("name", [m["name"] for m in bench_json()
                                  ["per_layer"]])
def test_nothing_to_read_gives_nothing(name):
    spec = run.cell_spec(next(w for w in bench_json()["per_layer"]
                              if w["name"] == name)["workloads"][0])
    value = reader(name).read(reduced(window=0.0), {}, spec)
    assert value is None


def test_idle_and_step_time():
    spec = run.cell_spec("train-qwen2-0.5b-masked")
    r = reduced(modules={"jit_step": {"count": 4, "ns": 4.2e9}})
    assert reader("device_idle.train").read(r, {}, spec) == \
        pytest.approx(10.0)
    assert reader("step_device_ms.train").read(r, {"steps": 4}, spec) == \
        pytest.approx(1050.0)


def test_decode_roofline_and_serve_mfu():
    spec = run.cell_spec("serve-qwen2-1.5b-chat")
    label = ('%paged_decode_attention.10 = bf16[64,2,6,128] custom-call(), '
             'custom_call_target="tpu_custom_call"')
    r = reduced(ops={"paged_decode_attention.10": {
        "count": 28, "ns": 2e6, "self_ns": 2e6, "label": label}})
    counters = {"attn_queries": 64, "attn_kv": 64 * 500,
                "prefill_flops": 1e12, "decode_flops": 1e11}
    work = spec.family.paged_decode_attention(spec.config, 64, 64 * 500)
    want = 100 * work["bytes"] / PEAKS["hbm_bytes_per_s"] / 2e-3
    got = reader("decode_attn_roofline.chat").read(r, counters, spec)
    assert got == pytest.approx(want) and 0 < got <= 100
    assert reader("serve_mfu.chat").read(r, counters, spec) == \
        pytest.approx(100 * 1.1e12 / (1.0 * 197e12))
