"""The trace reduction on a small synthetic trace, the peak table, and the
``qwen2`` family's FLOP and byte counts against numbers worked out by
hand for qwen2-0.5b."""
import json
import os

import pytest
from jax.profiler import ProfileData

from bench import flops, run, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Times in the trace: lines start at timestamp_ns, events at offset_ps.
# Host: the window 0-100 us; a step span 0-60 us, a wait span 60-100 us.
# Device 0: program jit_step(7) 10-40 us holding two ops (10-30, 25-40,
# overlapping), one op 70-80 us.
SYNTHETIC = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 60000000 }
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 40000000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.serve.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.serve.wait" } }
  event_metadata { key: 4 value { id: 4 name: "other.span" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 30000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 20000000
             stats { metadata_id: 1 str_value: "paged_flash_decode" } }
    events { metadata_id: 3 offset_ps: 25000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 70000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_step(7)" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.3" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.12" } }
  stat_metadata { key: 1 value { id: 1 name: "long_name" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC),
                                chips=1)


def test_busy_and_idle(reduced):
    assert reduced["window_ns"] == pytest.approx(100_000)
    # union of 10-30, 25-40 and 70-80 us: 30 + 10 = 40 us
    assert reduced["busy_ns"] == pytest.approx(40_000)
    assert trace.idle_pct(reduced) == pytest.approx(60.0)


def test_program_and_kernel_time(reduced):
    assert trace.module_time_ns(reduced, r"^jit_step$") == (1, 30_000)
    # a kernel is found by the text of its stats, not only its name
    assert trace.op_time_ns(reduced, "paged_flash_decode") == (1, 20_000)
    assert trace.op_time_ns(reduced, r"^fusion") == (2, 25_000)
    assert reduced["top_ops"][0] == ["fusion.12", pytest.approx(25e-6)]


def test_idle_gaps_by_span(reduced):
    # gaps 0-10 and 40-70 us (middle 55) go to the step span, 80-100 to
    # the wait span; spans that are not the harness's own are ignored
    by = reduced["idle_by_span"]
    assert by == {"bench.serve.step": pytest.approx(40_000),
                  "bench.serve.wait": pytest.approx(20_000)}
    assert reduced["top_gaps"][0] == ["bench.serve.step",
                                      pytest.approx(40e-6)]


NESTED_LOOP = """
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 40000000 }
  }
  event_metadata { key: 4 value { id: 4 name: "%while.2 = (s32[]) while()" } }
"""


def test_nested_ops_get_their_own_time():
    last = "duration_ps: 10000000 }\n  }\n"
    txt = SYNTHETIC.replace(last, "duration_ps: 10000000 }" + NESTED_LOOP, 1)
    r = trace.reduce_profile(ProfileData.from_text_proto(txt))
    loop = r["ops"]["while.2"]
    # 5-45 us holds 10-30 and 25-40 us: 40 - 20 - 15 = 5 us of its own
    assert loop["ns"] == pytest.approx(40_000)
    assert loop["self_ns"] == pytest.approx(5_000)
    assert r["busy_ns"] == pytest.approx(50_000)        # 5-45 and 70-80


def test_chips_average_over_devices():
    r = trace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC),
                             chips=2)
    assert r["busy_ns"] == pytest.approx((40_000 + 100_000) / 2)


def test_no_window_span_is_an_error():
    txt = SYNTHETIC.replace('name: "bench.window"', 'name: "x.window"')
    with pytest.raises(ValueError):
        trace.reduce_profile(ProfileData.from_text_proto(txt))


def test_peaks_table():
    p = trace.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace.peaks_for("TPU v9 imaginary")


@pytest.fixture(scope="module")
def qwen2():
    return run.load_part(os.path.dirname(BENCH), "families", "qwen2")


@pytest.fixture(scope="module")
def qwen05():
    with open(os.path.join(BENCH, "configs", "qwen2-0.5b.json")) as f:
        return json.load(f)


def test_qwen2_0_5b_counts(qwen2, qwen05):
    # per layer: q 896*896 + k, v 2*896*128 + o 896*896 + 3*896*4864
    assert qwen2.layer_matmul_params(qwen05) == 14_909_440
    # 24 layers + the tied head 151,936 * 896; with the 27,648 bias and
    # 43,904 norm weights this is the model's 494,032,768 parameters
    assert qwen2.matmul_params(qwen05) == 493_961_216
    # 6N + 3 * (4 * 14 * 64 * 24) * (1024 + 1) / 2
    assert qwen2.train_flops_per_token(qwen05, 1024) == 3_096_016_896
    # 2 * 24 layers * s + one head row + pairs s(s+1)/2 * 86,016
    assert qwen2.prefill_flops(qwen05, 100) == (
        2 * 357_826_560 * 100 + 2 * 136_134_656 + 86_016 * 5050)
    assert qwen2.decode_flops(qwen05, 1, 10) == 2 * 493_961_216 + 860_160
    # three tokens that attend over 10, 11 and 12 keys
    assert qwen2.decode_flops(qwen05, 3, 33) == \
        3 * 2 * 493_961_216 + 33 * 86_016


def test_paged_decode_bytes_and_roofline(qwen2, qwen05):
    w = qwen2.paged_decode_attention(qwen05, queries=2, kv_tokens=300)
    # keys and values: 2 * 2 kv heads * 64 * 300 tokens * 2 B * 24 layers
    # query and output: 2 * 14 * 64 * 2 positions * 2 B * 24 layers
    assert w["bytes"] == 2 * 2 * 64 * 300 * 2 * 24 + 2 * 14 * 64 * 2 * 2 * 24
    assert w["flops"] == 4 * 14 * 64 * 300 * 24
    peaks = trace.peaks_for("TPU v5 lite")
    r = flops.roofline_share(w["flops"], w["bytes"], 1e-6, peaks)
    assert r["bound"] == "bandwidth"
    assert r["share_pct"] == pytest.approx(100 * w["bytes"] / 819e9 / 1e-6)
