"""The low-precision control (``--check control``: the plain reference
computed in int8, in the program's place) at a size a CPU test holds:
it reads at least three times what the bf16 program reads, on the
number each cell's limit relies on to tell them apart (``grad_diff`` for
training, ``mean_gap`` for serving). On the chip, at the cells' own
sizes, the same readings set the limits (``PERF.md``)."""
import json

import pytest

from bench import run

MID = {"hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
       "vocab_size": 8192}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
CELLS = {
    "train-qwen2-0.5b-masked": (
        "grad_diff", {"global_batch": 8, "seq": 128, "pool_batches": 4}),
    "serve-qwen2-1.5b-chat": (
        "mean_gap", {"rate_per_s": 10.0, "num_slots": 8, "page_size": 16,
                     "prompt": {"median": 40, "sigma": 0.5, "min": 8,
                                "max": 96},
                     "output": {"median": 24, "sigma": 0.5, "min": 8,
                                "max": 48},
                     "check": {"requests": 4, "min_tokens": 80}}),
}


@pytest.fixture(autouse=True)
def own_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def reading(capsys, workload, check, seed):
    number, traffic = CELLS[workload]
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1", "--check", check],
                  require_tpu=False,
                  driver_hooks={"config": MID, "peaks": PEAKS,
                                "traffic": traffic})
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return line["checks"][number]["value"]


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_control_reads_three_times_the_program(capsys, workload):
    seed = 2**31 + 3
    program = reading(capsys, workload, "program", seed)
    control = reading(capsys, workload, "control", seed)
    assert control >= 3 * program, (program, control)
