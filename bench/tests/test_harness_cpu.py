"""A whole run of each cell's driver on the CPU at a small size, past the
harness's look for a chip: correct when the program is sound, and not
correct when the timed path is broken underneath, once for each fault
the cell can have. Also: without a TPU, and in a checkout that holds
only the benchmark, the command exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2**31 + 12345                      # more than 32 signed bits hold
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
TRAIN = "train-qwen2-0.5b-masked"
CHAT = "serve-qwen2-1.5b-chat"
SMALL_TRAFFIC = {
    TRAIN: {"global_batch": 8, "seq": 32, "pool_batches": 4},
    CHAT: {"rate_per_s": 20.0, "num_slots": 8, "page_size": 16,
           "prompt": {"median": 40, "sigma": 0.5, "min": 8, "max": 96},
           "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 32},
           "trace_seconds": 1, "check": {"requests": 3, "min_tokens": 30}},
}


@pytest.fixture(autouse=True)
def own_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def run_cell(capsys, workload, **hooks):
    hooks = dict(hooks, config=TINY, peaks=PEAKS,
                 traffic=SMALL_TRAFFIC[workload])
    rc = run.main(["--workload", workload, "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0"],
                  require_tpu=False, driver_hooks=hooks)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(lines[-1])
    assert list(line)[-1] == "checks"
    return line


# -- training: the step returns its state unchanged; half the batch left
# out, the mean taken over the rest

def frozen_step(loop):
    real = loop.step_fn

    def step(state, batch):
        _, metrics = real(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    loop.step_fn = step


def half_batch(loop):
    real = loop.step_fn

    def step(state, batch):
        b = batch["weights"].shape[0]
        w = batch["weights"].at[b // 2:].set(0.0)
        return real(state, dict(batch, weights=w))
    loop.step_fn = step


def test_train_run_is_correct(capsys):
    line = run_cell(capsys, TRAIN)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"train_tokens_per_s", "mfu", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("fault", [frozen_step, half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_train_fault_is_caught(capsys, fault):
    line = run_cell(capsys, TRAIN, loop=fault)
    assert not line["correct"], line["checks"]


# -- serving: a token altered where it is produced; the decode superstep
# returning its cache unchanged

def altered_token(eng):
    real = eng._superstep

    def superstep(*args, k):
        toks, cache, lens = real(*args, k=k)
        return toks.at[k // 2].add(1), cache, lens
    eng._superstep = superstep


def cache_unchanged(eng):
    real = eng._superstep

    def superstep(params, pending, cache, *args, k):
        toks, _, lens = real(params, pending,
                             jax.tree.map(jnp.copy, cache), *args, k=k)
        return toks, cache, lens
    eng._superstep = superstep


def test_serve_run_is_correct(capsys):
    line = run_cell(capsys, CHAT)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert line["attempted"] == 20 and line["failed"] == 0


@pytest.mark.parametrize("fault", [altered_token, cache_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_serve_fault_is_caught(capsys, fault):
    line = run_cell(capsys, CHAT, engine=fault)
    assert not line["correct"], line["checks"]


# -- no chip, no program: no result

def test_refuses_without_tpu(capsys):
    rc = run.main(["--workload", TRAIN, "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CHAT,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
