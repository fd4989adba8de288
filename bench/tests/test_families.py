"""A configuration's family is found by name: a new family, reference,
configuration, traffic mix and cell run as new files and new entries
alone, with no file of the harness changed. And what the existing cells
read through the ``qwen2`` family is pinned: the weights made from a
seed, and the FLOP and byte counts."""
import hashlib
import json
import os
import re
import shutil

import jax
import numpy as np
import pytest

from bench import run, weights

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CPU = run.load_module(os.path.join(ROOT, "bench", "tests",
                                   "test_harness_cpu.py"),
                      "bench_test_harness_cpu")
SEED = CPU.SEED


@pytest.fixture(autouse=True)
def own_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def files(root):
    return {os.path.relpath(os.path.join(d, f), root)
            for d, _, fs in os.walk(os.path.join(root, "bench"))
            if "__pycache__" not in d for f in fs}


def read_bytes(path):
    with open(path, "rb") as f:
        return f.read()


# -- a family added by new files alone

@pytest.mark.parametrize("workload", [CPU.TRAIN, CPU.CHAT],
                         ids=["train_job", "serve_open_loop"])
def test_a_family_is_added_by_new_files_alone(tmp_path, capsys, workload):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = files(str(root))
    b = root / "bench"
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)

    # the new family re-exports qwen2's; its reference is qwen2's
    (b / "families" / "newfam.py").write_text(
        "import os\nfrom bench.run import load_part\n"
        "_q = load_part(os.path.dirname(os.path.dirname(os.path.dirname("
        "os.path.abspath(__file__)))), 'families', 'qwen2')\n"
        "globals().update({k: v for k, v in vars(_q).items() "
        "if not k.startswith('__')})\n")
    shutil.copy(b / "references" / "qwen2.py", b / "references" / "newfam.py")
    cfg.update(name="newfam-model", reference="newfam")
    (b / "configs" / "newfam-model.json").write_text(json.dumps(cfg))
    shutil.copy(b / "traffic" / (cell["traffic"] + ".json"),
                b / "traffic" / "newfam-mix.json")
    bench["configs"].append(dict(conf, name="newfam-model",
                                 file="bench/configs/newfam-model.json"))
    bench["workloads"].append(dict(cell, name="newfam-cell",
                                   config="newfam-model",
                                   traffic="newfam-mix"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = run.cell_spec("newfam-cell", str(root))
    assert spec.family.__file__ == str(b / "families" / "newfam.py")
    rc = run.main(["--workload", "newfam-cell", "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0"], require_tpu=False,
                  root=str(root),
                  driver_hooks={"config": CPU.TINY, "peaks": CPU.PEAKS,
                                "traffic": CPU.SMALL_TRAFFIC[workload]})
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert "setup_s" in line["metrics"] and line["attempted"] > 0

    # no file of the harness changed; BENCHMARK.json only gained entries
    for rel in before:
        assert read_bytes(os.path.join(root, rel)) == \
            read_bytes(os.path.join(ROOT, rel)), rel


def test_generic_code_names_no_family():
    generic = ["bench/weights.py", "bench/flops.py", "bench/run.py",
               "bench/trace.py", "bench/data.py"] + [
        os.path.join("bench/drivers", f)
        for f in sorted(os.listdir(os.path.join(ROOT, "bench/drivers")))
        if f.endswith(".py")]
    words = re.compile(r'num_key_value_heads|intermediate_size|qkv_bias|'
                       r'Qwen2|"qwen2"')
    for rel in generic:
        with open(os.path.join(ROOT, rel)) as f:
            assert not words.search(f.read()), rel


# -- what the existing cells read, as recorded before the family moved out
# of the generic code

SMALL = {
    "qwen2-0.5b": {"hidden_size": 64, "intermediate_size": 128,
                   "num_hidden_layers": 2, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "head_dim": 16,
                   "vocab_size": 512},
    "qwen2-1.5b": {"hidden_size": 96, "intermediate_size": 160,
                   "num_hidden_layers": 3, "num_attention_heads": 6,
                   "num_key_value_heads": 2, "head_dim": 32,
                   "vocab_size": 384},
}
DIGESTS = {
    "qwen2-0.5b":
        "e5eef1a965efe13e6e1de24b37ad0c78a3c291d8fed73f795304ab830feefde9",
    "qwen2-1.5b":
        "74fdad0fbac63fb32ed1ccf9367e38bec4f18da216801e15e35cf0f27661969c",
}


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def family_of(cfg):
    return run.load_part(ROOT, "families", cfg["reference"])


def digest(params) -> str:
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_leaves_with_path(params):
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(SMALL))
def test_weights_as_recorded(name):
    cfg = dict(config(name), **SMALL[name])
    fam = family_of(cfg)
    arch = fam.arch_config(cfg, resize=True)
    assert digest(weights.make_params(fam, arch, SEED)) == DIGESTS[name]


COUNTS = [
    ("qwen2-0.5b", "train_flops_per_token", (1024,), 3_096_016_896),
    ("qwen2-1.5b", "train_flops_per_token", (1024,), 9_525_915_648),
    ("qwen2-1.5b", "prefill_flops", (64,), 168_529_625_088),
    ("qwen2-1.5b", "prefill_flops", (384,), 1_019_413_659_648),
    ("qwen2-1.5b", "prefill_flops", (1024,), 2_774_029_959_168),
    ("qwen2-1.5b", "decode_flops", (1, 385), 3_153_371_136),
    ("qwen2-1.5b", "decode_flops", (8, 4028), 25_390_055_424),
    ("qwen2-1.5b", "decode_flops", (64, 44800), 205_283_917_824),
    ("qwen2-1.5b", "paged_decode_attention", (64, 32000),
     {"flops": 5_505_024_000.0, "bytes": 928_514_048.0}),
    ("qwen2-1.5b", "paged_decode_attention", (1, 1537),
     {"flops": 264_413_184.0, "bytes": 44_240_896.0}),
]


@pytest.mark.parametrize("name,count,args,want", COUNTS,
                         ids=[f"{n}-{c}-{'-'.join(map(str, a))}"
                              for n, c, a, _ in COUNTS])
def test_counts_as_recorded(name, count, args, want):
    cfg = config(name)
    assert getattr(family_of(cfg), count)(cfg, *args) == want
