"""DeepSeek-V2-Lite on the training path against the plain reference
(``bench/references/deepseek_v2.py``) at a resize of the benchmark's
configuration, on seeded random weights: logits, loss and gradients;
the held shares of an expert layer adding up to the uncut layer; no pair
dropped under the worst imbalance; YaRN against the published formula;
and the benchmark cell run through the harness to ``correct``."""
import dataclasses
import json
import math
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run, weights  # noqa: E402
from repro.models import attention as A  # noqa: E402
from repro.models import moe as M  # noqa: E402
from repro.models.model import apply_model, lm_loss  # noqa: E402

CELL = "train-deepseek-v2-lite-masked"
SEED = 2**31 + 777
SMALL = {"hidden_size": 64, "intermediate_size": 96,
         "num_hidden_layers": 3, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "moe_intermediate_size": 32, "n_routed_experts": 4,
         "num_experts_per_tok": 3, "vocab_size": 256,
         "deployment": {"chips_sharing_each_layer": 2, "chip": 1,
                        "router_experts": 8}}
SPEC = run.cell_spec(CELL, ROOT)
FAM, REF = SPEC.family, SPEC.reference


def small_cfg(**over):
    return dict(SPEC.config, **dict(SMALL, **over))


def f32_arch(cfg):
    return dataclasses.replace(FAM.arch_config(cfg, resize=True),
                               param_dtype="float32",
                               compute_dtype="float32")


def program_loss(arch):
    def loss(p, tok, tgt, w):
        lg, aux, _ = apply_model(p, tok, arch, mode="train",
                                 loss_weights=w, remat_policy="none")
        return lm_loss(lg, tgt, w, aux["balance"])
    return loss


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-12))


@pytest.fixture(scope="module")
def model():
    cfg = small_cfg()
    arch = f32_arch(cfg)
    params = weights.make_params(FAM, arch, SEED)
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, arch.vocab_size, (3, 64)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, arch.vocab_size, (3, 64)), jnp.int32)
    w = jnp.ones((3, 64), jnp.float32)
    return cfg, arch, params, tok, tgt, w


def test_logits_match_reference(model):
    cfg, arch, params, tok, _, _ = model
    got, _, _ = apply_model(params, tok, arch, mode="train")
    c = REF.consts(cfg)
    with jax.default_matmul_precision("highest"):
        for b in range(tok.shape[0]):
            x, _ = REF.hidden(params, tok[b], c, False)
            want = REF.logits(params, x, False)
            assert rel(got[b], want) < 2e-4, b


def test_loss_and_gradients_match_reference(model):
    cfg, arch, params, tok, tgt, w = model
    w = w.at[0].set(0.0)                 # a masked agent's row
    loss, g = jax.value_and_grad(program_loss(arch))(params, tok, tgt, w)
    keep = np.asarray(w[:, 0] > 0)
    with jax.default_matmul_precision("highest"):
        want_loss, want_g = REF.grads_and_loss(
            params, tok[keep], tgt[keep], w[keep], REF.consts(cfg), False)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    worst = max(jax.tree.leaves(jax.tree.map(rel, g, want_g)))
    assert worst < 2e-3


def test_balance_loss_is_in_the_loss(model):
    """The balance loss counts: with alpha 0 the loss is lower by it."""
    cfg, arch, params, tok, tgt, w = model
    with_aux = program_loss(arch)(params, tok, tgt, w)
    arch0 = dataclasses.replace(arch, moe=dataclasses.replace(
        arch.moe, aux_coef=0.0))
    without = program_loss(arch0)(params, tok, tgt, w)
    assert float(with_aux) - float(without) > 1e-4


def _layer_params(params):
    """The first MoE layer's ffn params."""
    return jax.tree.map(lambda a: a[0], params["blocks"][0]["ffn"])


def test_held_shares_add_up_to_the_uncut_layer(model):
    """Every chip's held part, with the shared experts counted once, adds
    up to the uncut reference layer."""
    cfg, _, _, _, _, _ = model
    full_cfg = small_cfg(n_routed_experts=8,
                         deployment=dict(SMALL["deployment"], chip=0))
    arch_full = f32_arch(full_cfg)
    p = _layer_params(weights.make_params(FAM, arch_full, SEED))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64), jnp.float32)
    shared = M.apply_mlp(p["shared"], x, arch_full)
    total = shared
    for chip in range(4):
        sl = slice(2 * chip, 2 * chip + 2)
        moe = dataclasses.replace(arch_full.moe, held=2, first_held=2 * chip)
        share = dict(p, **{k: p[k][sl] for k in ("w_gate", "w_up",
                                                  "w_down")})
        out, aux = M.apply_moe(share, x, arch_full, moe)
        total = total + out - shared
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([REF.moe(x[b], p, REF.consts(full_cfg), False)[0]
                          for b in range(2)])
    assert rel(total, want) < 1e-5


def test_no_pair_dropped_under_worst_imbalance(model):
    """A router that sends every token to the same held experts: each of
    them takes every token, and the result is the reference's."""
    cfg, arch, params, _, _, _ = model
    p = _layer_params(params)
    first, k = arch.moe.first_held, arch.moe.top_k
    bias = jnp.zeros((arch.moe.num_experts,)).at[first:first + k].set(
        jnp.array([30.0, 20.0, 10.0]))
    p = dict(p, router=jnp.zeros_like(p["router"]).at[0].set(bias))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64), jnp.float32)
    x = x.at[..., 0].set(1.0)              # logits = bias for every token
    out, aux = M.apply_moe(p, x, arch, arch.moe)
    assert float(aux["held_pairs"]) == 2 * 64 * k
    assert float(aux["held_max_load"]) == pytest.approx(
        arch.moe.n_held / k)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([REF.moe(x[b], p, REF.consts(cfg), False)[0]
                          for b in range(2)])
    assert rel(out, want) < 1e-5


def _published_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepSeek-V2's modeling code, transcribed."""
    def find_dim(num_rot):
        return (dim * math.log(orig / (num_rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


@pytest.mark.parametrize("dim,factor,orig", [(64, 40.0, 4096),
                                             (32, 40.0, 4096),
                                             (128, 8.0, 8192),
                                             (16, 4.0, 2048)])
def test_yarn_matches_published_formula(dim, factor, orig):
    from repro.configs.base import YarnConfig
    y = YarnConfig(factor=factor, original_max_position_embeddings=orig)
    got = A.yarn_inv_freq(dim, 10_000.0, y)
    want = _published_yarn(dim, 10_000.0, factor, orig, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_yarn_mscale():
    m = A.yarn_mscale(40.0, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert m * m == pytest.approx(1.5896, abs=1e-4)
    assert A.yarn_mscale(1.0, 0.707) == 1.0
    arch = FAM.arch_config(SPEC.config)
    _, mul, scale = A._mla_rope(arch)
    assert mul == 1.0 and scale == pytest.approx(192 ** -0.5 * m * m)


# -- the cell through the harness, at a resize


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(root)


# The cell's limits are set from full-width chip runs; at this resize
# bf16 rounding moves the first gradient and the parameters' change
# further (grad_gap ~4e-3, change_gap ~3e-3 on the CPU), so the resize
# is held to the Qwen2 train cell's limits.
RESIZE_LIMITS = {"loss_gap": 0.01, "grad_gap": 0.012, "grad_diff": 0.06,
                 "change_gap": 0.1}


def run_cell(checkout, capsys, **hooks):
    hooks = dict(hooks, config=small_cfg(), peaks={
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        traffic={"global_batch": 8, "seq": 64, "pool_batches": 2,
                 "limits": RESIZE_LIMITS})
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                   "1", "--trace", "0"], require_tpu=False, root=checkout,
                  driver_hooks=hooks)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_cell_runs_correct(checkout, capsys):
    line = run_cell(checkout, capsys)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"train_tokens_per_s", "mfu", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


# -- the cell's per-layer readers, on a reduced trace


def _reduced(peaks=True):
    def op(name, ns, kernel=True):
        text = (f"%{name} = bf16[24576,2048] custom-call(), "
                f'custom_call_target="tpu_custom_call"' if kernel else
                f"%{name} = bf16[24576,2048] fusion(), kind=kLoop")
        return {"count": 1, "ns": ns, "self_ns": ns, "label": text}
    return {"window_ns": 4e9, "busy_ns": 4e9, "modules": {},
            "ops": {"gmm.63": op("gmm.63", 3e8),
                    "tgmm.22": op("tgmm.22", 5e8),
                    "fusion.1721": op("fusion.1721", 7e8, kernel=False),
                    "splash": op("splash_mha_fwd.1", 9e8)},
            "peaks": {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9} if peaks else None}


def test_expert_gmm_roofline_reader():
    reader = run.load_part(ROOT, "metrics", "expert_gmm_roofline.moe")
    counters = {"steps": 2, "tokens": 2 * 8 * 4096}
    work = FAM.expert_gmm(SPEC.config, counters["tokens"], 2)
    got = reader.read(_reduced(), counters, SPEC)
    assert got == pytest.approx(100 * work["flops"] / 197e12 / 0.8)
    assert 0 < got <= 100
    # a program without the kernels: nothing to read, no metric
    r = _reduced()
    r["ops"] = {"splash": r["ops"]["splash"]}
    assert reader.read(r, counters, SPEC) is None
    assert reader.read(_reduced(False), counters, SPEC) is None


def test_counts():
    """The cell's counts, by bench/flops.py's rules, at the published
    widths: N = 295.6 M active matmul weights, 2.151 GFLOP a token."""
    cfg = SPEC.config
    assert FAM.matmul_params(cfg) == 295_632_896
    assert FAM.train_flops_per_token(cfg, 4096) == 2_151_376_896
    assert FAM.expected_held_per_token(cfg) == 0.75
    work = FAM.expert_gmm(cfg, 32768)
    assert work["flops"] == 4 * 3 * 2 * 2048 * 1408 * 32768 * 0.75 * 5
    assert work["bytes"] == 4 * 3 * 8 * 2048 * 1408 * 2 * 5
