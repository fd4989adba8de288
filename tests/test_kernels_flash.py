"""Pallas flash-attention kernel vs pure-jnp oracle (interpret=True on
CPU): shape/dtype sweep per the kernel-validation protocol."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import ref_flash_attention

SHAPES = [
    # (B, H, S, D, Dv, block_q, block_k)
    (1, 1, 128, 64, 64, 128, 128),
    (2, 2, 256, 64, 64, 128, 128),
    (1, 2, 256, 128, 128, 128, 128),
    (2, 1, 512, 64, 64, 128, 256),
    (1, 1, 256, 128, 64, 128, 128),   # Dv != D (MLA-style)
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_ref(shape, dtype, causal):
    b, h, s, d, dv, bq, bk = shape
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, h, s, dv)), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    ref = ref_flash_attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_matches_model_attention_math():
    """The kernel computes the same math as the model's roofline-path
    chunked attention (different layouts: (B,H,S,D) vs (B,S,H,D))."""
    from repro.models.attention import chunked_attention
    rng = np.random.default_rng(1)
    b, h, s, d = 1, 2, 4096, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    t = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    out2 = t(chunked_attention(t(q), t(k), t(v), causal=True, chunk=512))
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# causal GQA flash (splash) with a backward pass: the training/prefill path

GQA_CASES = [
    # (B, S, H, Hkv, D): qwen2-0.5b's heads (14/2, 64), qwen2-1.5b's (12/2, 128)
    (1, 256, 14, 2, 64),
    (2, 512, 14, 2, 64),
    (2, 256, 12, 2, 128),
    (1, 512, 12, 2, 128),
]


def _gqa_inputs(b, s, h, hkv, d, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda hh: jnp.asarray(rng.normal(size=(b, s, hh, d)), jnp.bfloat16)
    return mk(h), mk(hkv), mk(hkv)


def _plain_gqa(q, k, v):
    from repro.models.attention import plain_attention
    g = q.shape[2] // k.shape[2]
    return plain_attention(q, jnp.repeat(k, g, axis=2),
                           jnp.repeat(v, g, axis=2), causal=True)


def _close(a, b, tol=2e-2):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("case", GQA_CASES, ids=lambda c: "-".join(map(str, c)))
def test_causal_gqa_flash_forward_matches_plain(case):
    from repro.kernels.ops import causal_gqa_flash
    q, k, v = _gqa_inputs(*case)
    out = causal_gqa_flash(q, k, v, impl="interpret")
    assert out.shape == q.shape and out.dtype == q.dtype
    _close(out, jax.jit(_plain_gqa)(q, k, v))


@pytest.mark.parametrize("case", GQA_CASES, ids=lambda c: "-".join(map(str, c)))
def test_causal_gqa_flash_grad_matches_plain(case):
    """jax.grad through the splash custom_vjp (the fused backward) against
    the plain form's autodiff over repeated K/V, for q, k and v."""
    from repro.kernels.ops import causal_gqa_flash
    q, k, v = _gqa_inputs(*case, seed=1)
    d = q.shape[-1]
    proj = jnp.asarray(np.random.default_rng(2).normal(size=(d,)) / d,
                       jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * proj)

    flash = lambda q, k, v: causal_gqa_flash(q, k, v, impl="interpret")
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(_plain_gqa), argnums=(0, 1, 2)))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape, name
        scale = float(jnp.max(jnp.abs(w.astype(jnp.float32))))
        _close(g / scale, w / scale)


def test_causal_gqa_flash_ref_is_grouped_oracle():
    """impl="ref" (and "auto" off a TPU) is the un-repeated grouped oracle,
    the same math as the plain form over repeated K/V."""
    from repro.kernels.ops import causal_gqa_flash
    q, k, v = _gqa_inputs(2, 128, 14, 2, 64)
    _close(causal_gqa_flash(q, k, v, impl="ref"), jax.jit(_plain_gqa)(q, k, v))
    jaxpr = str(jax.make_jaxpr(lambda *a: causal_gqa_flash(*a))(q, k, v))
    assert "pallas_call" not in jaxpr


# the dispatch predicate: the kernel at the training shape, plain for each
# exclusion. (name, q shape, kv shape, v head dim, causal, kv_len, ambient
# sharding context, on_tpu) -> takes the kernel
_TRAIN = (24, 1024, 14, 64)
_KV = (24, 1024, 2, 64)
GATE_CASES = [
    ("train_shape", _TRAIN, _KV, 64, True, None, None, True, True),
    ("prefill_bucket_576", (1, 576, 12, 128), (1, 576, 2, 128), 128, True,
     None, None, True, False),
    ("kv_len_given", _TRAIN, _KV, 64, True, "len", None, True, False),
    ("non_causal", _TRAIN, _KV, 64, False, None, None, True, False),
    ("cross_attention", _TRAIN, (24, 512, 2, 64), 64, True, None, None,
     True, False),
    ("dv_ne_d", (2, 256, 16, 24), (2, 256, 16, 24), 16, True, None, None,
     True, False),
    ("ragged_groups", (2, 256, 14, 64), (2, 256, 4, 64), 64, True, None,
     None, True, False),
    ("act_policy", _TRAIN, _KV, 64, True, None, "act_policy", True, False),
    ("serve_tp_mesh", _TRAIN, _KV, 64, True, None, "serve_tp", True, False),
    ("cpu_auto", _TRAIN, _KV, 64, True, None, None, False, False),
]


@pytest.mark.parametrize("case", GATE_CASES, ids=lambda c: c[0])
def test_flash_gate(monkeypatch, case):
    import contextlib
    from repro.dist.act_sharding import act_policy
    from repro.dist.sharding import serve_tp
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.models.attention import flash_gate
    _, qs, ks, dv, causal, kv_len, ctx, on_tpu, want = case
    if on_tpu:
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    else:
        assert jax.default_backend() != "tpu"
    q = jax.ShapeDtypeStruct(qs, jnp.bfloat16)
    k = jax.ShapeDtypeStruct(ks, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(ks[:-1] + (dv,), jnp.bfloat16)
    kl = jnp.full((qs[0],), 7, jnp.int32) if kv_len else None
    amb = {None: contextlib.nullcontext,
           "act_policy": lambda: act_policy("data", "model"),
           "serve_tp": lambda: serve_tp(make_mesh((1,), ("model",)))}
    with amb[ctx]():
        assert flash_gate(q, k, v, causal=causal, kv_len=kl) is want


def _tiny_qwen2_0_5b():
    """Two layers of qwen2-0.5b with its head geometry (14 q / 2 kv heads,
    head_dim 64, bf16); narrower MLP and vocabulary."""
    import dataclasses
    from repro.configs.qwen2_0_5b import CONFIG
    return dataclasses.replace(CONFIG, n_layers=2, d_ff=256, vocab_size=512)


def _model_loss_and_grad(cfg, params, tokens):
    from repro.models.model import apply_model

    def loss(p):
        logits, _, _ = apply_model(p, tokens, cfg, mode="train")
        return jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32), -1)
                        - logits[..., 0].astype(jnp.float32))
    return jax.jit(jax.value_and_grad(loss))(params)


def test_apply_model_flash_matches_plain(monkeypatch):
    """One train-mode forward and gradient of a 2-layer qwen2-0.5b-shaped
    model at S 256: the kernel path (wrapper forced to interpret mode)
    against the plain path, within the bf16 tolerance. On CPU under
    "auto" the model never reaches the kernel."""
    from repro.kernels import ops
    from repro.models.model import init_model
    cfg = _tiny_qwen2_0_5b()
    params = init_model(jax.random.PRNGKey(0), cfg, max_pos=256)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0,
                                cfg.vocab_size)

    calls = []
    orig = ops.causal_gqa_flash
    monkeypatch.setattr(ops, "causal_gqa_flash",
                        lambda *a, **kw: calls.append(1) or orig(*a, **kw))
    want_l, want_g = _model_loss_and_grad(cfg, params, tokens)
    assert not calls                      # CPU, "auto": plain path

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        ops, "causal_gqa_flash",
        lambda q, k, v: calls.append(1) or orig(q, k, v, impl="interpret"))
    got_l, got_g = _model_loss_and_grad(cfg, params, tokens)
    assert calls                          # the kernel path was traced

    _close(got_l, want_l)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        scale = max(float(jnp.max(jnp.abs(w.astype(jnp.float32)))), 1e-6)
        _close(g / scale, w / scale)
