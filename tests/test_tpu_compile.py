"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e
chip, at real widths. Nothing runs: the TPU compiler, which is installed
without a chip, is handed a described v5e:2x2 topology and must accept
each kernel (``tpu_custom_call`` in the compiled program) — it refuses
block shapes the tiling cannot take, VMEM overuse and programs that do
not fit HBM, none of which interpret mode can see.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.ledger import ledger_width
from repro.kernels import agg, causal_flash, decode_attention

QWEN_P = 494_032_768          # qwen2-0.5b's parameter count
LENET_P = 431_080             # the paper's LeNet


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("h,hkv,d", [(14, 2, 64),      # qwen2-0.5b
                                     (20, 20, 128)])   # qwen1.5-4b
def test_paged_decode_compiles(one_chip, h, hkv, d):
    b, ps, pmax, n = 8, 16, 34, 8 * 34 + 1
    bf = jnp.bfloat16
    args = (_sds((b, h, d), bf, one_chip),
            _sds((n, hkv, ps, d), bf, one_chip),
            _sds((n, hkv, ps, d), bf, one_chip),
            _sds((b, pmax), jnp.int32, one_chip),
            _sds((b,), jnp.int32, one_chip))
    compiled = _compile(decode_attention.paged_flash_decode, *args)
    assert "tpu_custom_call" in compiled.as_text()


AGG_KERNELS = {
    "masked_cge_reduce": (lambda g, rx, s: agg.masked_cge_reduce(g, rx, 1),
                          jnp.float32),
    "trimmed_mean_tiled": (lambda g, rx, s: agg.trimmed_mean_tiled(g, rx, 1),
                           jnp.float32),
    "dequant_accum": (lambda q, rx, s: agg.dequant_accum(q, s, rx),
                      jnp.int8),
}


@pytest.mark.parametrize("n,p", [(20, LENET_P), (4, QWEN_P)],
                         ids=["lenet_n20", "qwen2-0.5b_n4"])
@pytest.mark.parametrize("kernel", sorted(AGG_KERNELS))
def test_agg_kernel_compiles_at_ledger_width(one_chip, kernel, n, p):
    """At a ledger's allocated width the kernel takes the stack as is:
    no per-call pad copy of the (n, P) ledger."""
    fn, dtype = AGG_KERNELS[kernel]
    w = ledger_width(p)
    compiled = _compile(fn, _sds((n, w), dtype, one_chip),
                        _sds((n,), jnp.bool_, one_chip),
                        _sds((n,), jnp.float32, one_chip))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"\bpad\(", text)


@pytest.mark.parametrize("b,s,h,hkv,d,grad", [
    (24, 1024, 14, 2, 64, True),       # qwen2-0.5b training step
    (1, 1024, 12, 2, 128, False),      # qwen2-1.5b prefill bucket
], ids=["qwen2-0.5b_train", "qwen2-1.5b_prefill"])
def test_causal_gqa_flash_compiles(one_chip, b, s, h, hkv, d, grad):
    """The splash forward, and for training its fused backward kernel,
    at the model's GQA geometry: two Mosaic calls with grad."""
    bf = jnp.bfloat16
    fwd = causal_flash.causal_gqa_flash
    fn = (jax.grad(lambda q, k, v: jnp.sum(fwd(q, k, v).astype(jnp.float32)),
                   argnums=(0, 1, 2)) if grad else fwd)
    compiled = _compile(fn, _sds((b, s, h, d), bf, one_chip),
                        _sds((b, s, hkv, d), bf, one_chip),
                        _sds((b, s, hkv, d), bf, one_chip))
    assert compiled.as_text().count("tpu_custom_call") >= (2 if grad else 1)


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)],
                         ids=["gate_up", "down"])
def test_grouped_matmul_compiles(one_chip, monkeypatch, k, n):
    """The held experts' grouped matmul (megablox ``gmm``), forward and
    backward (``gmm`` and ``tgmm``), at DeepSeek-V2-Lite's expert widths
    and one chunk's pair buffer (4,096 tokens, top-6) over 8 experts."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # the chip's path
    bf = jnp.bfloat16

    def fn(x, w, sizes):
        return jax.grad(lambda x, w: jnp.sum(jnp.square(ops.grouped_matmul(
            x, w, sizes).astype(jnp.float32))), argnums=(0, 1))(x, w)

    compiled = _compile(fn, _sds((4096 * 6, k), bf, one_chip),
                        _sds((8, k, n), bf, one_chip),
                        _sds((8,), jnp.int32, one_chip))
    assert compiled.as_text().count("tpu_custom_call") >= 3
