"""``chip_smoke.py``'s phases at ``reduced()`` sizes on the CPU: the same
entry points and checks the chip run makes, at a size this host runs in
seconds. The four-chip phases run on four virtual CPU devices in a
subprocess (the device count is fixed when jax starts)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs                                    # noqa: E402
from repro.configs.registry import get_config              # noqa: E402


@pytest.fixture(scope="module")
def qwen():
    return get_config("qwen2-0.5b").reduced()


def test_serve_phase(qwen):
    chk = cs.serve_phase(qwen, prompt_lens=(16, 32, 48), new_tokens=6,
                         page_size=8)
    assert chk.ok, chk.results
    assert set(chk.results) == {"budget", "first_token",
                                "decode_kernel_vs_ref"}
    # off the chip the dispatcher takes the jnp path: no Mosaic kernel
    assert chk.info["superstep_tpu_custom_call"] is False


def test_train_phase(qwen):
    chk = cs.train_phase(qwen, n_agents=4, r=1, steps=5, batch=4, seq=16)
    assert chk.ok, chk.results


def test_paper_phase():
    chk = cs.paper_phase(n=8, r=2, iters=4, n_train=400)
    assert chk.ok, chk.results
    assert len(chk.results) == len(cs.PAPER_RULES)


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


_FOUR_CHIP = """
import dataclasses, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
import chip_smoke as cs
from repro.configs.registry import get_config
from repro.launch.mesh import make_mesh
devs = jax.devices()
assert len(devs) == 4, devs
data = make_mesh((4,), ("data",), devices=devs)
ok = cs.sharded_ledger_phase(data, n=8, r=2, iters=3, n_train=400).ok
ok &= cs.wide_ledger_phase(data, p=100_003, n=8).ok
q = dataclasses.replace(get_config("qwen1.5-4b").reduced(),
                        n_heads=4, n_kv_heads=4)
tp = make_mesh((1, 4), ("data", "model"), devices=devs)
ok &= cs.serve_tp_phase(q, tp, prompt_lens=(16, 32), new_tokens=5,
                        page_size=8).ok
print("ALL OK" if ok else "FAILED")
"""


@pytest.mark.multidev
@pytest.mark.timeout(300)
def test_four_chip_phases_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _FOUR_CHIP.format(root=ROOT, src=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=280)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-2000:])
    assert proc.returncode == 0 and "ALL OK" in proc.stdout
