#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU, in one process.

    python chip_smoke.py              # one chip: serve, train, paper server
    python chip_smoke.py --chips 4    # four chips: sharded ledger, serve TP

One chip, three phases, each through the entry point a user calls:

1. serve  — ``ServeEngine`` on qwen2-0.5b at its published widths (bf16,
   24 layers, vocab 151,936), paged cache, default decode superstep: 8
   requests with prompts of 64-512 tokens, 32 new tokens each. Checks:
   every request retires with its full budget; each first token is the
   argmax of a plain forward of its prompt; the Pallas paged-decode
   kernel matches the jnp oracle at the served shapes; the lowered
   superstep holds the kernel (``tpu_custom_call``).
2. train  — ``TrainLoop`` on qwen2-0.5b at full width, masked mode
   (Algorithm 1), n = 8 agents, r = 2, 5 steps. Checks: finite loss and
   grad norm; the loss fell; the parameters changed.
3. paper  — ``AsyncEngine`` in the paper's section-5 deployment (LeNet,
   n = 20, r = 3; ``benchmarks/comm_time.py``) with
   ``agg_backend="device"`` for each of the five rules, 20 iterations.
   Check: every iteration's step agrees with the f64
   ``agg_backend="host"`` reference taken from the same iterate.

``--chips 4`` runs only the multi-chip paths and what they compare with:
the paper's server with its ledger sharded over a 4-chip ``data`` mesh
(the run bit-identical to the one-chip device path with
``ledger_combine="gather"``; one step within 1e-5 of it with
``"partial"``), the same ledger at
qwen2-0.5b's width with n = 8 (15.8 GB, more than one chip holds), and a
tp = 4 ``ServeEngine`` on qwen1.5-4b (20 KV heads; depth cut to 8
layers) against the replicated engine, token for token.

Weights come from ``init_model`` and data from ``--seed``; nothing is
downloaded. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed
on a TPU. Without a TPU, or after any failed check, the script exits
non-zero and prints no such line. Times printed are host wall-clock
seconds and include compilation unless stated otherwise.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402

QWEN_P = 494_032_768           # qwen2-0.5b parameter count (flat ledger P)
PAPER_RULES = (("sum", 0), ("mean", 0), ("cge", 1), ("trimmed_mean", 1),
               ("quantized", 0))


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Named boolean checks of one phase; a failed check never raises,
    so the remaining checks of the phase still run and print."""

    def __init__(self, phase: str):
        self.phase = phase
        self.results: dict = {}
        self.info: dict = {}      # facts the caller may check further

    def __call__(self, name: str, ok, detail: str = "") -> bool:
        ok = bool(ok)
        self.results[name] = ok
        log(f"[{'PASS' if ok else 'FAIL'}] {self.phase}/{name}"
            + (f"  {detail}" if detail else ""))
        return ok

    @property
    def ok(self) -> bool:
        return bool(self.results) and all(self.results.values())


# ---------------------------------------------------------------------------
# compile-time accounting (jax monitoring events)

_COMPILE = {"secs": 0.0, "count": 0, "cache_hits": 0}


def _install_compile_listeners() -> None:
    from jax import monitoring

    def on_duration(event, secs, **_):
        # wraps compile-or-fetch: a persistent-cache hit adds its
        # retrieval time only
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE["secs"] += secs
            _COMPILE["count"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILE["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def _peak_bytes(device=None) -> int:
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# phase 1: serving


def serve_phase(cfg, *, prompt_lens=(64, 128, 176, 240, 304, 368, 448, 512),
                new_tokens: int = 32, page_size: int = 16,
                seed: int = 0) -> Checks:
    """``ServeEngine`` on ``cfg`` with one slot per request. Prompt
    lengths are distinct page multiples, so each prefill group holds one
    request at exactly its prompt's shape, the shape of the plain forward
    the first token is checked against."""
    from repro.kernels import ops
    from repro.models.model import apply_model, init_model
    from repro.serve import PagedCacheConfig, ServeEngine
    from repro.serve.kv_cache import pages_needed

    chk = Checks("serve")
    params = init_model(jax.random.PRNGKey(seed), cfg, max_pos=1024)
    slots = len(prompt_lens)
    per_seq = pages_needed(max(prompt_lens) + new_tokens, page_size)
    ccfg = PagedCacheConfig(num_slots=slots, page_size=page_size,
                            num_pages=slots * per_seq + 1,
                            max_pages_per_seq=per_seq)
    eng = ServeEngine(params, cfg, ccfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in prompt_lens]

    # the decode program the engine runs, lowered at its real shapes
    zeros = jnp.zeros((slots,), jnp.int32)
    hlo = eng._superstep.lower(eng.params, zeros, eng.kv.cache,
                               eng.kv.kv_lens_dev, eng.kv.page_table_dev,
                               zeros, k=eng.superstep_k).as_text()
    chk.info["superstep_tpu_custom_call"] = "tpu_custom_call" in hlo

    t0 = time.perf_counter()
    rids = [eng.submit(p, new_tokens) for p in prompts]
    out = eng.run()
    wall = time.perf_counter() - t0
    n_tok = sum(len(out[r]) for r in rids)
    log(f"serve: {len(rids)} requests, {n_tok} tokens in {wall:.3f} s "
        f"wall time (incl. compile); stats={eng.stats}")
    chk("budget", all(len(out[r]) == new_tokens for r in rids),
        f"lengths={[len(out[r]) for r in rids]}")

    # first token vs the argmax of a plain forward of the prompt
    fwd = jax.jit(lambda p, t: apply_model(p, t, cfg, mode="train",
                                           remat_policy="none")[0][0, -1])
    exact, tied = 0, 0
    for r, p in zip(rids, prompts):
        ref = np.asarray(fwd(eng.params, jnp.asarray(p)[None]), np.float32)
        tok = int(out[r][0])
        if tok == int(np.argmax(ref)):
            exact += 1
        elif ref[tok] >= ref.max() - 2e-2 * max(1.0, abs(ref.max())):
            tied += 1          # a tie at bf16 resolution, not a wrong token
    chk("first_token", exact + tied == len(rids),
        f"exact={exact} bf16-ties={tied} of {len(rids)}")

    # the decode kernel against the oracle at the served shapes
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.compute_dtype)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    q = jax.random.normal(keys[0], (slots, cfg.n_heads, hd), dt)
    pool = (ccfg.num_pages, hkv, page_size, hd)
    kp = jax.random.normal(keys[1], pool, dt)
    vp = jax.random.normal(keys[2], pool, dt)
    tbl = jnp.asarray(1 + rng.permutation(ccfg.num_pages - 1)
                      [: slots * per_seq].reshape(slots, per_seq), jnp.int32)
    lens = jnp.asarray([n + new_tokens for n in prompt_lens], jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, tbl, lens)
    want = ops.paged_decode_attention(q, kp, vp, tbl, lens, impl="ref")
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    chk("decode_kernel_vs_ref", err <= 2e-2, f"max_abs_err={err:.3e}")
    return chk


# ---------------------------------------------------------------------------
# phase 2: masked-DGD training


def train_phase(cfg, *, n_agents: int = 8, r: int = 2, steps: int = 5,
                batch: int = 8, seq: int = 256, seed: int = 0) -> Checks:
    """``TrainLoop`` in masked mode (Algorithm 1: the r slowest agents'
    examples get zero loss weight each step)."""
    from repro.data.synthetic import lm_batches, markov_tokens
    from repro.launch.loop import TrainLoop
    from repro.launch.train import TrainConfig

    chk = Checks("train")
    tokens = markov_tokens(4 * batch * seq * steps, vocab=cfg.vocab_size,
                           seed=seed)
    loop = TrainLoop(cfg, TrainConfig(mode="masked"),
                     lm_batches(tokens, batch, seq, seed=seed),
                     n_agents=n_agents, r=r, max_pos=seq, seed=seed)
    before = jax.device_get(loop.state["params"])
    t0 = time.perf_counter()
    hist = loop.run(steps)
    wall = time.perf_counter() - t0
    log(f"train: {steps} steps of {batch}x{seq} tokens in {wall:.3f} s "
        f"wall time (incl. compile); loss={hist.loss} "
        f"grad_norm={hist.grad_norm}")
    chk("finite", np.all(np.isfinite(hist.loss))
        and np.all(np.isfinite(hist.grad_norm)))
    chk("loss_decreased", hist.loss[-1] < hist.loss[0])
    after = jax.device_get(loop.state["params"])
    same = [jax.tree_util.keystr(k) for (k, a), b in zip(
        jax.tree_util.tree_flatten_with_path(before)[0],
        jax.tree.leaves(after)) if np.array_equal(a, b)]
    n_leaves = len(jax.tree.leaves(after))
    # bf16 parameters keep no f32 master copy: an Adam step below half a
    # bf16 ulp rounds away (RMSNorm scales at 1.0 under lr 1e-3), so at
    # the published dtype not every leaf moves in a few steps
    chk("params_changed", len(same) < n_leaves,
        f"{n_leaves - len(same)} of {n_leaves} leaves changed; "
        f"unchanged: {same}")
    return chk


# ---------------------------------------------------------------------------
# phase 3: the paper's asynchronous server


def _paper_eta(rule, n, r) -> float:
    """The paper's step, 0.05 on the mean gradient; rules that sum the
    received gradients take it divided by the n - r they sum."""
    return 0.05 if rule in ("mean", "trimmed_mean") else 0.05 / (n - r)


def _paper_engine(rule, f, backend, *, n, r, batch, agent_sets, lat, seed,
                  mesh=None):
    from benchmarks.comm_time import make_agent_grad_fn
    from repro.core.async_engine import AsyncEngine, EngineConfig

    # fresh per-agent batch streams for every engine: each backend sees
    # the same gradients
    grad_fn, flat0, _ = make_agent_grad_fn(agent_sets, batch)
    eta = _paper_eta(rule, n, r)
    return AsyncEngine(
        grad_fn, np.asarray(flat0),
        EngineConfig(n_agents=n, r=r, rule=rule, f=f,
                     step_size=lambda t: eta, proj_gamma=1e6, seed=seed,
                     agg_backend=backend),
        latency=lat, mesh=mesh)


def _paper_setup(n, n_train, seed):
    from repro.core.async_engine import default_latency
    from repro.data.partition import partition
    from repro.data.synthetic import mnist_like

    train, _ = mnist_like(n_train=n_train, n_test=16, seed=seed)
    agent_sets = partition(train, n, overlap=2, seed=seed)
    return agent_sets, default_latency(n, n_stragglers=3, factor=10.0,
                                       seed=seed)


def paper_phase(*, n: int = 20, r: int = 3, iters: int = 20,
                batch: int = 32, n_train: int = 4000, seed: int = 0,
                rules=PAPER_RULES) -> Checks:
    """The section-5 deployment: device backend vs the f64 host
    reference, per rule, at the tolerance tests/test_gradledger.py
    holds the device path to.

    The two servers run in lockstep, and after each iteration the host
    server continues from the device server's iterate. So every
    iteration compares one aggregation step from the same point. Run
    apart, the f32 and f64 LeNet trajectories part by more than the
    tolerance within 20 iterations, on the CPU too, with no kernel
    involved (ReLU gradients jump under a one-ulp change of x)."""
    chk = Checks("paper")
    agent_sets, lat = _paper_setup(n, n_train, seed)
    for rule, f in rules:
        t0 = time.perf_counter()
        host, dev = (_paper_engine(rule, f, backend, n=n, r=r, batch=batch,
                                   agent_sets=agent_sets, lat=lat, seed=seed)
                     for backend in ("host", "device"))
        ok, err = True, 0.0
        for _ in range(iters):
            host.run(1)
            dev.run(1)
            ok &= np.allclose(host.x, dev.x, rtol=1e-3, atol=1e-5)
            err = max(err, float(np.max(np.abs(host.x - dev.x))))
            host.x = dev.x.copy()
        chk(f"{rule}_device_vs_host", ok,
            f"max_abs_err={err:.3e} over {iters} steps "
            f"({time.perf_counter() - t0:.3f} s wall time for both)")
    return chk


# ---------------------------------------------------------------------------
# four chips


def sharded_ledger_phase(mesh, *, n: int = 20, r: int = 3, iters: int = 10,
                         batch: int = 32, n_train: int = 4000,
                         seed: int = 0, rules=PAPER_RULES) -> Checks:
    """The paper's server with its ledger sharded over ``mesh``'s data
    axis against the one-chip device path (DESIGN.md section 14). With
    ``ledger_combine="gather"`` the whole run must be bit-identical. The
    ``"partial"`` combine sums in another order, which a LeNet
    trajectory would amplify (see :func:`paper_phase`), so it is held to
    1e-5 on one fused step over the gradients the run left in the
    ledger."""
    from repro.core.ledger import (GradLedger, ShardedGradLedger,
                                   make_aggregate_apply,
                                   make_sharded_aggregate_apply)
    from repro.launch.mesh import dp_axis_names

    chk = Checks("sharded_ledger")
    axes = dp_axis_names(mesh)
    agent_sets, lat = _paper_setup(n, n_train, seed)
    received = jnp.asarray(np.arange(n) < n - r)
    for rule, f in rules:
        xs = {}
        for backend in ("device", "sharded"):
            eng = _paper_engine(rule, f, backend, n=n, r=r, batch=batch,
                                agent_sets=agent_sets, lat=lat, seed=seed,
                                mesh=mesh if backend == "sharded" else None)
            eng.run(iters)
            xs[backend] = eng.x.copy()
            g = eng.ledger_host()
        chk(f"{rule}_gather_bit_identical",
            np.array_equal(xs["sharded"], xs["device"]))

        eta = _paper_eta(rule, n, r)
        x = xs["device"].astype(np.float32)
        one = GradLedger(n, x.size)
        one.load(g)
        want = make_aggregate_apply(rule, f, 1e6)(
            jnp.asarray(x), one.front_for_aggregate(), received, eta)
        led = ShardedGradLedger(n, x.size, mesh=mesh, axes=axes)
        led.load(g)
        got = make_sharded_aggregate_apply(rule, f, 1e6, mesh, axes, n,
                                           "partial")(
            jnp.asarray(x), led.front_for_aggregate(), received, eta)
        want = np.asarray(want)
        err = float(np.max(np.abs(np.asarray(got) - want)))
        chk(f"{rule}_partial",
            err <= 1e-5 * max(float(np.max(np.abs(want))), 1.0),
            f"max_abs_err={err:.3e}")
    return chk


def wide_ledger_phase(mesh, *, p: int = QWEN_P, n: int = 8, f: int = 1,
                      seed: int = 0) -> Checks:
    """The sharded ledger at a model's width: n rows of P f32 (15.8 GB at
    qwen2-0.5b's P, n = 8), one fused CGE step with the partial combine.
    Row j holds (j + 1) * v, so the step's result is known in closed
    form: CGE keeps the m - f smallest-norm received rows, rows
    0 .. m - f - 1, whose coefficients sum to (m - f)(m - f + 1) / 2."""
    from repro.core.ledger import (ShardedGradLedger,
                                   make_sharded_aggregate_apply)
    from repro.launch.mesh import dp_axis_names

    chk = Checks("wide_ledger")
    axes = dp_axis_names(mesh)
    rng = np.random.default_rng(seed)
    v = rng.standard_normal(p, dtype=np.float32)
    led = ShardedGradLedger(n, p, mesh=mesh, axes=axes)
    t0 = time.perf_counter()
    led.load(np.arange(1, n + 1, dtype=np.float32)[:, None] * v[None])
    jax.block_until_ready(led.data)
    log(f"wide_ledger: loaded ({n}, {p}) f32 in "
        f"{time.perf_counter() - t0:.3f} s wall time")
    received = np.ones(n, bool)
    received[-2:] = False                        # r = 2 stragglers
    eta, gamma = 1e-3, 1e12
    step = make_sharded_aggregate_apply("cge", f, gamma, mesh, axes, n,
                                        "partial")
    t0 = time.perf_counter()
    x = step(jnp.zeros((p,), jnp.float32), led.front_for_aggregate(),
             jnp.asarray(received), eta)
    x = np.asarray(x)
    log(f"wide_ledger: fused cge step in {time.perf_counter() - t0:.3f} s "
        f"wall time (incl. compile)")
    shard = led.data.addressable_shards[0].data
    for d in mesh.devices.flat:
        stats = d.memory_stats() or {}
        log(f"wide_ledger: {d} bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    log(f"wide_ledger: ledger {n * p * 4} bytes in all, "
        f"{shard.nbytes} bytes per chip per buffer ({shard.shape}), "
        f"2 buffers")
    keep = int(received.sum()) - f               # smallest-norm rows kept
    want = -eta * (keep * (keep + 1) / 2) * v
    err = float(np.max(np.abs(x - want)))
    chk("cge_step_exact", err <= 1e-5 * float(np.max(np.abs(want))),
        f"max_abs_err={err:.3e}")
    return chk


def serve_tp_phase(cfg, mesh, *, prompt_lens=(64, 128, 192, 256),
                   new_tokens: int = 16, page_size: int = 16,
                   seed: int = 0) -> Checks:
    """tp-sharded ``ServeEngine`` (KV pools split over kv heads, the
    decode kernel per shard) against the replicated engine."""
    from repro.models.model import init_model
    from repro.serve import PagedCacheConfig, ServeEngine
    from repro.serve.kv_cache import pages_needed

    chk = Checks("serve_tp")
    slots = len(prompt_lens)
    per_seq = pages_needed(max(prompt_lens) + new_tokens, page_size)
    ccfg = PagedCacheConfig(num_slots=slots, page_size=page_size,
                            num_pages=slots * per_seq + 1,
                            max_pages_per_seq=per_seq)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in prompt_lens]
    outs = {}
    for tag, m in (("replicated", None), ("tp", mesh)):
        params = init_model(jax.random.PRNGKey(seed), cfg, max_pos=1024)
        eng = ServeEngine(params, cfg, ccfg, mesh=m)
        if m is not None:
            spec = eng.kv.cache[0]["mixer"]["k_pages"].sharding.spec
            chk("pools_sharded", "model" in tuple(spec), f"spec={spec}")
        t0 = time.perf_counter()
        rids = [eng.submit(p, new_tokens) for p in prompts]
        out = eng.run()
        outs[tag] = [np.asarray(out[r]) for r in rids]
        log(f"serve_tp: {tag} engine drained in "
            f"{time.perf_counter() - t0:.3f} s wall time (incl. compile)")
        del eng, params                # one copy of the weights at a time
    same = all(np.array_equal(a, b)
               for a, b in zip(outs["replicated"], outs["tp"]))
    chk("token_identical", same)
    return chk


# ---------------------------------------------------------------------------


def _run_phase(name, fn, results) -> None:
    log(f"== phase {name}")
    c0 = dict(_COMPILE)
    t0 = time.perf_counter()
    try:
        chk = fn()
        results[name] = chk.ok
    except Exception:                  # the phase failed; report, go on
        traceback.print_exc()
        results[name] = False
    log(f"phase {name}: {'ok' if results[name] else 'FAILED'}; "
        f"{time.perf_counter() - t0:.3f} s wall time; compile "
        f"{_COMPILE['secs'] - c0['secs']:.3f} s over "
        f"{_COMPILE['count'] - c0['count']} programs "
        f"({_COMPILE['cache_hits'] - c0['cache_hits']} persistent-cache "
        f"hits); peak_bytes_in_use={_peak_bytes()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: jax found {dev.platform!r} devices", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, jax found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh

    log(f"compile cache: {enable_compile_cache()}")
    _install_compile_listeners()
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    t_all = time.perf_counter()
    results: dict = {}
    if args.chips == 1:
        qwen = get_config("qwen2-0.5b")

        def serve():
            chk = serve_phase(qwen, seed=args.seed)
            chk("superstep_has_kernel",
                chk.info["superstep_tpu_custom_call"])
            return chk

        _run_phase("serve", serve, results)
        _run_phase("train", lambda: train_phase(qwen, seed=args.seed),
                   results)
        _run_phase("paper", lambda: paper_phase(seed=args.seed), results)
    else:
        data = make_mesh((4,), ("data",), devices=devices[:4])
        _run_phase("sharded_ledger",
                   lambda: sharded_ledger_phase(data, seed=args.seed),
                   results)
        _run_phase("wide_ledger",
                   lambda: wide_ledger_phase(data, seed=args.seed), results)
        qwen4b = dataclasses.replace(get_config("qwen1.5-4b"), n_layers=8)
        tp = make_mesh((1, 4), ("data", "model"), devices=devices[:4])
        _run_phase("serve_tp",
                   lambda: serve_tp_phase(qwen4b, tp, seed=args.seed),
                   results)
    log(f"total: {time.perf_counter() - t_all:.3f} s wall time; compile "
        f"{_COMPILE['secs']:.3f} s over {_COMPILE['count']} programs "
        f"({_COMPILE['cache_hits']} persistent-cache hits); phases={results}")
    if not all(results.values()):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
