"""Driver ``train_job``: a training job through ``TrainLoop``.

Set-up builds one ``TrainLoop`` (the compiled step and its state) with
the harness's weights, and drives it from the seed through its first
``check_steps`` steps with ``TrainLoop.run`` on rows that all differ;
the readings of those steps are kept. The same loop then runs, one
``TrainLoop.run(1)`` at a time, until the window's seconds have passed;
the window ends at the end of the step that crosses them.

The straggler mask comes from the program's seeded ``StragglerOracle``,
fed by a latency model of the harness's (per-agent step times drawn from
the seed); the reference recomputes the mask from the same times.

After the window the loop is freed and the plain reference
(``bench/references/<reference>.py``) runs the checked steps from the
same weights, rows and times. Numbers compared, each with its limit from
the traffic file:

- ``loss_gap``: the largest relative gap of a checked step's loss;
- ``grad_gap``: the worst leaf's gap between the norms of the first
  step's gradient as the optimizer gets it (clipped), the program's
  worked out from its first moment after one step, against the larger of
  the reference leaf's norm and the median leaf's;
- ``grad_diff``: the worst leaf's norm of the difference between the two
  first gradients (as the optimizer gets them: the first moments after
  one step) over the same floor: unlike a gap of norms, it sees noise
  that is independent from element to element;
- ``change_gap``: the gap of norms for each leaf's change over the
  checked steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, trace, weights


class HarnessLatency:
    """Per-agent step times: lognormal around 1, with the mix's slow
    agents slower by ``slow_factor``. Every sample is kept, in order, so
    the reference can recompute each step's mask."""

    def __init__(self, n: int, spec: dict, seed: int):
        self.n = n
        self.sigma = spec["sigma"]
        self.rng = data.rng_for(seed, 4)
        self.slow = self.rng.permutation(n)[: spec["slow_agents"]]
        self.factor = spec["slow_factor"]
        self.samples = []

    def sample(self, _rng=None) -> np.ndarray:
        t = self.rng.lognormal(0.0, self.sigma, size=self.n)
        t[self.slow] *= self.factor
        self.samples.append(t)
        return t


def mask_of(times: np.ndarray, r: int) -> np.ndarray:
    """Algorithm 1's selection: the r slowest agents get weight 0."""
    keep = np.zeros(times.shape, np.float32)
    keep[np.argsort(times)[: len(times) - r]] = 1.0
    return keep


def row_weights(mask: np.ndarray, rows: int) -> np.ndarray:
    """Agent a owns the a-th contiguous block of rows."""
    return np.repeat(mask, rows // mask.shape[0])


class Feed:
    """The rows of step i: a pool of distinct Markov rows, used in order
    and then again."""

    def __init__(self, rows: np.ndarray, batch: int):
        self.rows, self.batch = rows, batch
        self.n = rows.shape[0] // batch
        self.i = 0

    def at(self, i: int):
        blk = self.rows[(i % self.n) * self.batch:][: self.batch]
        return blk[:, :-1], blk[:, 1:]

    def __iter__(self):
        return self

    def __next__(self):
        out = self.at(self.i)
        self.i += 1
        return out


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def _change_norms(new, old):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))])


def host_leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b)))


def diff_norms(host, tree) -> np.ndarray:
    """Per leaf, the norm of (host leaf - device leaf), a leaf at a time
    on the device."""
    return np.asarray([float(_diff_norm(jnp.asarray(h), d))
                       for h, d in zip(host, jax.tree.leaves(tree))])


def leaf_gap(got: np.ndarray, want: np.ndarray, keep=None) -> float:
    """Worst leaf's |got - want| over max(want, median of want)."""
    floor = np.median(want)
    gap = np.abs(got - want) / np.maximum(want, floor)
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap))


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run(ctx) -> dict:
    from repro.launch.loop import StragglerOracle, TrainLoop
    from repro.launch.train import TrainConfig, make_optimizer

    spec, seed = ctx.spec, ctx.seed
    cfg = dict(spec.config, **ctx.hooks.get("config", {}))
    tr = dict(spec.traffic, **ctx.hooks.get("traffic", {}))
    fam, ref = spec.family, spec.reference
    arch = fam.arch_config(cfg, resize="config" in ctx.hooks)
    B, S = tr["global_batch"], tr["seq"]
    n, r = tr["n_agents"], tr["r"]
    opt = tr["optimizer"]
    n_check = tr["check_steps"]

    rows = data.markov_rows(data.rng_for(seed, 3), tr["pool_batches"] * B,
                            S + 1, arch.vocab_size, **tr["markov"])
    feed = Feed(rows, B)
    lat = HarnessLatency(n, tr["stragglers"], seed)
    tc = TrainConfig(mode=tr["mode"], optimizer=opt["name"], lr=opt["lr"],
                     lr_kind="constant", clip_norm=opt["clip"],
                     remat_policy=tr["remat_policy"])
    check = ctx.check == "program"
    readings = {}
    if check:
        loop = TrainLoop(arch, tc, feed, n_agents=n, r=r,
                         oracle=StragglerOracle(n, r, latency=lat,
                                                seed=seed),
                         max_pos=S, seed=seed)
        loop.state = None              # the program's own init, freed
        params = weights.make_params(fam, arch, seed)
        loop.state = {"params": params,
                      "opt": make_optimizer(tc).init(params),
                      "step": jnp.zeros((), jnp.int32)}
        del params
        ctx.hooks.get("loop", lambda _: None)(loop)

        hist = loop.run(1)
        readings["grad"] = np.asarray(_leaf_norms(
            loop.state["opt"]["m"]), np.float64) / (1.0 - opt["b1"])
        readings["m1"] = host_leaves(loop.state["opt"]["m"])
        hist = loop.run(n_check - 1)
        readings["loss"] = np.asarray(hist.loss[:n_check], np.float64)
        p0 = weights.make_params(fam, arch, seed)
        readings["change"] = np.asarray(_change_norms(
            loop.state["params"], p0), np.float64)
        del p0

        setup_s = ctx.setup_done()
        if ctx.trace:
            ctx.start_trace()
        steps = 0
        t0 = time.perf_counter()
        with ctx.span("bench.window"):
            while time.perf_counter() - t0 < ctx.seconds:
                with ctx.span("bench.train.run"):
                    loop.run(1)
                steps += 1
        elapsed = time.perf_counter() - t0
        if ctx.trace:
            ctx.stop_trace()
        window_compiles = ctx.window_compiles()
        mem = peak_bytes()
        loss_window = float(hist.loss[-1])
        ctx.log(f"train: {steps} steps of {B}x{S} tokens in "
                f"{elapsed:.6f} s; last loss {loss_window}")
        del loop, hist
        gc.collect()
    else:                              # control: no program, no window
        setup_s, steps, elapsed, window_compiles, mem = 0.0, 0, 1.0, 0, 0

    # the plain reference, then (control) the int8 reference in the
    # program's place
    c = ref.frozen(ref.consts(cfg))
    o = ref.frozen({k: float(opt[k]) for k in ("lr", "b1", "b2", "eps",
                                               "clip")})

    def reference(quant: bool, compare=None) -> dict:
        """The checked steps in float32 (or int8 with ``quant``). With
        ``compare`` (first moments after step 1, on the host), also the
        per-leaf norm of their difference from this run's."""
        p = weights.make_params(fam, arch, seed)
        m, v = ref.zeros_f32(p), ref.zeros_f32(p)
        out = {"loss": []}
        for i in range(n_check):
            toks, tgts = feed.at(i)
            w = row_weights(mask_of(lat.samples[i], r), B)
            keep = w > 0
            p, m, v, loss, gn = ref.train_step(
                p, m, v, jnp.asarray(i, jnp.int32), jnp.asarray(toks[keep]),
                jnp.asarray(tgts[keep]),
                jnp.asarray(np.repeat(w[keep, None], S, 1)),
                c=c, quant=quant, opt=o)
            out["loss"].append(float(loss))
            if i == 0:
                out["grad"] = np.asarray(gn, np.float64)
                out["m1"] = host_leaves(m)
                if compare is not None:     # moments -> gradients
                    out["grad_diff"] = diff_norms(compare, m) / (
                        1.0 - opt["b1"])
        del m, v
        p0 = weights.make_params(fam, arch, seed)
        out["change"] = np.asarray(_change_norms(p, p0), np.float64)
        out["loss"] = np.asarray(out["loss"])
        del p, p0
        gc.collect()
        return out

    if not check:                      # the control's times, as the oracle
        for _ in range(n_check):       # would have drawn them
            lat.sample()
    t_ref = time.perf_counter()
    got = readings if check else reference(True)
    want = reference(False, compare=got["m1"])
    ctx.log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    moved = want["grad"] >= 1e-3 * np.median(want["grad"])
    lim = tr["limits"]
    rel = np.abs(got["loss"] - want["loss"]) / np.abs(want["loss"])
    checks = {
        "loss_gap": (float(np.max(rel)), lim["loss_gap"]),
        "grad_gap": (leaf_gap(got["grad"], want["grad"]), lim["grad_gap"]),
        "grad_diff": (float(np.max(want["grad_diff"] / np.maximum(
            want["grad"], np.median(want["grad"])))), lim["grad_diff"]),
        "change_gap": (leaf_gap(got["change"], want["change"], moved),
                       lim["change_gap"]),
    }
    ctx.log(f"losses: program {got['loss'].tolist()} reference "
            f"{want['loss'].tolist()}")

    tokens = steps * B * S
    tps = tokens / elapsed
    metrics = {"train_tokens_per_s": tps, "setup_s": setup_s}
    if check:
        peaks = ctx.hooks.get("peaks") or trace.peaks_for(
            jax.devices()[0].device_kind)
        metrics["mfu"] = 100.0 * tps * fam.train_flops_per_token(cfg, S) \
            / (spec.chips * peaks["bf16_flops_per_s"])
    return {
        "metrics": metrics,
        "counters": {"steps": steps, "tokens": tokens, "seq": S,
                     "batch": B},
        "attempted": steps, "failed": 0,
        "checks": checks, "memory_peak_bytes": mem,
        "window_compiles": window_compiles,
    }
