"""Driver ``serve_open_loop``: open-loop Poisson traffic into ``ServeEngine``.

One process, one thread. The schedule (``bench/data.py``) fixes each
request's due time, prompt length and output length; the run's seed
shuffles the mix's fixed set of sizes and gaps and draws the prompts'
tokens. The loop submits every request that is due, runs one
``ServeEngine.step()`` while there is work, and stamps each request's
tokens when the step that produced them returns: that is when a client
of this engine would see them. When there is no work it sleeps until
the next due time. Arrivals stop at the end of the window; the drain
that follows runs until every request has finished or for at most the
mix's ``drain_seconds``.

End-to-end metrics over every request due in the window:

- ``ttft_p95_ms``: first token's stamp minus the request's due time (not
  its submit time, so a late generator or a stalled loop counts);
- ``tpot_p95_ms``: (last stamp - first stamp) / (tokens - 1).

A request with no first token by the end of the drain is failed, and
counts at the drain's end in the TTFT tail.

Set-up makes the weights, builds the engine, and warms every shape the
mix can produce: a prefill and the cache writes of each prompt length in
the mix (the admission's eager writes take their shapes from the exact
length), and the decode superstep at every K up to ``superstep_k``.

After the window the engine and weights are freed, and the reference
checks a sample of the finished requests, drawn from the seed with the
longest output in it. At each served position the gap is the reference's
best logit less its logit of the served token; ``mean_gap``, the mean
over the sample's served tokens, is compared (the widest gap, logged
beside it, does not separate the int8 control from the program by the
factor a limit needs; ``PERF.md``).
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench import data, weights


class Req:
    __slots__ = ("arr", "prompt", "due", "submit", "state", "first", "last",
                 "n", "tokens")

    def __init__(self, arr, prompt):
        self.arr, self.prompt = arr, prompt
        self.due = arr.due
        self.submit = self.state = self.first = self.last = None
        self.n = 0
        self.tokens = None


def pctl(x, q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def build_engine(arch, tr: dict, params):
    from repro.serve import PagedCacheConfig, ServeEngine
    from repro.serve.kv_cache import pages_needed
    ps = tr["page_size"]
    per_seq = pages_needed(tr["prompt"]["max"] + tr["output"]["max"], ps)
    ccfg = PagedCacheConfig(num_slots=tr["num_slots"], page_size=ps,
                            num_pages=tr["num_slots"] * per_seq + 1,
                            max_pages_per_seq=per_seq)
    return ServeEngine(params, arch, ccfg, superstep_k=tr["superstep_k"],
                       prefix_cache=tr["prefix_cache"])


def drain(eng, max_steps: int = 100_000) -> None:
    for _ in range(max_steps):
        if eng.sched.idle:
            return
        eng.step()
    raise RuntimeError("warm-up did not drain")


def warm(eng, tr: dict, lens, vocab: int, seed: int) -> None:
    """Every prompt length of the mix once (prefill bucket and the exact
    cache writes), then the superstep at each K."""
    rng = data.rng_for(seed, 5)
    for n in sorted(set(int(x) for x in lens)):
        eng.submit(rng.integers(0, vocab, size=n).astype(np.int32), 1)
        drain(eng)
    n0 = int(min(lens))
    for k in range(1, tr["superstep_k"] + 1):
        eng.submit(rng.integers(0, vocab, size=n0).astype(np.int32), k + 1)
        drain(eng)


def run(ctx) -> dict:
    spec, seed = ctx.spec, ctx.seed
    cfg = dict(spec.config, **ctx.hooks.get("config", {}))
    tr = dict(spec.traffic, **ctx.hooks.get("traffic", {}))
    fam, ref = spec.family, spec.reference
    arch = fam.arch_config(cfg, resize="config" in ctx.hooks)
    T = ctx.seconds
    sched = data.schedule(tr, T, seed)
    prompts = data.prompt_tokens(seed, [a.prompt_len for a in sched],
                                 arch.vocab_size)
    reqs = [Req(a, p) for a, p in zip(sched, prompts)]
    control = ctx.check == "control"

    params = weights.make_params(fam, arch, seed)
    eng = build_engine(arch, tr, params)
    ctx.hooks.get("engine", lambda _: None)(eng)
    warm(eng, tr, [a.prompt_len for a in sched], arch.vocab_size, seed)
    jax.block_until_ready(eng.kv.cache)

    setup_s = ctx.setup_done()
    span = ctx.span
    tr_lo = max(0.0, 0.5 * (T - tr["trace_seconds"]))
    tr_hi = min(T, tr_lo + tr["trace_seconds"])
    counters = {"prompt_tokens": 0, "decode_tokens": 0, "decode_flops": 0.0,
                "prefill_flops": 0.0, "attn_queries": 0, "attn_kv": 0,
                "window_s": 0.0}
    pending = list(reqs)
    pending.reverse()                  # pop() takes the earliest due
    live = []
    traced = {"state": "before", "span": None, "t0": 0.0}
    t0 = time.perf_counter()

    def now():
        return time.perf_counter() - t0

    def trace_edge(t: float, window_open: bool) -> None:
        """Start the profiler in the middle of the window, for the mix's
        ``trace_seconds``, and stop it after."""
        if not ctx.trace:
            return
        if traced["state"] == "before" and window_open and t >= tr_lo:
            ctx.start_trace()
            traced.update(state="on", span=span("bench.window"), t0=t)
            traced["span"].__enter__()
        elif traced["state"] == "on" and (t >= tr_hi or not window_open):
            traced["span"].__exit__(None, None, None)
            ctx.stop_trace()
            counters["window_s"] = t - traced["t0"]
            traced["state"] = "done"

    def submit_due(limit: float) -> None:
        while pending and pending[-1].due <= limit:
            q = pending.pop()
            with span("bench.serve.submit"):
                rid = eng.submit(q.prompt, q.arr.out_len)
            q.submit = now()
            if not eng.sched.waiting or eng.sched.waiting[-1].req.rid != rid:
                raise RuntimeError(f"request {rid} refused: {eng.rejected}")
            q.state = eng.sched.waiting[-1]
            live.append(q)

    def account(before) -> None:
        """Counters of the traced window: each request's prefill and its
        decode positions in this step's superstep."""
        for q, g0 in before:
            g1 = len(q.state.generated)
            if g0 == 0 and g1 > 0:
                counters["prompt_tokens"] += q.arr.prompt_len
                counters["prefill_flops"] += fam.prefill_flops(
                    cfg, q.arr.prompt_len)
            dec = g1 - max(g0, 1)
            if dec > 0:
                # the i-th decode position attends over prompt + tokens
                # generated before it + itself
                base = q.arr.prompt_len + g1 - dec
                kv = dec * base + dec * (dec - 1) // 2
                counters["decode_tokens"] += dec
                counters["attn_queries"] += dec
                counters["attn_kv"] += kv
                counters["decode_flops"] += fam.decode_flops(cfg, dec, kv)

    def step() -> None:
        on = traced["state"] == "on"
        snap = [(q, len(q.state.generated)) for q in live] if on else None
        with span("bench.serve.step"):
            eng.step()
        t = now()
        if on:
            account(snap)
        for q in list(live):
            g = len(q.state.generated)
            if g > q.n:
                if q.first is None:
                    q.first = t
                q.n = g
                if g >= q.arr.out_len:
                    q.last = t
                    live.remove(q)

    # -- the window --------------------------------------------------------
    while True:
        t = now()
        trace_edge(t, t < T)
        if t >= T:
            break
        submit_due(t)
        if eng.sched.idle:
            nxt = pending[-1].due if pending else T
            with span("bench.serve.wait"):
                time.sleep(max(0.0, min(nxt, T) - now()))
            continue
        step()
    submit_due(float("inf"))           # due in the window, submitted late
    t_close = now()
    while live and now() - t_close < tr["drain_seconds"]:
        step()
    t_end = now()
    window_compiles = ctx.window_compiles()
    mem = peak_bytes()

    failed = [q for q in reqs if q.first is None or q.last is None]
    ttft = [(q.first if q.first is not None else t_end) - q.due
            for q in reqs]
    tpot = [(q.last - q.first) / (q.n - 1) for q in reqs
            if q.last is not None and q.n > 1]
    late = [q.submit - q.due for q in reqs]
    ctx.log(f"serve: {len(reqs)} requests due in {T} s, {len(failed)} "
            f"failed; drain {t_end - t_close:.3f} s; generator lateness "
            f"p50 {pctl(late, 50) * 1e3:.3f} ms p95 "
            f"{pctl(late, 95) * 1e3:.3f} ms; engine stats {eng.stats}")

    for q in reqs:
        if q.state is not None:
            q.tokens = np.asarray(q.state.generated, np.int32)
    del eng, params
    gc.collect()

    # -- the reference ------------------------------------------------------
    done = [q for q in reqs if q.last is not None]
    checks = {}
    lim = tr["limits"]
    if done:
        rng = data.rng_for(seed, 6)
        longest = max(done, key=lambda q: (q.n, q.arr.prompt_len))
        rest = [done[i] for i in rng.permutation(len(done))
                if done[i] is not longest]
        sample = [longest]
        n_tok = longest.n
        for q in rest:
            if len(sample) >= tr["check"]["requests"] and \
                    n_tok >= tr["check"]["min_tokens"]:
                break
            sample.append(q)
            n_tok += q.n
        c = ref.frozen(ref.consts(cfg))
        L = tr["prompt"]["max"] + tr["output"]["max"]
        n_out = tr["output"]["max"]
        rparams = weights.make_params(fam, arch, seed)
        t_ref = time.perf_counter()
        all_gaps, all_ctrl = [], []
        for q in sample:
            toks = np.zeros((L,), np.int32)
            s0 = q.arr.prompt_len
            seq = np.concatenate([q.prompt, q.tokens[:-1]])
            toks[: seq.size] = seq
            served = np.zeros((n_out,), np.int32)
            served[: q.n] = q.tokens
            gaps, ctrl = ref.served_gaps(rparams, toks, s0 - 1, served,
                                         c=c, n_out=n_out, control=control)
            all_gaps.append(np.asarray(gaps)[: q.n])
            all_ctrl.append(np.asarray(ctrl)[: q.n])
        all_gaps = np.concatenate(all_gaps)
        all_ctrl = np.concatenate(all_ctrl)
        worst, worst_ctrl = float(all_gaps.max()), float(all_ctrl.max())
        ctx.log(f"reference: {len(sample)} requests, {n_tok} served "
                f"tokens, {time.perf_counter() - t_ref:.3f} s; widest gap "
                f"{worst!r}, mean {float(all_gaps.mean())!r}, flips "
                f"{int((all_gaps > 0).sum())}" + (
                    f"; control widest {worst_ctrl!r}, mean "
                    f"{float(all_ctrl.mean())!r}, flips "
                    f"{int((all_ctrl > 0).sum())}" if control else ""))
        gaps_used = all_ctrl if control else all_gaps
        checks["mean_gap"] = (float(gaps_used.mean()), lim["mean_gap"])
        del rparams

    metrics = {"setup_s": setup_s,
               "ttft_p95_ms": 1e3 * pctl(ttft, 95),
               "tpot_p95_ms": 1e3 * pctl(tpot, 95) if tpot else 0.0}
    ctx.log(f"ttft p50 {1e3 * pctl(ttft, 50):.3f} ms, tpot p50 "
            f"{1e3 * pctl(tpot, 50) if tpot else 0.0:.3f} ms")
    return {"metrics": metrics, "counters": counters,
            "attempted": len(reqs), "failed": len(failed),
            "checks": checks, "memory_peak_bytes": mem,
            "window_compiles": window_compiles}
