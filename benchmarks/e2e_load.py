"""Trace-driven e2e load benchmark: every named scenario replayed against
real replicated ServeEngines (DESIGN.md §15).

One shared :class:`repro.sim.e2e.EngineFleet` (jit paid once) replays
every registered scenario through ``repro.sim.e2e.run_e2e``: open-loop
Poisson arrivals, per-superstep virtual-time billing through the
scenario's ``SimTransport``, crashes/stragglers/drops/Byzantine replicas
acting on real decode supersteps. Per scenario it reports the native-r
row (churn applied) plus the post-hoc goodput / p99-TTFT curve over
r in {0..3}, with the §10 conformance checks (vote soundness,
replica agreement, request liveness, quorum_honest) run on every
request.

For scale, the stand-in dispatch curve (``serve_latency.run_dispatch``)
is re-run at the same fleet size so BENCH_e2e.json carries both the
simulated-replica and the real-engine r-curves side by side.

    PYTHONPATH=src python benchmarks/e2e_load.py [--smoke] [--record] \
        [--scenario NAME ...] [--fleet]

``--fleet`` additionally replays the fault scenarios through the fleet
controller (``repro.sim.fleet_e2e``: phi-accrual detection, hedged
re-dispatch, checkpoint-based rejoin) and gates crash_cascade /
rolling_restart on post-rejoin recovery and zero permanent loss.

``--wallclock`` runs the realtime chaos presets (``repro.sim.
realtime_chaos``) on REAL threads and timers: RealClock + EngineReplica
wrappers around the shared engines at a compressed timescale. Rows
record recovery time, goodput under churn, and the hedge-fire rate —
the wall-clock counterparts of the ``--fleet`` virtual-time rows.

``--record`` writes BENCH_e2e.json; under ``--smoke`` it writes
BENCH_e2e.smoke.json instead so a reduced sweep never clobbers the
committed full baseline.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

BENCH_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_e2e.json"

# scenarios whose *design* includes losing the honest majority or a
# total outage; native-row violations there are the scenario's point,
# everywhere else they fail the gate
EXPECT_VIOLATIONS: tuple = ()
SMOKE_REQUESTS = 4

# fault scenarios additionally replayed through the fleet controller
# (repro.sim.fleet_e2e): detection + hedged re-dispatch + checkpoint
# rejoin instead of the oracle retry loop
FLEET_SCENARIOS = ("crash_cascade", "rolling_restart", "partition_heal",
                   "churn_elastic")
# scenarios whose post-rejoin window must recover >= this fraction of the
# pre-fault success rate (full runs only: smoke truncation leaves the
# post-rejoin window without arrivals, making the ratio undefined)
FLEET_RECOVERY_GATED = ("crash_cascade", "rolling_restart")
FLEET_RECOVERED_MIN = 0.9
FLEET_SMOKE_REQUESTS = 16

# realtime chaos presets replayed on real threads + timers (RealClock +
# EngineReplica, repro.sim.realtime_chaos); the timescale adapts to one
# measured engine-request latency so plan proportions match the stubs'
WALLCLOCK_PLANS = ("kill_rejoin", "pause_blip", "straggler",
                   "crash_cascade")
WALLCLOCK_N = 4
WALLCLOCK_SMOKE_REQUESTS = 12
WALLCLOCK_STUB_WORK = 0.3     # StubReplica work_time at scale 1.0


def run_scenarios(names=None, n_requests=None, fleet=None, log=print):
    from repro.sim.e2e import EngineFleet, run_e2e
    from repro.sim.scenario import SCENARIOS, get_scenario

    names = list(names) if names else sorted(SCENARIOS)
    scs = [get_scenario(n) for n in names]
    sizes = {sc.n_agents for sc in scs}
    if len(sizes) != 1:
        raise ValueError(f"scenarios disagree on fleet size: {sizes}")
    if fleet is None:
        fleet = EngineFleet(sizes.pop())
    rows = []
    for sc in scs:
        t0 = time.time()
        rep = run_e2e(sc, fleet=fleet, n_requests=n_requests)
        wall = time.time() - t0
        if n_requests is not None and n_requests < sc.n_requests:
            log(f"# e2e/{sc.name}: truncated to {n_requests}/"
                f"{sc.n_requests} requests (smoke)")
        rows.append(dict(
            scenario=sc.name, wall_s=wall,
            n_requests=len(rep.requests), r_native=sc.r,
            retries=sum(q.retries for q in rep.requests),
            copies_lost=sum(1 for q in rep.requests
                            for c in q.copies.values()
                            if c.status == "lost"),
            copies_dropped=sum(1 for q in rep.requests
                               for c in q.copies.values()
                               if c.status == "dropped"),
            native=rep.native.as_dict(),
            sweep={str(r): row.as_dict() for r, row in rep.sweep.items()},
            violations=rep.violations))
    return rows, fleet


def run_fleet_scenarios(names=None, n_requests=None, fleet=None, log=print):
    from repro.sim.e2e import EngineFleet
    from repro.sim.fleet_e2e import run_fleet_e2e
    from repro.sim.scenario import get_scenario

    names = list(names) if names else list(FLEET_SCENARIOS)
    scs = [get_scenario(n) for n in names]
    if fleet is None:
        fleet = EngineFleet(scs[0].n_agents)
    rows = []
    for sc in scs:
        t0 = time.time()
        rep = run_fleet_e2e(sc, fleet=fleet, n_requests=n_requests)
        wall = time.time() - t0
        if n_requests is not None and n_requests < sc.n_requests:
            log(f"# fleet/{sc.name}: truncated to {n_requests}/"
                f"{sc.n_requests} requests (smoke)")
        rows.append(dict(
            scenario=sc.name, wall_s=wall,
            n_requests=len(rep.requests), r_native=sc.r,
            native=rep.native.as_dict(),
            sweep={str(r): row.as_dict() for r, row in rep.sweep.items()},
            fleet=rep.metrics.as_dict(),
            violations=rep.violations))
    return rows, fleet


def run_wallclock(plans=None, fleet=None, n_requests=None, log=print):
    import dataclasses

    import numpy as np

    from repro.serve.fleet import FleetConfig
    from repro.serve.realtime import EngineReplica, RealClock
    from repro.sim.e2e import E2EConfig, EngineFleet
    from repro.sim.realtime_chaos import PLANS, run_realtime_chaos

    plans = list(plans) if plans else list(WALLCLOCK_PLANS)
    ecfg = E2EConfig()
    if fleet is None or fleet.n < WALLCLOCK_N:
        fleet = EngineFleet(WALLCLOCK_N)
    replicas = [EngineReplica(e, ecfg.max_new_tokens)
                for e in fleet.engines[:WALLCLOCK_N]]
    # warm every engine (jit paid here), then time one request per
    # replica to pick the timescale: plans keep their stub-time
    # proportions, so heartbeat/arrival/fault spacing stays meaningful
    # whatever the hardware
    req = np.arange(1, 9, dtype=np.int32)
    for rep in replicas:
        rep.process(req, lambda: False)
    t0 = time.time()
    for rep in replicas:
        rep.process(req, lambda: False)
    lat = (time.time() - t0) / len(replicas)
    scale = max(lat, 1e-3) / WALLCLOCK_STUB_WORK
    rows = []
    for name in plans:
        plan = PLANS[name](WALLCLOCK_N, scale=scale)
        if n_requests is not None and n_requests < plan.n_requests:
            log(f"# wallclock/{name}: truncated to {n_requests}/"
                f"{plan.n_requests} requests (smoke)")
            plan = dataclasses.replace(plan, n_requests=n_requests)
        cfg = FleetConfig(n_replicas=WALLCLOCK_N, r=1, seed=0,
                          heartbeat_period=2.0 * scale)
        t0 = time.time()
        rep = run_realtime_chaos(plan, cfg, clock=RealClock(),
                                 replicas=replicas)
        rows.append(dict(wall_s=time.time() - t0, scale=scale,
                         **rep.as_dict()))
    return rows, fleet


def check_wallclock_rows(rows, smoke: bool) -> list:
    """§17 gates on real timers, outcomes only: zero permanent loss,
    conformance clean, every kill answered by a restart + rejoin. The
    recovery ratio is reported but not gated — wall-clock goodput on a
    shared CI box is informative, not reproducible."""
    problems = []
    for row in rows:
        name = row["plan"]
        if row["violations"]:
            problems.append(f"wallclock/{name}: "
                            f"{len(row['violations'])} violations: "
                            f"{row['violations'][:3]}")
        if row["lost"]:
            problems.append(f"wallclock/{name}: {row['lost']} requests "
                            f"permanently lost")
        if not row["drained"]:
            problems.append(f"wallclock/{name}: shutdown did not drain")
        if not smoke and name in ("kill_rejoin", "crash_cascade"):
            if not (row["deaths"] >= 1 and row["rejoins"] >= 1):
                problems.append(f"wallclock/{name}: kill never detected "
                                f"or never rejoined "
                                f"(deaths={row['deaths']}, "
                                f"rejoins={row['rejoins']})")
    return problems


def check_fleet_rows(rows, smoke: bool) -> list:
    """§16 acceptance gates: conformance clean (no permanent loss with
    >= n-r survivors, no vote below the 2f+1 floor), and on full runs
    the gated scenarios' post-rejoin success rate must recover to >=
    FLEET_RECOVERED_MIN of the pre-fault rate with zero requests
    permanently lost."""
    import math
    problems = []
    for row in rows:
        name, m = row["scenario"], row["fleet"]
        if row["violations"]:
            problems.append(f"fleet/{name}: {len(row['violations'])} "
                            f"violations: {row['violations'][:3]}")
        if smoke:
            continue
        if m["permanently_lost"]:
            problems.append(f"fleet/{name}: {m['permanently_lost']} "
                            f"requests permanently lost")
        if name in FLEET_RECOVERY_GATED:
            rec = m["recovered"]
            if not (math.isfinite(rec) and rec >= FLEET_RECOVERED_MIN):
                problems.append(f"fleet/{name}: post-rejoin recovery "
                                f"{rec} < {FLEET_RECOVERED_MIN}")
    return problems


def check_rows(rows) -> list:
    """The acceptance gates of DESIGN.md §15, machine-checked at record
    time so a drifted BENCH_e2e.json can never be committed quietly:
    conformance must be clean outside the scenarios that expect
    violations, and p99 TTFT must improve with r wherever a straggler
    ramp (or permanent stragglers) gives redundancy something to hide."""
    from repro.sim.scenario import get_scenario
    problems = []
    for row in rows:
        name = row["scenario"]
        if name not in EXPECT_VIOLATIONS and row["violations"]:
            problems.append(f"{name}: {len(row['violations'])} conformance "
                            f"violations: {row['violations'][:3]}")
        sc = get_scenario(name)
        if sc.faults.ramps or sc.stragglers:
            curve = [row["sweep"][str(r)]["p99_ttft"]
                     for r in (0, 1, 2, 3)]
            if not all(a >= b for a, b in zip(curve, curve[1:])):
                problems.append(f"{name}: p99 TTFT not improving with r: "
                                f"{curve}")
    return problems


def _fmt_wallclock(row) -> str:
    return (f"wallclock/{row['plan']},{row['wall_s'] * 1e6:.0f},"
            f"scale={row['scale']:.3f};deaths={row['deaths']};"
            f"rejoins={row['rejoins']};restarts={row['restarts']};"
            f"hedge_rate={row['hedge_rate']:.3f};"
            f"retries={row['retries']};lost={row['lost']};"
            f"rec_t={row['recovery_time_mean']:.2f}/"
            f"{row['recovery_time_max']:.2f};"
            f"recovered={row['recovered']:.3f};"
            f"goodput={row['goodput_pre']:.3f}->{row['goodput_post']:.3f};"
            f"ok={row['delivered']}/{row['delivered'] + row['lost']};"
            f"viol={len(row['violations'])}")


def record(rows, dispatch_rows, smoke: bool,
           fleet_rows=None, wallclock_rows=None) -> pathlib.Path:
    import jax
    from repro.sim.e2e import E2EConfig
    ecfg = E2EConfig()
    payload = {
        "meta": {
            "backend": jax.default_backend(),
            "devices": jax.device_count(),
            "arch": ecfg.arch, "max_new_tokens": ecfg.max_new_tokens,
            "superstep_k": ecfg.superstep_k,
            "smoke": smoke,   # a reduced sweep must be visibly reduced
            "note": "reduced() registry archs; every row is a full "
                    "scenario replay against real replicated engines "
                    "with per-superstep virtual-time fault injection "
                    "(DESIGN.md §15); sweep rows are the post-hoc "
                    "first-(n-r) selection over one recorded run; "
                    "dispatch rows are the stand-in replica curve at "
                    "the same fleet size for comparison",
        },
        "scenarios": [{**r, "violations": len(r["violations"])}
                      for r in rows],
        "dispatch_standin": dispatch_rows,
    }
    if fleet_rows is not None:
        payload["fleet"] = [{**r, "violations": len(r["violations"])}
                            for r in fleet_rows]
    if wallclock_rows is not None:
        payload["wallclock"] = [{**r, "violations": len(r["violations"])}
                                for r in wallclock_rows]
    path = BENCH_PATH.with_suffix(".smoke.json") if smoke else BENCH_PATH
    path.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    print(f"wrote {path}")
    return path


def _fmt(row) -> str:
    nat = row["native"]
    curve = ";".join(f"r{r}={row['sweep'][str(r)]['p99_ttft']:.3f}"
                     for r in (0, 1, 2, 3))
    return (f"e2e/{row['scenario']},{row['wall_s'] * 1e6:.0f},"
            f"p99_ttft={nat['p99_ttft']:.3f};p99_lat={nat['p99_latency']:.3f};"
            f"goodput={nat['goodput']:.4f};ok={nat['n_ok']}/"
            f"{nat['n_requests']};deg={nat['n_degraded']};"
            f"retries={row['retries']};viol={nat['violations']};{curve}")


def _fmt_fleet(row) -> str:
    m = row["fleet"]
    nat = row["native"]
    return (f"fleet/{row['scenario']},{row['wall_s'] * 1e6:.0f},"
            f"deaths={m['deaths']};rejoins={m['rejoins']};"
            f"restarts={m['restarts']};hedges={m['hedges']};"
            f"retries={m['retries']};shed={m['shed']};"
            f"lost={m['permanently_lost']};"
            f"rec_t={m['recovery_time_mean']:.2f}/{m['recovery_time_max']:.2f};"
            f"recovered={m['recovered']:.3f};"
            f"goodput={m['goodput_pre']:.4f}->{m['goodput_post']:.4f};"
            f"ok={nat['n_ok']}/{nat['n_requests']};"
            f"viol={nat['violations']}")


def main(smoke: bool = False, do_record: bool = False, names=None,
         fleet_mode: bool = False, wallclock_mode: bool = False,
         wallclock_only: bool = False):
    try:                  # package import (benchmarks/run.py harness) …
        from benchmarks.serve_latency import run_dispatch
    except ImportError:   # … or standalone `python benchmarks/e2e_load.py`
        from serve_latency import run_dispatch
    from repro.sim.scenario import SCENARIOS
    problems, rows, fleet = [], [], None
    if not wallclock_only:
        n_req = SMOKE_REQUESTS if smoke else None
        rows, fleet = run_scenarios(names=names, n_requests=n_req)
        for row in rows:
            print(_fmt(row), flush=True)
        problems = check_rows(rows)
    fleet_rows = None
    if fleet_mode and not wallclock_only:
        fnames = [n for n in (names or FLEET_SCENARIOS)
                  if n in FLEET_SCENARIOS]
        if fnames:
            fleet_rows, _ = run_fleet_scenarios(
                names=fnames, fleet=fleet,
                n_requests=FLEET_SMOKE_REQUESTS if smoke else None)
            for row in fleet_rows:
                print(_fmt_fleet(row), flush=True)
            problems += check_fleet_rows(fleet_rows, smoke)
    wallclock_rows = None
    if wallclock_mode or wallclock_only:
        wallclock_rows, fleet = run_wallclock(
            fleet=fleet,
            n_requests=WALLCLOCK_SMOKE_REQUESTS if smoke else None)
        for row in wallclock_rows:
            print(_fmt_wallclock(row), flush=True)
        problems += check_wallclock_rows(wallclock_rows, smoke)
    if do_record and not wallclock_only:
        dispatch_rows = run_dispatch(200 if smoke else 2000,
                                     n_replicas=fleet.n)
        record(rows, dispatch_rows, smoke, fleet_rows=fleet_rows,
               wallclock_rows=wallclock_rows)
    if not wallclock_only and names is None and \
            set(SCENARIOS) - {r["scenario"] for r in rows}:
        problems.append("not every registered scenario was replayed")
    assert not problems, "; ".join(problems)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help=f"truncate every scenario to {SMOKE_REQUESTS} "
                         f"requests (CI)")
    ap.add_argument("--record", action="store_true",
                    help="write BENCH_e2e.json (BENCH_e2e.smoke.json "
                         "under --smoke)")
    ap.add_argument("--scenario", action="append", default=None,
                    help="replay only this scenario (repeatable)")
    ap.add_argument("--fleet", action="store_true",
                    help="additionally replay the fault scenarios through "
                         "the fleet controller (detection + hedged "
                         "re-dispatch + checkpoint rejoin) and gate on "
                         "recovery metrics")
    ap.add_argument("--wallclock", action="store_true",
                    help="additionally run the realtime chaos presets on "
                         "real threads + timers (RealClock + "
                         "EngineReplica) and report recovery time, "
                         "goodput under churn, hedge-fire rate")
    ap.add_argument("--wallclock-only", action="store_true",
                    help="run only the wallclock presets (CI stage 12 "
                         "smoke)")
    args = ap.parse_args()
    main(smoke=args.smoke, do_record=args.record, names=args.scenario,
         fleet_mode=args.fleet, wallclock_mode=args.wallclock,
         wallclock_only=args.wallclock_only)
