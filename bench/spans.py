#!/usr/bin/env python3
"""The program's own host spans in a profiler trace, on the clock of the
device planes that ``bench/trace.py`` reduces.

``src/`` writes a span at each layer boundary of the serving engine and
the training loop with ``jax.profiler.TraceAnnotation``: names under
``repro.serve.`` and ``repro.train.``, request ids and counts as the
spans' stats (``PERF.md`` lists each). ``reduce_profile`` returns what
``bench/trace.py`` returns for a trace, and three keys more:

- ``program_spans``: every ``repro.*`` host event wholly inside the
  window, as ``[start_ns, end_ns, name, {stat: value}]``, in time order;
- ``idle_by_phase``: the idle gaps of ``idle_by_span``, each put down to
  the innermost harness (``bench.*``) or program (``repro.*``) span that
  holds its midpoint, else to ``(no span)``;
- ``wall_by_span``: ``[count, wall ns]`` of the harness and program spans
  wholly inside the window, by name.

The functions after it read per-layer numbers from those spans, each
``None`` where the trace has no program spans.

As a script it runs one cell as ``bench/run.py`` does (same arguments,
same result line) with these keys in the reduction of a ``--trace 1``
run, and logs the readings, the idle by phase and the span walls to
standard error:

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s> --trace 1

The harness's own runs do not read these yet (``PERF.md``, Open
questions).
"""
from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

PROGRAM_PREFIX = "repro."
# the spans in which admission first works on a request
ADMISSION_WORK = ("repro.serve.prefill", "repro.serve.page_write",
                  "repro.serve.suffix")


def host_spans(pd):
    """(harness, program) host spans of a ``ProfileData``: harness spans
    as (start, end, name), program spans as (start, end, name, stats)."""
    harness, program = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(trace.SPAN_PREFIX):
                    harness.append((ev.start_ns, ev.end_ns, ev.name))
                elif ev.name.startswith(PROGRAM_PREFIX):
                    program.append((ev.start_ns, ev.end_ns, ev.name,
                                    dict(ev.stats)))
    return harness, program


def _first_device_gaps(pd, chips: int, lo: float, hi: float):
    """The idle gaps of the lowest-numbered device used, as
    ``bench/trace.py`` finds them."""
    devices = {}
    for plane in pd.planes:
        idx = trace._device_index(plane.name)
        if idx is None or idx >= chips:
            continue
        ops = [(ev.start_ns, ev.end_ns) for line in plane.lines
               if line.name == trace.OPS_LINE for ev in line.events]
        devices[idx] = ops
    busy = trace._union(trace._clip(devices[min(devices)], lo, hi))
    return trace._gaps(busy, lo, hi)


def idle_by_owner(gaps, spans) -> Dict[str, float]:
    """Each gap's length, summed by the innermost of ``spans`` (start,
    end, name) that holds the gap's midpoint: the shortest, and of equal
    ones the first listed; ``(no span)`` where none does. ``gaps`` come
    in time order, so one sweep over the spans by start serves them
    all."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][0])
    by: Dict[str, float] = {}
    live: List[int] = []
    j = 0
    for gs, ge in gaps:
        mid = 0.5 * (gs + ge)
        while j < len(order) and spans[order[j]][0] <= mid:
            live.append(order[j])
            j += 1
        live = [i for i in live if spans[i][1] >= mid]
        owner = "(no span)"
        if live:
            owner = spans[min(live, key=lambda i: (
                spans[i][1] - spans[i][0], i))][2]
        by[owner] = by.get(owner, 0.0) + (ge - gs)
    return by


def reduce_profile(pd, chips: int = 1) -> dict:
    """``bench/trace.py``'s reduction of ``pd`` with ``program_spans``,
    ``idle_by_phase`` and ``wall_by_span``."""
    out = trace.reduce_profile(pd, chips=chips)
    harness, program = host_spans(pd)
    windows = [(s, e) for s, e, n in harness if n == trace.WINDOW_SPAN]
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    inner = [(s, e, n) for s, e, n in harness if n != trace.WINDOW_SPAN]
    named = inner + [(s, e, n) for s, e, n, _ in program]
    out["idle_by_phase"] = idle_by_owner(
        _first_device_gaps(pd, chips, lo, hi), named)
    out["program_spans"] = sorted(
        ([s, e, n, st] for s, e, n, st in program if s >= lo and e <= hi),
        key=lambda p: (p[0], -p[1]))
    wall: Dict[str, List[float]] = {}
    for s, e, n in named:
        if s >= lo and e <= hi:
            w = wall.setdefault(n, [0, 0.0])
            w[0] += 1
            w[1] += e - s
    out["wall_by_span"] = wall
    return out


def reduce_dir(trace_dir: str, chips: int = 1) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(
        trace.find_xplane(trace_dir)), chips=chips)


# ---------------------------------------------------------------------------
# readings


def span_events(reduced: dict, name: str) -> list:
    """The window's program spans called ``name``, in time order."""
    return [ev for ev in reduced.get("program_spans") or ()
            if ev[2] == name]


def span_wall_ns(reduced: dict, name: str) -> Tuple[int, float]:
    """(count, summed wall ns) of the window's program spans ``name``."""
    evs = span_events(reduced, name)
    return len(evs), sum(e - s for s, e, _, _ in evs)


def request_ids(stats: dict) -> List[int]:
    """The requests a span names: ``rid``, or ``rids`` joined by ``;``
    (a lone id comes back from the trace as a number)."""
    if "rid" in stats:
        return [int(stats["rid"])]
    if "rids" in stats:
        return [int(r) for r in str(stats["rids"]).split(";")]
    return []


def queue_waits_ms(reduced: dict) -> np.ndarray:
    """Per request with both in the window: the start of the first
    admission work that names it less the start of its
    ``repro.serve.submit``."""
    submit = {int(st["rid"]): s for s, _, _, st in
              span_events(reduced, "repro.serve.submit")}
    first: Dict[int, float] = {}
    for name in ADMISSION_WORK:
        for s, _, _, st in span_events(reduced, name):
            for rid in request_ids(st):
                first[rid] = min(s, first.get(rid, s))
    return np.asarray([first[r] - t for r, t in submit.items()
                       if r in first]) * 1e-6


def queue_wait_ms(reduced: dict) -> Optional[float]:
    """Mean of ``queue_waits_ms``: ``queue_wait_ms.chat``."""
    waits = queue_waits_ms(reduced)
    return float(waits.mean()) if waits.size else None


def _per(reduced: dict, name: str, stat: str) -> Optional[float]:
    """Summed wall ms of the spans ``name`` over the sum of their
    ``stat``."""
    evs = span_events(reduced, name)
    n = sum(int(st.get(stat, 0)) for _, _, _, st in evs)
    return sum(e - s for s, e, _, _ in evs) / n * 1e-6 if n else None


def admit_ms_per_req(reduced: dict) -> Optional[float]:
    """Wall of ``repro.serve.admit`` per admitted request (its ``n``):
    ``admit_ms_per_req.chat``."""
    return _per(reduced, "repro.serve.admit", "n")


def decode_iter_ms(reduced: dict) -> Optional[float]:
    """Wall of ``repro.serve.decode`` per decode iteration (its ``k``):
    ``decode_iter_ms.chat``."""
    return _per(reduced, "repro.serve.decode", "k")


def host_ms_per_step(reduced: dict) -> Optional[float]:
    """Wall of ``repro.train.feed`` and ``repro.train.select`` per
    ``repro.train.step``: ``host_ms_per_step.train``."""
    steps, _ = span_wall_ns(reduced, "repro.train.step")
    if not steps:
        return None
    return (span_wall_ns(reduced, "repro.train.feed")[1]
            + span_wall_ns(reduced, "repro.train.select")[1]) / steps * 1e-6


READINGS = {"queue_wait_ms.chat": queue_wait_ms,
            "admit_ms_per_req.chat": admit_ms_per_req,
            "decode_iter_ms.chat": decode_iter_ms,
            "host_ms_per_step.train": host_ms_per_step}


def log_readings(reduced: dict, log) -> None:
    for name, ns in sorted(reduced["idle_by_phase"].items(),
                           key=lambda kv: -kv[1])[:10]:
        log(f"idle by phase: {name} {ns * 1e-9!r} s")
    for name, (n, ns) in sorted(reduced["wall_by_span"].items()):
        log(f"span wall: {name} x{n} {ns * 1e-9!r} s")
    waits = queue_waits_ms(reduced)
    if waits.size:
        log(f"queue wait: {waits.size} requests, p50 "
            f"{float(np.percentile(waits, 50))!r} ms, p95 "
            f"{float(np.percentile(waits, 95))!r} ms")
    for name, read in READINGS.items():
        value = read(reduced)
        if value is not None:
            log(f"span reading: {name} {value!r}")


def main(argv=None) -> int:
    from bench import run
    kept = {}

    def reduce_with_spans(trace_dir: str, chips: int = 1) -> dict:
        kept["reduced"] = reduce_dir(trace_dir, chips=chips)
        return kept["reduced"]

    trace.reduce_dir = reduce_with_spans
    rc = run.main(argv)
    if "reduced" in kept:
        log_readings(kept["reduced"], run.log)
    return rc


if __name__ == "__main__":
    sys.exit(main())
