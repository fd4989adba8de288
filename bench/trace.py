"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The reduction, for the traced window (the harness span ``bench.window``):

- busy and idle: the union of the intervals in which an operation ran on
  each device, averaged over the chips used, against the window's length;
- device time per program (the ``XLA Modules`` line) and per operation
  (the ``XLA Ops`` line), by stable name: a program's name without its
  ``(id)`` suffix, an operation's HLO instruction name; each operation's
  whole HLO text and stats are kept beside it for readers that match a
  kernel. Operations nest (a loop holds its body's operations): ``ns``
  is an operation's whole time, ``self_ns`` its time less what nests in
  it, and the breakdown ranks by ``self_ns``. Only operations wholly
  inside the window count;
- idle gaps: each interval with no operation on device 0 is attributed to
  the innermost harness span (``bench.*``) that the host was in at the
  gap's midpoint.

Times are nanoseconds as the trace gives them. Nothing here knows a
workload: readers in ``bench/metrics/`` pick what they need.
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 1_000.0           # shorter gaps are dispatch jitter


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def stable_module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def stable_op_name(text: str) -> str:
    """An op event's name is its HLO instruction; keep the instruction's
    name (``fusion.12``), not its operands."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def _self_times(ops) -> None:
    """Events of one line nest (a ``while`` holds its body's ops): give
    each its own time less the time of the events inside it."""
    ops.sort(key=lambda o: (o[0], -o[1]))
    stack = []
    for o in ops:
        while stack and stack[-1][1] <= o[0]:
            stack.pop()
        for outer in reversed(stack):           # the innermost that holds o
            if o[1] <= outer[1]:
                outer[4] -= o[1] - o[0]
                break
        stack.append(o)


def _device_index(plane_name: str):
    m = re.match(r"^/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def reduce_profile(pd, chips: int = 1) -> dict:
    """``pd``: a ``jax.profiler.ProfileData``. Returns the reduced dict."""
    spans: List[Tuple[float, float, str]] = []
    devices: Dict[int, dict] = {}
    for plane in pd.planes:
        idx = _device_index(plane.name)
        if idx is None:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append((ev.start_ns, ev.end_ns, ev.name))
            continue
        if idx >= chips:
            continue
        lines = {line.name: line for line in plane.lines}
        ops = []
        if OPS_LINE in lines:
            for ev in lines[OPS_LINE].events:
                label = " ".join([ev.name] + [str(v) for _, v in ev.stats
                                              if isinstance(v, str)])
                ops.append([ev.start_ns, ev.end_ns, stable_op_name(ev.name),
                            label, ev.end_ns - ev.start_ns])
        _self_times(ops)
        mods = []
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                mods.append((ev.start_ns, ev.end_ns,
                             stable_module_name(ev.name)))
        devices[idx] = {"ops": ops, "modules": mods}

    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    window = hi - lo

    busy_total = 0.0
    ops_acc: Dict[str, dict] = {}
    mods_acc: Dict[str, dict] = {}
    for idx, d in devices.items():
        busy = _union(_clip([(o[0], o[1]) for o in d["ops"]], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for s, e, name, label, own in d["ops"]:
            if s >= lo and e <= hi:
                a = ops_acc.setdefault(name, {"count": 0, "ns": 0.0,
                                              "self_ns": 0.0,
                                              "label": label})
                a["count"] += 1
                a["ns"] += e - s
                a["self_ns"] += own
        for s, e, name in d["modules"]:
            for cs, ce in _clip([(s, e)], lo, hi):
                a = mods_acc.setdefault(name, {"count": 0, "ns": 0.0})
                a["count"] += 1
                a["ns"] += ce - cs
        if idx == min(devices):
            gaps = _gaps(busy, lo, hi)
    n_dev = len(devices)
    for acc in (ops_acc, mods_acc):
        for a in acc.values():
            for k in ("ns", "self_ns", "count"):
                if k in a:
                    a[k] /= n_dev

    by_span: Dict[str, float] = {}
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW_SPAN]
    for gs, ge in gaps:
        mid = 0.5 * (gs + ge)
        owner = "(no span)"
        best = None
        for s, e, n in inner:
            if s <= mid <= e and (best is None or e - s < best):
                owner, best = n, e - s
        by_span[owner] = by_span.get(owner, 0.0) + (ge - gs)

    top_ops = sorted(ops_acc.items(),
                     key=lambda kv: -kv[1]["self_ns"])[:10]
    top_gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_ns": window,
        "busy_ns": busy_total / n_dev,
        "ops": ops_acc,
        "modules": mods_acc,
        "idle_by_span": by_span,
        "top_ops": [[k, v["self_ns"] * 1e-9] for k, v in top_ops],
        "top_gaps": [[k, v * 1e-9] for k, v in top_gaps],
    }


def _gaps(busy, lo: float, hi: float):
    out, t = [], lo
    for s, e in busy:
        if s - t >= MIN_GAP_NS:
            out.append((t, s))
        t = max(t, e)
    if hi - t >= MIN_GAP_NS:
        out.append((t, hi))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir: str, chips: int = 1) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)),
                          chips=chips)


# ---------------------------------------------------------------------------
# helpers for metric readers


def module_time_ns(reduced: dict, pattern: str) -> Tuple[float, float]:
    """(count, ns) of the programs whose stable name matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [v for k, v in reduced["modules"].items() if rx.search(k)]
    return sum(v["count"] for v in hits), sum(v["ns"] for v in hits)


def op_time_ns(reduced: dict, pattern: str) -> Tuple[float, float]:
    """(count, ns) of the operations whose name or stats match."""
    rx = re.compile(pattern)
    hits = [v for k, v in reduced["ops"].items() if rx.search(v["label"])]
    return sum(v["count"] for v in hits), sum(v["ns"] for v in hits)


def idle_pct(reduced: dict):
    """Percent of the traced window with no operation on the device."""
    if not reduced["window_ns"]:
        return None
    return 100.0 * (1.0 - reduced["busy_ns"] / reduced["window_ns"])
