#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name, from files of its own
under the checkout's ``bench/``:

- ``BENCHMARK.json`` (next to ``bench/``) names the cell's configuration
  and traffic mix;
- a configuration is its file of sizes, ``bench/configs/<config>.json``,
  whose ``"reference"`` key names its family. The family module
  ``bench/families/<family>.py`` says what the harness knows of that
  architecture: ``arch_config(cfg, resize=False)`` (the program's
  configuration, checked against the file), ``shapes(arch)`` and
  ``init(path, x)`` (the weights, ``bench/weights.py``), and, by the
  rules of ``bench/flops.py``, the counts
  ``train_flops_per_token(cfg, seq)``, ``prefill_flops(cfg, prompt_len)``,
  ``decode_flops(cfg, tokens, kv)`` and
  ``paged_decode_attention(cfg, queries, kv_tokens)`` (FLOPs and bytes).
  The plain reference ``bench/references/<family>.py`` is the architecture
  in float32 ``jax.numpy``, which the drivers compare with;
- ``bench/traffic/<traffic>.json`` holds the mix and names the driver
  that runs it, ``bench/drivers/<driver>.py``;
- ``bench/metrics/<metric>.py`` is the reader of each per-layer metric.

A new cell, configuration, family, mix, driver or metric is new files and
new entries; nothing here changes.

A run makes its weights and inputs from ``--seed``, warms every shape its
traffic uses (set-up), measures for ``--seconds``, checks what the timed
path produced against a plain reference, and prints, as the last line of
standard output, one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and with ``--trace 1`` ``breakdown``)
and, last, ``checks``: each number compared, with its limit. With
``--trace 0`` the metrics are the cell's end-to-end ones; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer ones, reduced from the device trace and the counters.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.

``--check control`` (not used by the benchmark's own runs) replaces the
comparison with the low-precision control described in ``PERF.md``: the
same readings, taken from the reference computed in int8.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is timed from here

import argparse                                            # noqa: E402
import importlib.util                                      # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402
import types                                               # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = ".bench_out"                    # traces, in the checkout; git-ignored
CACHE = os.path.join(ROOT, ".jax_cache")       # fixed: part of the key
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# libtpu would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    """Import ``path`` as a fresh module."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_part(root: str, kind: str, name: str):
    """``<root>/bench/<kind>/<name>.py``: a driver, family, reference or
    metric reader, found by its name."""
    return load_module(os.path.join(root, "bench", kind, name + ".py"),
                       "_".join(("bench", kind, name)).replace(
                           ".", "_").replace("-", "_"))


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str, root: str = ROOT) -> types.SimpleNamespace:
    """Everything ``BENCHMARK.json`` and the cell's files say about one
    workload: its entry, configuration with its family and reference,
    traffic mix, driver and the end-to-end and per-layer metrics it
    reports."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))

    def reports(metric) -> bool:
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return types.SimpleNamespace(
        name=workload, root=root, cell=cell, config=config,
        family=load_part(root, "families", config["reference"]),
        reference=load_part(root, "references", config["reference"]),
        traffic=traffic,
        chips=int(cell["chips"]), end_to_end=e2e, per_layer=layer,
        driver=traffic["driver"], run_seconds=bench["run_seconds"])


# ---------------------------------------------------------------------------
# compile accounting (copied from chip_smoke.py: jax monitoring events)

COMPILES = {"secs": 0.0, "count": 0, "cache_hits": 0, "installed": False}


def install_compile_listeners() -> None:
    from jax import monitoring
    if COMPILES["installed"]:
        return
    COMPILES["installed"] = True

    def on_duration(event, secs, **_):
        # wraps compile-or-fetch: a persistent-cache hit adds its
        # retrieval time only
        if event == "/jax/core/compile/backend_compile_duration":
            COMPILES["secs"] += secs
            COMPILES["count"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            COMPILES["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def enable_compile_cache() -> str:
    """The persistent cache, with every program in it: JAX's defaults
    skip programs that compile in under a second, which are most of the
    serving engine's."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# the run


class Context:
    """What a driver gets: the cell, the seed, the window length, and the
    harness's spans, profiler and compile counter."""

    def __init__(self, spec, seed: int, seconds: float, trace: bool,
                 check: str, t_start: float, trace_dir: str):
        self.spec = spec
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.check = check
        self.t_start = t_start
        self.trace_dir = trace_dir
        self.log = log

    @staticmethod
    def span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def setup_done(self) -> float:
        """Seconds of set-up so far; call at the start of the window."""
        self.compiles_at_window = COMPILES["count"]
        return time.perf_counter() - self.t_start

    def window_compiles(self) -> int:
        return COMPILES["count"] - self.compiles_at_window

    def start_trace(self) -> None:
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.trace_dir)

    def stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()


def per_layer_metrics(spec, reduced, counters) -> dict:
    """Each per-layer metric from its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in spec.per_layer:
        reader = load_part(spec.root, "metrics", m["name"])
        value = reader.read(reduced, counters, spec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(spec, res, device, reduced, trace: bool) -> dict:
    checks = res["checks"]
    correct = bool(checks) and all(v <= lim for v, lim in checks.values())
    correct = correct and res["failed"] == 0 and res.get("ok", True)
    if trace:
        metrics = per_layer_metrics(spec, reduced, res["counters"])
        device = dict(device, busy_s=reduced["busy_ns"] * 1e-9,
                      window_s=reduced["window_ns"] * 1e-9)
    else:
        want = {m["name"]: m["unit"] for m in spec.end_to_end}
        metrics = {k: {"value": float(res["metrics"][k]), "unit": u}
                   for k, u in want.items() if k in res["metrics"]}
    line = {"correct": correct, "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": metrics,
            "device": device}
    if trace:
        line["breakdown"] = {"device_ops": reduced["top_ops"],
                             "idle_gaps": reduced["top_gaps"]}
    line["checks"] = {k: {"value": float(v), "limit": float(lim)}
                      for k, (v, lim) in checks.items()}
    return line


def main(argv=None, *, require_tpu: bool = True, root: str = ROOT,
         driver_hooks=None) -> int:
    """``require_tpu=False`` and ``driver_hooks`` exist for the harness's
    own tests, which drive a run on the CPU at a small size."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--check", choices=("program", "control"),
                    default="program")
    args = ap.parse_args(argv)
    try:
        spec = cell_spec(args.workload, root)
        import jax
        import repro  # noqa: F401  the system under test
    except Exception as e:                 # an incomplete checkout
        log(f"cannot set up {args.workload!r}: {type(e).__name__}: {e}")
        return 2

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            log(f"no TPU: jax found {devices[0].platform!r} devices")
            return 3
        if len(devices) < spec.chips:
            log(f"{args.workload} needs {spec.chips} chips, jax found "
                f"{len(devices)}")
            return 3
    log(f"compile cache: {enable_compile_cache()}")
    install_compile_listeners()
    ctx = Context(spec, args.seed, args.seconds, bool(args.trace),
                  args.check, T_START if require_tpu else
                  time.perf_counter(),
                  os.path.join(root, OUT, "trace"))
    ctx.hooks = driver_hooks or {}
    driver = load_part(root, "drivers", spec.driver)
    res = driver.run(ctx)

    reduced = None
    if args.trace:
        from bench import trace as T     # noqa: E402
        peaks = T.peaks_for(devices[0].device_kind) if require_tpu else None
        reduced = T.reduce_dir(ctx.trace_dir, chips=spec.chips)
        reduced["peaks"] = peaks
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        with open(os.path.join(root, OUT, f"trace-{spec.name}.json"),
                  "w") as f:
            json.dump({"reduced": reduced, "counters": res["counters"]}, f)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(res["memory_peak_bytes"])}
    line = result_line(spec, res, device, reduced, bool(args.trace))
    log(f"window compiles: {res['window_compiles']}")
    for k, v in line["checks"].items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
