import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract memory / cost / collective statistics.

MUST be run as its own process (the two lines above precede every other
import — jax locks the device count on first init):

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh both

Results land in results/dryrun/<arch>__<shape>__<mesh>[__tag].json and feed
benchmarks/roofline.py (EXPERIMENTS.md §Dry-run / §Roofline).
"""
import argparse     # noqa: E402
import json         # noqa: E402
import re           # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import SHAPES, get_shape           # noqa: E402
from repro.configs.registry import get_config, list_configs  # noqa: E402
from repro.dist.sharding import (MeshRules, tree_specs, batch_specs,
                                 cache_specs)               # noqa: E402
from repro.launch.mesh import make_production_mesh, n_agents_of  # noqa: E402
from repro.launch.specs import (input_specs, state_specs,
                                max_pos_for)                # noqa: E402
from repro.launch import train as T                        # noqa: E402
from repro.launch import serve as V                        # noqa: E402
from repro.launch.hlo_analysis import analyze              # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}

_COLL_RE = re.compile(
    r"=\s*(?P<res>[^=]*?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?\s*\(")
_SHAPE_RE = re.compile(r"(?P<dt>[a-z]+[0-9]+(?:e[0-9]+m[0-9]+(?:fn)?)?|pred)"
                       r"\[(?P<dims>[0-9,]*)\]")


def _shape_bytes(txt: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(txt):
        dt = m.group("dt")
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str):
    """Per-device wire bytes by collective kind (ring-algorithm costs:
    all-reduce 2x result; ag/rs/a2a/permute 1x the larger side)."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        res_bytes = _shape_bytes(m.group("res"))
        # operands: first balanced paren group after the op keyword
        tail = line[m.end():]
        depth, j = 1, 0
        for j, ch in enumerate(tail):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        arg_bytes = _shape_bytes(tail[:j])
        if op == "all-reduce":
            wire = 2 * res_bytes
        elif op == "reduce-scatter":
            wire = arg_bytes
        else:
            wire = max(res_bytes, arg_bytes)
        out[op] += wire
        out["count"] += 1
    return out


def _mk_shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# named layout experiments (hillclimb levers); see EXPERIMENTS.md Perf
LAYOUTS = {
    "baseline": {},
    # full data-parallel: no TP; "model" becomes a second DP/ZeRO axis —
    # for small archs over-sharded by TP=16 (qwen2-0.5b etc.)
    "dp_all": {"tp_axes": (), "fsdp_axes": ("data", "model"),
               "dp_axes_single": ("data", "model"),
               "dp_axes_multi": ("pod", "data", "model")},
}


def _apply_cfg_patch(cfg, patch):
    import dataclasses as _dc
    if not patch:
        return cfg
    sub = {}
    top = {}
    for k, v in patch.items():
        if "." in k:
            o, f = k.split(".", 1)
            subcfg = getattr(cfg, o)
            sub.setdefault(o, {})[f] = v
        else:
            top[k] = v
    for o, fields in sub.items():
        top[o] = _dc.replace(getattr(cfg, o), **fields)
    return _dc.replace(cfg, **top)


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               mode: str = "masked", overrides=None, tc_kw=None,
               cfg_patch=None, layout: str = "baseline"):
    """Returns (lowered, meta) for one dry-run cell."""
    cfg = _apply_cfg_patch(get_config(arch), cfg_patch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    lay = LAYOUTS[layout]
    dp_axes = lay.get("dp_axes_multi" if multi_pod else "dp_axes_single")
    rules_kw = dict(
        multi_pod=multi_pod, overrides=overrides or {},
        fsdp_axes=lay.get("fsdp_axes", ("data",)),
        tp_axes=lay.get("tp_axes", ("model",)),
        ep_axes=lay.get("ep_axes", lay.get("tp_axes", ("model",))),
        dp_axes=dp_axes)
    rules = MeshRules(**rules_kw)
    n_ag = 1
    for a in rules.dp:
        n_ag *= dict(mesh.shape)[a]
    tc = T.TrainConfig(mode=mode, **(tc_kw or {}))
    kind = shape.kind
    dp = rules.dp if len(rules.dp) > 1 else rules.dp[0]
    tp = lay.get("tp_axes", ("model",))
    tp = tp[0] if tp else None
    sizes = dict(mesh.shape)

    meta = dict(arch=arch, shape=shape_name,
                mesh="multi" if multi_pod else "single",
                kind=kind, n_agents=n_ag, mode=mode,
                chips=int(mesh.devices.size))

    compute_rules = MeshRules(**{**rules_kw, "fsdp_axes": ()})
    if kind == "train" and mode in ("cge", "stale", "trimmed",
                                    "quantized"):
        # general path (partial-manual shard_map over DP): per-agent
        # gradients -> CGE filter / rule-15 ledger / compression. Params
        # are TP-sharded + DP-replicated (DESIGN.md §5); the ledger / error
        # trees carry a leading n_agents axis sharded over DP.
        state = T.abstract_state(cfg, tc, max_pos=max_pos_for(shape),
                                 n_agents=n_ag)
        batch = input_specs(cfg, shape, n_ag, "train")
        st_specs = tree_specs(state, compute_rules)
        dp_spec = dp
        for key in ("ledger", "err"):
            if key in state:
                st_specs[key] = jax.tree.map(
                    lambda l: P(*([dp_spec] + [None] * (len(l.shape) - 1))),
                    state[key])
        bt_specs = batch_specs(rules, batch)
        fresh = jax.ShapeDtypeStruct((n_ag,), jnp.float32)
        step = T.make_general_step(cfg, tc, mesh, moe_groups=n_ag)
        jf = jax.jit(step,
                     in_shardings=(_mk_shardings(mesh, st_specs),
                                   _mk_shardings(mesh, bt_specs),
                                   NamedSharding(mesh, P())))
        with jax.set_mesh(mesh):
            lowered = jf.lower(state, batch, fresh)
    elif kind == "train":
        state = T.abstract_state(cfg, tc, max_pos=max_pos_for(shape),
                                 n_agents=n_ag)
        batch = input_specs(cfg, shape, n_ag, "train")
        st_specs = tree_specs(state, rules)
        bt_specs = batch_specs(rules, batch)
        # compute-layout specs (manual ZeRO-3 gather targets) for params
        param_cspecs = tree_specs(state["params"], compute_rules)
        step = T.make_train_step(cfg, tc, moe_groups=n_ag, dp=dp, tp=tp,
                                 param_specs=param_cspecs, sizes=sizes)
        jf = jax.jit(step,
                     in_shardings=(_mk_shardings(mesh, st_specs),
                                   _mk_shardings(mesh, bt_specs)),
                     donate_argnums=(0,))
        with jax.set_mesh(mesh):
            lowered = jf.lower(state, batch)
    elif kind == "prefill":
        state = state_specs(cfg, shape, optimizer="none")
        params = state["params"]
        batch = input_specs(cfg, shape, n_ag, "prefill")
        p_specs = tree_specs(params, compute_rules)
        bt_specs = batch_specs(rules, batch)
        step = V.make_prefill_step(cfg, moe_groups=n_ag, dp=dp, tp=tp, sizes=sizes)
        jf = jax.jit(step, in_shardings=(_mk_shardings(mesh, p_specs),
                                         _mk_shardings(mesh, bt_specs)))
        with jax.set_mesh(mesh):
            lowered = jf.lower(params, batch)
    else:  # decode
        state = state_specs(cfg, shape, optimizer="none")
        params = state["params"]
        batch = input_specs(cfg, shape, n_ag, "decode")
        p_specs = tree_specs(params, compute_rules)
        b_specs = {"tokens": batch_specs(rules, batch["tokens"]),
                   "cache": cache_specs(rules, batch["cache"],
                                        n_query_heads=cfg.n_heads),
                   "pos": P()}
        step = V.make_decode_step(cfg, moe_groups=n_ag, dp=dp, tp=tp, sizes=sizes)
        jf = jax.jit(step, in_shardings=(_mk_shardings(mesh, p_specs),
                                         _mk_shardings(mesh, b_specs)),
                     donate_argnums=(1,))
        with jax.set_mesh(mesh):
            lowered = jf.lower(params, batch)
    return lowered, meta


def run_cell(arch, shape_name, multi_pod, mode="masked", overrides=None,
             tc_kw=None, out_dir=RESULTS_DIR, tag="", cfg_patch=None,
             layout="baseline"):
    t0 = time.time()
    rec = dict(arch=arch, shape=shape_name,
               mesh="multi" if multi_pod else "single", mode=mode, tag=tag,
               layout=layout, cfg_patch=cfg_patch)
    try:
        lowered, meta = build_cell(arch, shape_name, multi_pod, mode,
                                   overrides, tc_kw, cfg_patch, layout)
        rec.update(meta)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):       # jax 0.4.x: list of dicts
            ca = ca[0] if ca else {}
        rec["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float)) and k in
                       ("flops", "bytes accessed", "transcendentals",
                        "utilization operand 0 {}", "optimal_seconds")}
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                k: int(getattr(ma, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes") if hasattr(ma, k)}
        except Exception as e:  # pragma: no cover
            rec["memory"] = {"error": str(e)}
        hlo = compiled.as_text()
        rec["hlo"] = analyze(hlo)           # while-aware flops/bytes/colls
        rec["collectives"] = collective_bytes(hlo)  # body-once (reference)
        try:
            import zstandard as zstd
            os.makedirs(out_dir, exist_ok=True)
            nm = f"{arch}__{shape_name}__{rec['mesh']}"
            if tag:
                nm += f"__{tag}"
            with open(os.path.join(out_dir, nm + ".hlo.zst"), "wb") as zf:
                zf.write(zstd.ZstdCompressor(level=6).compress(
                    hlo.encode()))
        except Exception:
            pass
        rec["lower_s"] = round(t1 - t0, 2)
        rec["compile_s"] = round(t2 - t1, 2)
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}__{shape_name}__{rec['mesh']}"
    if tag:
        name += f"__{tag}"
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def live_cells():
    """The 32 live (arch x shape) cells (long_500k only for sub-quadratic
    archs; see DESIGN.md skip list)."""
    cells = []
    for arch in list_configs():
        cfg = get_config(arch)
        for shape in SHAPES:
            if shape.name == "long_500k" and not cfg.sub_quadratic:
                continue
            cells.append((arch, shape.name))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--mode", default="masked")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    cells = live_cells() if args.all else [(args.arch, args.shape)]
    meshes = (["single", "multi"] if args.mesh == "both"
              else [args.mesh])
    for arch, shape in cells:
        for mesh in meshes:
            rec = run_cell(arch, shape, mesh == "multi", args.mode,
                           out_dir=args.out, tag=args.tag)
            jax.clear_caches()
            status = "OK " if rec.get("ok") else "FAIL"
            print(f"[{status}] {arch:18s} {shape:12s} {mesh:6s} "
                  f"compile={rec.get('compile_s', '-')}s "
                  f"flops={rec.get('cost', {}).get('flops', '-')} "
                  f"coll={rec.get('collectives', {}).get('count', '-')}"
                  + ("" if rec.get("ok") else f"  {rec.get('error')}"),
                  flush=True)


if __name__ == "__main__":
    main()


def reanalyze(results_dir=RESULTS_DIR):
    """Recompute the hlo analysis of every saved .hlo.zst (no recompiles)."""
    import zstandard as zstd
    import glob
    for hp in sorted(glob.glob(os.path.join(results_dir, "*.hlo.zst"))):
        jp = hp[:-8] + ".json"
        if not os.path.exists(jp):
            continue
        with open(hp, "rb") as f:
            hlo = zstd.ZstdDecompressor().decompress(f.read()).decode()
        with open(jp) as f:
            rec = json.load(f)
        rec["hlo"] = analyze(hlo)
        with open(jp, "w") as f:
            json.dump(rec, f, indent=1)
        print("reanalyzed", os.path.basename(jp), flush=True)
