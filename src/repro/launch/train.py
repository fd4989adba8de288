"""Training step factories — the paper's technique as a first-class feature.

Two paths:

1. ``masked`` (default, pure GSPMD): Algorithm 1 via per-token loss weights.
   The host (straggler oracle / telemetry) zeroes the weights of the r
   masked agents' examples; their gradients vanish from the single bulk
   all-reduce. Straggler drop costs **zero extra collectives** and composes
   with FSDP+TP sharding of params/optimizer — this is the path the
   dry-run/roofline measures.

2. ``general`` (partial-manual shard_map over the DP axes; "model" stays
   auto/GSPMD): per-agent gradients are materialized per DP shard, enabling
   - ``cge``        two-phase CGE filter (norms all-gather + masked psum),
   - ``stale``      rule (15) with a per-agent gradient ledger,
   - ``trimmed``    coordinate-wise trimmed mean,
   - ``quantized``  int8 error-feedback compressed aggregation.
   Params/optimizer are TP-sharded + DP-replicated on this path. The
   (n, P) ledger itself shards over DP — each shard owns its agent's
   row, and ``core.ledger.ShardedGradLedger`` carries the same row
   layout server-side (DESIGN.md §5, §14).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.ledger import layout_of, ledger_zeros
from repro.dist import collectives as C
from repro.dist.registry import resolve_mode
from repro.dist.sharding import MeshRules, tree_specs, batch_specs
from repro.launch.mesh import dp_axis_names, n_agents_of
from repro.launch.specs import max_pos_for
from repro.models.model import apply_model, init_model, lm_loss
from repro.optim.optimizers import (adamw, sgd, apply_updates,
                                    clip_by_global_norm)

PyTree = Any


@dataclass(frozen=True)
class TrainConfig:
    mode: str = "masked"            # masked | sync | cge | stale | trimmed | quantized
    optimizer: str = "adamw"
    lr_kind: str = "constant"       # constant | inv_t | cosine
    lr: float = 1e-3
    lr_total: int = 1000            # cosine horizon
    warmup: int = 0
    clip_norm: float = 1.0
    aux_coef: float = 0.01
    remat_policy: str = "full"
    accum_steps: int = 1            # microbatch gradient accumulation
    f: int = 0                      # Byzantine tolerance (cge/trimmed)
    tau: int = 4                    # staleness bound (stale)
    logits_fp32: bool = False


def lr_at(tc: TrainConfig, step):
    s = step.astype(jnp.float32)
    if tc.lr_kind == "inv_t":
        base = tc.lr / (s + 1.0)
    elif tc.lr_kind == "cosine":
        frac = jnp.clip((s - tc.warmup) / max(tc.lr_total - tc.warmup, 1),
                        0.0, 1.0)
        base = 0.5 * tc.lr * (1 + jnp.cos(jnp.pi * frac))
    else:
        base = jnp.asarray(tc.lr)
    if tc.warmup:
        base = jnp.where(s < tc.warmup, tc.lr * (s + 1) / tc.warmup, base)
    return base


def make_optimizer(tc: TrainConfig):
    if tc.optimizer == "adamw":
        return adamw(weight_decay=0.0)
    if tc.optimizer == "sgdm":
        return sgd(momentum=0.9)
    return sgd()


# ---------------------------------------------------------------------------
# state


def init_state(rng, cfg: ArchConfig, tc: TrainConfig, max_pos: int = 32768,
               n_agents: int = 1) -> Dict[str, PyTree]:
    params = init_model(rng, cfg, max_pos=max_pos)
    opt = make_optimizer(tc).init(params)
    state = {"params": params, "opt": opt,
             "step": jnp.zeros((), jnp.int32)}
    if tc.mode == "stale":
        # one flat (n_agents, P) f32 buffer per run instead of a per-leaf
        # pytree of ledgers: the rule-(15) substitution and the masked
        # psum run over a single resident array, with the leaf offsets
        # from the cached repro.core.ledger layout — built through the
        # same ledger_zeros helper as GradLedger/ShardedGradLedger, so
        # the (n, P) layout contract exists once (DESIGN.md §11, §14)
        state["ledger"] = {
            "g": ledger_zeros(n_agents, params),
            "ts": jnp.full((n_agents,), -1, jnp.int32),
        }
    if tc.mode == "quantized":
        state["err"] = jax.tree.map(
            lambda p: jnp.zeros((n_agents,) + p.shape, jnp.float32), params)
    return state


def abstract_state(cfg: ArchConfig, tc: TrainConfig, max_pos: int = 32768,
                   n_agents: int = 1):
    return jax.eval_shape(
        lambda: init_state(jax.random.PRNGKey(0), cfg, tc,
                           max_pos=max_pos, n_agents=n_agents))


# ---------------------------------------------------------------------------
# loss


def make_loss_fn(cfg: ArchConfig, tc: TrainConfig, moe_groups: int,
                 dp=None, tp=None, param_specs=None, sizes=None):
    import contextlib
    from repro.dist.act_sharding import act_policy

    def loss_fn(params, batch):
        ctx = (act_policy(dp, tp, sizes)
               if (dp is not None or tp is not None)
               else contextlib.nullcontext())
        with ctx:
            logits, aux, _ = apply_model(
                params, batch["tokens"], cfg, mode="train",
                enc_embed=batch.get("enc_embed"),
                moe_groups=moe_groups, remat_policy=tc.remat_policy,
                param_specs=param_specs)
            return lm_loss(logits, batch["targets"], batch["weights"], aux,
                           aux_coef=tc.aux_coef)
    return loss_fn


# ---------------------------------------------------------------------------
# masked fast path (pure GSPMD)


def make_train_step(cfg: ArchConfig, tc: TrainConfig, moe_groups: int = 1,
                    dp=None, tp=None, param_specs=None, sizes=None) -> Callable:
    """Algorithm 1 / synchronous step. batch["weights"] carries the agent
    mask (zeros for dropped stragglers). Pure pjit; FSDP-compatible."""
    resolve_mode(tc.mode)               # fail fast on unknown modes
    opt = make_optimizer(tc)
    loss_fn = make_loss_fn(cfg, tc, moe_groups, dp=dp, tp=tp,
                           param_specs=param_specs, sizes=sizes)

    def step(state, batch):
        if tc.accum_steps > 1:
            # microbatch accumulation: the batch splits along the batch dim
            # into accum_steps slices processed sequentially (bounds the
            # live activation set for the >200B archs); gradients average.
            k = tc.accum_steps

            def micro(carry, i):
                acc, lsum = carry
                mb = jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * (x.shape[0] // k), x.shape[0] // k, axis=0)
                    if x.ndim else x, batch)
                l, g = jax.value_and_grad(loss_fn)(state["params"], mb)
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32), acc, g)
                return (acc, lsum + l), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            (gsum, lsum), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)),
                jnp.arange(k))
            grads = jax.tree.map(
                lambda g, p: (g / k).astype(p.dtype), gsum,
                state["params"])
            loss = lsum / k
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state["params"],
                                                      batch)
        if tc.clip_norm:
            grads, gnorm = clip_by_global_norm(grads, tc.clip_norm)
        else:
            gnorm = jnp.sqrt(C.tree_sq_norm(grads))
        updates, new_opt = opt.update(grads, state["opt"], state["params"],
                                      state["step"])
        params = apply_updates(state["params"], updates,
                               lr_at(tc, state["step"]))
        new_state = {"params": params, "opt": new_opt,
                     "step": state["step"] + 1}
        for k in ("ledger", "err"):
            if k in state:
                new_state[k] = state[k]
        return new_state, {"loss": loss, "grad_norm": gnorm}

    return step


# ---------------------------------------------------------------------------
# general path (partial-manual shard_map over DP axes)


def make_general_step(cfg: ArchConfig, tc: TrainConfig, mesh,
                      moe_groups: int = 1) -> Callable:
    """Per-agent gradient paths: cge / stale / trimmed / quantized.

    Signature: step(state, batch, fresh_mask (n_agents,) f32) -> (state, m).
    """
    opt = make_optimizer(tc)
    dp = dp_axis_names(mesh)
    n = n_agents_of(mesh)
    rule = resolve_mode(tc.mode)        # single dispatch point (registry)
    # NOTE: activation pins inside the partial-manual region trigger an
    # XLA partitioner check-failure at 256+ devices (both Shardy and legacy
    # GSPMD); the general path therefore runs without them and relies on
    # propagation from the TP-sharded params (see EXPERIMENTS.md §Perf).
    loss_fn = make_loss_fn(cfg, tc, max(moe_groups // n, 1))

    def local(state, batch, fresh_mask):
        me = C.agent_index(dp)
        mask_self = fresh_mask[me]
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)

        if tc.mode == "cge":
            agg, keep = rule.collective(grads, mask_self > 0, tc.f, dp)
            denom = jnp.sum(keep.astype(jnp.float32))
            loss = _psum_all(loss * mask_self, dp)
        elif tc.mode == "trimmed":
            agg = rule.collective(grads, mask_self > 0, tc.f, dp)
            denom = (jnp.asarray(1.0) if rule.normalized
                     else _psum_all(mask_self, dp))
            loss = _psum_all(loss * mask_self, dp)
        elif tc.mode == "stale":
            layout = layout_of(grads)   # cached shared layout (module top)
            ledger_self = state["ledger"]["g"][0]          # (W,) flat
            ts_self = state["ledger"]["ts"][0]
            fresh = mask_self > 0
            new_ts = jnp.where(fresh, state["step"], ts_self)
            usable = (state["step"] - new_ts) <= tc.tau
            flat = layout.flatten(grads)                    # (P,), P <= W
            flat = jnp.pad(flat, (0, ledger_self.shape[0] - flat.shape[0]))
            contrib = jnp.where(fresh, flat, ledger_self)
            agg_flat = rule.collective(contrib,
                                       usable.astype(jnp.float32), dp)
            agg = layout.unflatten(agg_flat, dtype=jnp.float32)
            denom = _psum_all(usable.astype(jnp.float32), dp)
            new_ledger = {"g": contrib[None], "ts": new_ts[None]}
            loss = _psum_all(loss * mask_self, dp)
        elif tc.mode == "quantized":
            err_self = jax.tree.map(lambda l: l[0], state["err"])
            agg, new_err = rule.collective(grads, mask_self, err_self, dp)
            denom = _psum_all(mask_self, dp)
            loss = _psum_all(loss * mask_self, dp)
        else:
            raise ValueError(tc.mode)

        denom = jnp.maximum(denom, 1.0)
        agg = jax.tree.map(lambda g: (g / denom), agg)
        loss = loss / denom

        if tc.clip_norm:
            agg, gnorm = clip_by_global_norm(agg, tc.clip_norm)
        else:
            gnorm = jnp.sqrt(C.tree_sq_norm(agg))
        agg = jax.tree.map(lambda a, p: a.astype(p.dtype), agg,
                           state["params"])
        updates, new_opt = opt.update(agg, state["opt"], state["params"],
                                      state["step"])
        params = apply_updates(state["params"], updates,
                               lr_at(tc, state["step"]))
        new_state = {"params": params, "opt": new_opt,
                     "step": state["step"] + 1}
        if tc.mode == "stale":
            new_state["ledger"] = new_ledger
        elif "ledger" in state:
            new_state["ledger"] = state["ledger"]
        if tc.mode == "quantized":
            new_state["err"] = jax.tree.map(lambda e: e[None], new_err)
        elif "err" in state:
            new_state["err"] = state["err"]
        return new_state, {"loss": loss, "grad_norm": gnorm}

    def _psum_all(x, axes):
        for a in axes:
            x = jax.lax.psum(x, a)
        return x

    def in_specs_of(state, batch, fresh_mask):
        dp_spec = dp if len(dp) > 1 else dp[0]
        st = jax.tree.map(lambda _: P(), state)
        if "ledger" in state:
            st["ledger"] = jax.tree.map(lambda _: P(dp_spec),
                                        state["ledger"])
        if "err" in state:
            st["err"] = jax.tree.map(lambda _: P(dp_spec), state["err"])
        bt = jax.tree.map(lambda _: P(dp_spec), batch)
        return st, bt, P()

    def step(state, batch, fresh_mask):
        st_specs, bt_specs, fm_spec = in_specs_of(state, batch, fresh_mask)
        out_state_specs = jax.tree.map(lambda s: s, st_specs)
        fn = jax.shard_map(
            partial(local),
            mesh=mesh,
            in_specs=(st_specs, bt_specs, fm_spec),
            out_specs=(out_state_specs, {"loss": P(), "grad_norm": P()}),
            axis_names=set(dp), check_vma=False)
        return fn(state, batch, fresh_mask)

    return step
