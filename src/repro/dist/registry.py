"""Unified aggregation-rule registry (the dispatch tentpole).

One ``AggregationRule`` strategy object per rule, bundling

- ``reference``   the jittable numpy/jnp form from ``repro.core.gradagg``
                  operating on a stacked ``(n, d)`` gradient matrix plus a
                  boolean ``received`` mask (the reference engine's view),
- ``collective``  the raw shard_map-side twin from
                  ``repro.dist.collectives`` (native signature — e.g.
                  ``cge_psum`` also returns its keep-set),
- ``spmd``        a uniform wrapper ``(tree, mask_self, f, axes) -> tree``
                  with exactly the reference semantics, used by the
                  reference/SPMD parity suite,
- ``wire_bytes``  upload payload width per parameter (None -> the wire
                  dtype's width; 1 for the int8 compressed rule), which
                  the async engine's ``History.bytes_tx`` accounting uses,
- ``device``      the f32 device-resident twin over a flat ``(n, P)``
                  ledger (Pallas kernels on TPU via ``kernels/ops.py``,
                  jnp elsewhere), consumed by the fused
                  ``core.ledger.make_aggregate_apply`` jit; rules without
                  a specialized form fall back to their (jittable)
                  reference.

``EngineConfig.rule`` (via ``gradagg.make_gradagg``) and
``TrainConfig.mode`` (via ``resolve_mode`` in the SPMD step factories)
both resolve through this table — there is no second string-matched
rule dispatch anywhere in the repo (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import gradagg
from repro.dist import collectives as C

PyTree = Any


@dataclasses.dataclass(frozen=True)
class AggregationRule:
    name: str
    reference: Callable                  # (g, received[, f]) -> (d,)
    collective: Callable                 # native shard_map-side twin
    spmd: Callable                       # (tree, mask_self, f, axes) -> tree
    needs_f: bool = False
    normalized: bool = False             # True if output is already a mean
    wire_bytes: Optional[int] = None     # upload bytes/param (None = dtype)
    device: Optional[Callable] = None    # (g (n,P) f32, received[, f]) twin
    doc: str = ""

    def bind_reference(self, f: int = 0) -> Callable:
        """Reference callable with the Byzantine tolerance bound."""
        if self.needs_f:
            return partial(self.reference, f=f)
        return self.reference

    def bind_device(self, f: int = 0) -> Callable:
        """Device twin ``(g (n, P) f32, received (n,) bool) -> (P,) f32``
        for the fused aggregate_apply jit over a resident ledger
        (DESIGN.md §11). Falls back to the reference — every reference
        rule is pure jittable jnp — when no kernel-backed form exists."""
        fn = self.device or self.reference
        if self.needs_f:
            return partial(fn, f=f)
        return fn

    def bind_sharded(self, f: int = 0, *, axes, n: int,
                     combine: str = "gather") -> Callable:
        """Shard_map-body twin over a dp-sharded ledger (DESIGN.md §14):
        ``(g_loc (n_loc, P) f32, received (n,) bool) -> (P,) f32`` where
        ``g_loc`` is this shard's row block and ``received`` is the full
        replicated mask. Two combine modes:

        - ``"gather"``   rebuild the full ledger bit-exactly
                         (``ledger_all_rows``) and run the unsharded
                         device twin — the conformance mode, bit-identical
                         to the single-buffer PR 4 path by construction.
        - ``"partial"``  run the fused GradAgg kernel on the local row
                         block and finish with ONE masked psum — the
                         production mode (P-sized memory per shard stays
                         n_loc x P); reduction order differs from the
                         monolithic dot, so parity is tolerance-checked.
                         trimmed_mean has no partial form (coordinate-wise
                         order statistics need every row) and falls back
                         to gather, same as its collective twin.
        """
        if combine not in ("gather", "partial"):
            raise ValueError(f"unknown combine mode {combine!r}")
        sharded = _PARTIAL_FORMS.get(self.name) if combine == "partial" \
            else None
        if sharded is not None:
            return partial(sharded, f=f, axes=axes, n=n) if self.needs_f \
                else partial(sharded, axes=axes, n=n)
        dev = self.bind_device(f)

        def gather_run(g_loc, received):
            return dev(C.ledger_all_rows(g_loc, axes, n), received)

        return gather_run


# ---------------------------------------------------------------------------
# uniform SPMD wrappers (parity-suite semantics == reference semantics)


def _spmd_sum(tree, mask, f, axes):
    del f
    return C.masked_psum(tree, mask, axes)


def _spmd_mean(tree, mask, f, axes):
    del f
    agg = C.masked_psum(tree, mask, axes)
    denom = jnp.maximum(C.psum_all(mask, axes), 1.0)
    return jax.tree.map(lambda g: g / denom, agg)


def _spmd_cge(tree, mask, f, axes):
    return C.cge_psum(tree, mask > 0, f, axes)[0]


def _spmd_trimmed(tree, mask, f, axes):
    return C.trimmed_mean_all(tree, mask > 0, f, axes)


def _spmd_quantized(tree, mask, f, axes):
    del f
    zeros = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), tree)
    return C.quantized_psum(tree, mask, zeros, axes)[0]


# ---------------------------------------------------------------------------
# device twins (flat (n, P) f32 ledger form; kernels/ops dispatches on
# backend — Pallas on TPU, jnp oracle elsewhere)


def _dev_sum(g, received):
    from repro.kernels.agg import masked_sum_dot
    return masked_sum_dot(g, received)


def _dev_mean(g, received):
    from repro.kernels.agg import masked_sum_dot
    s = masked_sum_dot(g, received)
    return s / jnp.maximum(jnp.sum(received.astype(jnp.float32)), 1.0)


def _dev_cge(g, received, f):
    from repro.kernels import ops as K
    return K.masked_cge_reduce(g, received, f=f)


def _dev_trimmed(g, received, f):
    from repro.kernels import ops as K
    return K.trimmed_mean_tiled(g, received, f=f)


def _dev_quantized(g, received):
    from repro.kernels import ops as K
    q, scale = gradagg.quantize_int8_parts(g.astype(jnp.float32))
    return K.dequant_accum(q, scale[:, 0], received)


# ---------------------------------------------------------------------------
# shard-local partial forms (combine="partial"; DESIGN.md §14)
#
# Each runs inside a shard_map body on this shard's (n_loc, P) row block
# with the full replicated (n,) received mask, applies the same fused
# kernel the replicated device path uses — but on n_loc rows — and
# finishes with ONE psum. Row-local math (per-row norms, per-row int8
# quantization) is exact on shards because row sharding keeps P intact
# per row; only the final cross-shard sum reorders reductions.


def _recv_local(received, axes, n):
    row0, n_loc = C.shard_row_slice(axes, n)
    return jax.lax.dynamic_slice_in_dim(received, row0, n_loc)


def _part_sum(g_loc, received, *, axes, n):
    from repro.kernels.agg import masked_sum_dot
    return C.psum_all(masked_sum_dot(g_loc, _recv_local(received, axes, n)),
                      axes)


def _part_mean(g_loc, received, *, axes, n):
    s = _part_sum(g_loc, received, axes=axes, n=n)
    return s / jnp.maximum(jnp.sum(received.astype(jnp.float32)), 1.0)


def _part_cge(g_loc, received, *, f, axes, n):
    from repro.kernels.agg import row_norms
    # (n,) norm vector all-reduced bit-exactly -> every shard derives the
    # identical keep-set (the keep-set math exists once, same as cge_psum)
    norms = C.ledger_all_rows(row_norms(g_loc), axes, n)
    keep = gradagg.cge_mask_from_norms(norms, received, f)
    from repro.kernels.agg import masked_sum_dot
    return C.psum_all(masked_sum_dot(g_loc, _recv_local(keep, axes, n)),
                      axes)


def _part_quantized(g_loc, received, *, axes, n):
    from repro.kernels import ops as K
    q, scale = gradagg.quantize_int8_parts(g_loc.astype(jnp.float32))
    return C.psum_all(
        K.dequant_accum(q, scale[:, 0], _recv_local(received, axes, n)),
        axes)


_PARTIAL_FORMS: Dict[str, Callable] = {
    "sum": _part_sum,
    "mean": _part_mean,
    "cge": _part_cge,
    "quantized": _part_quantized,
    # trimmed_mean: intentionally absent -> gather fallback
}


# ---------------------------------------------------------------------------
# registry


_REGISTRY: Dict[str, AggregationRule] = {}


def register_rule(rule: AggregationRule) -> AggregationRule:
    _REGISTRY[rule.name] = rule
    return rule


def get_rule(name: str) -> AggregationRule:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown aggregation rule {name!r}; "
            f"registered: {sorted(_REGISTRY)}") from None


def rule_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_rule(AggregationRule(
    name="sum", reference=gradagg.agg_sum,
    collective=C.masked_psum, spmd=_spmd_sum, device=_dev_sum,
    doc="Algorithm 1 eq. (3): sum over S^t (one bulk psum)."))

register_rule(AggregationRule(
    name="mean", reference=gradagg.agg_mean,
    collective=C.masked_psum, spmd=_spmd_mean, normalized=True,
    device=_dev_mean,
    doc="sum / |S^t| — the LR-rescaled D-SGD variant."))

register_rule(AggregationRule(
    name="cge", reference=gradagg.agg_cge,
    collective=C.cge_psum, spmd=_spmd_cge, needs_f=True,
    device=_dev_cge,
    doc="CGE filter eq. (18): sum of the m-f smallest-norm gradients "
        "(norms all-reduce + masked psum)."))

register_rule(AggregationRule(
    name="trimmed_mean", reference=gradagg.agg_trimmed_mean,
    collective=C.trimmed_mean_all, spmd=_spmd_trimmed, needs_f=True,
    normalized=True, device=_dev_trimmed,
    doc="Coordinate-wise trimmed mean (Yin et al.): full stack gather."))

register_rule(AggregationRule(
    name="quantized", reference=gradagg.agg_quantized,
    collective=C.quantized_psum, spmd=_spmd_quantized, wire_bytes=1,
    device=_dev_quantized,
    doc="int8 error-feedback compressed sum (1 byte/param uploads)."))


# ---------------------------------------------------------------------------
# TrainConfig.mode -> rule resolution (SPMD step factories)

_MODE_RULES = {
    "masked": "sum",      # Algorithm 1 via loss-weight masking (fast path)
    "sync": "sum",
    "cge": "cge",
    "stale": "sum",       # rule (15): ledger substitution, then masked sum
    "trimmed": "trimmed_mean",
    "quantized": "quantized",
}


def resolve_mode(mode: str) -> AggregationRule:
    try:
        return get_rule(_MODE_RULES[mode])
    except KeyError:
        raise ValueError(
            f"unknown train mode {mode!r}; known: {sorted(_MODE_RULES)}"
        ) from None
