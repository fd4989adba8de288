"""Model-coupled serving loop: continuous batching over the paged cache.

One engine owns one jitted decode program of fixed batch ``num_slots``;
every wall-clock step it (1) admits waiting requests into free slots
(batched prefill per prompt-length group — the first generated token
comes from the prefill logits, never from a second full forward), (2)
runs a **decode superstep**: K decode iterations inside one jitted
``lax.scan`` whose carry holds the pending tokens, the paged cache and
the per-slot lengths — greedy argmax, KV appends, ``kv_lens`` bumps and
done-masking (idle slots point at the null page) all stay on device, (3)
downloads the K×B emitted tokens in ONE transfer, commits them and
retires finished requests, freeing pages/slots for the next admissions.

The scheduler picks ``K = min(superstep_cap, min remaining budgets)``
(budgets are known at admission), so no slot can overrun its budget
in-scan and the min-budget slot finishes exactly at the superstep
boundary — the host is consulted only there (DESIGN.md §12). Straggler
tolerance at the dispatch layer can't hide a synchronous host sync every
token; with supersteps the engine pays O(1/K) host syncs per token
(``stats["host_syncs"]``). ``superstep_k=1`` preserves the original
host-driven per-token loop bit-exactly and is the conformance reference,
the same way ``agg_backend="host"`` is for training (DESIGN.md §11).

Greedy (argmax) decoding, matching the rest of the repo's drivers.

Every layer boundary of a step is a host span on the profiler's clock
(``jax.profiler.TraceAnnotation``, names ``repro.serve.*``, request ids
as span stats): ``submit``; ``step``, holding ``admit`` (which holds
``schedule``, ``prefill``, ``page_write`` and ``suffix``), ``schedule``
for the preemption choice, ``decode`` and ``retire``. With the profiler
off a span costs about a microsecond; nothing else here keeps time.

MoE is drop-free (``repro.models.moe``), so a request's tokens never
depend on whatever else shares its decode batch — continuous batching
must be batch-composition-invariant.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ArchConfig
from repro.dist.sharding import MeshRules, cache_specs, serve_tp
from repro.models.model import apply_model
from repro.serve.kv_cache import (PagedCacheConfig, PagedKVCache,
                                  pages_needed)
from repro.serve.scheduler import Request, RequestState, Scheduler


class SnapshotInFlightError(RuntimeError):
    """``ServeEngine.snapshot()`` called while requests are in flight.

    The snapshot contract is idle-only (DESIGN.md §16): an image taken
    mid-decode would capture KV pools whose pages belong to requests the
    scheduler still owns — restoring it would resurrect half-decoded
    state the fleet already requeued elsewhere. The wall-clock rejoin
    path hits this race for real (a supervisor restarting a replica the
    moment the monitor declares it dead, while a straggling copy still
    decodes), so the guard is typed: callers drain or ``crash()`` first,
    and nothing about the engine is mutated by the refused call.
    Subclasses RuntimeError so pre-existing handlers keep working.

    Attributes: ``n_active`` / ``n_waiting`` — the in-flight population
    that made the snapshot unsafe."""

    def __init__(self, n_active: int, n_waiting: int):
        super().__init__(
            f"snapshot requires a drained engine ({n_active} active, "
            f"{n_waiting} waiting) — crash() or drain first")
        self.n_active = int(n_active)
        self.n_waiting = int(n_waiting)


class ServeEngine:
    def __init__(self, params, cfg: ArchConfig,
                 ccfg: Optional[PagedCacheConfig] = None,
                 superstep_k: int = 8, prefix_cache: str = "off",
                 policy: str = "fifo", mesh=None,
                 rules: Optional[MeshRules] = None):
        if superstep_k < 1:
            raise ValueError(f"need superstep_k >= 1, got {superstep_k}")
        if prefix_cache not in ("off", "on"):
            raise ValueError(f"prefix_cache must be off|on, "
                             f"got {prefix_cache!r}")
        if prefix_cache == "on" and any(k != "attn"
                                        for k in cfg.layer_pattern):
            # only attention KV is paged; a recurrent layer's state is not
            # content-addressable per token chunk, so prefix reuse cannot
            # reconstruct it
            raise ValueError(
                "prefix_cache requires an attention-only layer pattern")
        # serving TP (DESIGN.md §14): with a mesh, params stay *replicated*
        # — the exactness boundary is the paged attention kernel alone, so
        # every matmul outside it keeps the single-device reduction order
        # and the token stream matches the replicated engine bit for bit.
        if rules is not None and mesh is None:
            raise ValueError(
                "rules= provided without mesh= — pass the mesh the rules "
                "describe, or drop rules for the replicated engine")
        self.mesh = mesh
        if mesh is not None and rules is None:
            rules = MeshRules(
                fsdp_axes=(),
                axis_sizes={a: mesh.shape[a] for a in mesh.axis_names})
        self.rules = rules
        if mesh is not None:
            params = jax.device_put(
                params, NamedSharding(mesh, PartitionSpec()))
        self.params = params
        self.cfg = cfg
        self.superstep_k = int(superstep_k)
        self.prefix_cache = prefix_cache
        self.ccfg = ccfg or PagedCacheConfig()
        self.kv = PagedKVCache(cfg, self.ccfg,
                               enable_prefix=(prefix_cache == "on"),
                               mesh=mesh, rules=self.rules)
        self.sched = Scheduler(self.ccfg, policy=policy)
        # host_syncs counts device->host materializations (one per prefill
        # group + one per superstep boundary): the drained-workload figure
        # of merit is host_syncs / tokens ~ O(1/K) (DESIGN.md §12)
        self.stats = {"prefill_calls": 0, "decode_steps": 0,
                      "supersteps": 0, "host_syncs": 0,
                      "admitted": 0, "retired": 0, "aborted": 0,
                      "table_uploads": 0,
                      "cache_hit_tokens": 0, "cache_miss_tokens": 0,
                      "suffix_steps": 0, "preemptions": 0, "resumed": 0,
                      "swapped_pages": 0, "cow_forks": 0,
                      "prefix_evictions": 0}
        self._next_rid = 0

        # _tp() installs the ambient (mesh, tp_axes) context *around the
        # closure bodies below* — tracing happens inside it, so the paged
        # decode branches in models/attention.py route through the
        # per-shard kernel wrappers. _pin() constrains the carried cache
        # back to its cache_specs placement so the pools stay kv-head-
        # sharded across scan iterations instead of being gathered.
        if mesh is not None:
            tp_ax = self.rules.tp_axes
            specs = cache_specs(self.rules, self.kv.cache,
                                n_query_heads=self.cfg.n_heads)
            _, treedef = jax.tree_util.tree_flatten(self.kv.cache)
            cache_sh = jax.tree_util.tree_unflatten(
                treedef, [NamedSharding(mesh, s)
                          for s in treedef.flatten_up_to(specs)])

            def _tp():
                return serve_tp(mesh, tp_ax)

            def _pin(cch):
                return jax.lax.with_sharding_constraint(cch, cache_sh)
        else:
            def _tp():
                return contextlib.nullcontext()

            def _pin(cch):
                return cch

        def _prefill(params, tokens):
            # under a mesh the context keeps prefill off the Pallas flash
            # kernel, which GSPMD cannot partition
            with _tp():
                logits, _, cache = apply_model(params, tokens, cfg,
                                               mode="prefill",
                                               remat_policy="none")
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        def _decode(params, tokens, cache, lens, tbl):
            with _tp():
                logits, _, new_cache = apply_model(
                    params, tokens, cfg, mode="decode", cache=cache,
                    cache_index=lens, page_table=tbl, remat_policy="none")
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return nxt, _pin(new_cache)

        def _superstep(params, pending, cache, lens, tbl, remaining, *,
                       k: int):
            """K decode iterations fully on device (one lax.scan).

            Carry = (pending (B,), cache pytree, lens (B,), remaining
            (B,)). Each iteration feeds the pending token at per-slot
            position ``lens``, argmaxes the logits, bumps the lengths of
            active slots (remaining > 0) in-scan and holds everything
            else fixed — idle slots keep writing their masked garbage
            into the null page, exactly as in the per-token path. Emits
            the (K, B) generated tokens; the host reads them once.
            """
            def body(carry, _):
                pend, cch, ln, rem = carry
                active = (rem > 0).astype(jnp.int32)
                with _tp():
                    logits, _, cch = apply_model(
                        params, pend[:, None], cfg, mode="decode",
                        cache=cch, cache_index=ln, page_table=tbl,
                        remat_policy="none")
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                nxt = jnp.where(active == 1, nxt, pend)
                return (nxt, _pin(cch), ln + active, rem - active), nxt

            (pending, cache, lens, _), toks = jax.lax.scan(
                body, (pending, cache, lens, remaining), None, length=k)
            return toks, cache, lens

        self._prefill = jax.jit(_prefill)
        # donate the cache so the single-token page append updates the
        # pools in place instead of copying every pool every step
        self._decode = jax.jit(_decode, donate_argnums=(2,))
        # one compiled program per distinct K (bounded by superstep_k)
        self._superstep = jax.jit(_superstep, static_argnames=("k",),
                                  donate_argnums=(2,))
        # prompts admit in groups of one padded length each; padding to a
        # page multiple bounds the jit shape set to max_pages_per_seq
        # buckets. Right-padding is invisible to *causal attention*
        # prefixes, but a recurrent (SSM/RWKV) state would absorb the pad
        # garbage — those archs bucket by exact length instead.
        self._pad_buckets = all(k == "attn"
                                for k in self.cfg.layer_pattern)

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, priority: int = 0,
               deadline: Optional[float] = None) -> int:
        rid = self._next_rid
        with TraceAnnotation("repro.serve.submit", rid=rid):
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            if prompt.size == 0:
                raise ValueError("empty prompt")
            if max_new_tokens < 1:
                raise ValueError("need max_new_tokens >= 1")
            self._next_rid += 1
            # an over-capacity request lands in sched.rejected (with
            # reason) instead of raising — one bad request must not kill
            # the stream
            self.sched.submit(Request(rid=rid, prompt=prompt,
                                      max_new_tokens=max_new_tokens,
                                      priority=priority, deadline=deadline))
        return rid

    @property
    def rejected(self):
        """(Request, reason) pairs refused at submit (over-capacity)."""
        return self.sched.rejected

    # ------------------------------------------------------------------
    def _need_pages(self, st: RequestState) -> int:
        """Page bill for the admission gate: a prefix-cache hit only pays
        for its uncached pages (plus a COW copy); swaps and cold requests
        pay the full conservative reservation."""
        if st.swap is None and self.kv.prefix is not None:
            return self.kv.prefix.plan(st.req.prompt,
                                       st.req.total_len).need_pages
        return pages_needed(st.req.total_len, self.ccfg.page_size)

    def _admit(self) -> None:
        with TraceAnnotation("repro.serve.admit") as span:
            with TraceAnnotation("repro.serve.schedule"):
                admitted = self.sched.admissions(
                    self.kv.available_pages, need_pages=self._need_pages)
            span.set_metadata(n=len(admitted))
            if not admitted:
                if not self.sched.active and self.sched.waiting:
                    raise RuntimeError(
                        "head request can never be admitted (page pool "
                        "too small even when idle)")
                return
            fresh = [st for st in admitted if st.swap is None]
            resumed = [st for st in admitted if st.swap is not None]
            for st in resumed:
                self._resume(st)
            self.stats["admitted"] += len(fresh)
            if fresh:
                if self.kv.prefix is None:
                    self._admit_grouped(fresh)
                else:
                    for st in fresh:
                        self._admit_prefix(st)
            # keep the counter live for prefill-only workloads too —
            # step() may never reach a decode that would otherwise
            # refresh it
            self.stats["table_uploads"] = self.kv.table_uploads

    def _admit_grouped(self, admitted: List[RequestState]) -> None:
        """The conformance admission path (prefix_cache="off"): batched
        prefill per padded prompt-length group, verbatim pre-§13."""
        ps = self.ccfg.page_size
        groups: Dict[int, List[RequestState]] = {}
        for st in admitted:
            s0 = st.req.prompt_len
            bucket = -(-s0 // ps) * ps if self._pad_buckets else s0
            groups.setdefault(bucket, []).append(st)
        for bucket, group in sorted(groups.items()):
            with TraceAnnotation(
                    "repro.serve.prefill",
                    rids=";".join(str(st.req.rid) for st in group),
                    tokens=bucket * len(group)):
                prompts = np.zeros((len(group), bucket), np.int32)
                for i, st in enumerate(group):
                    prompts[i, : st.req.prompt_len] = st.req.prompt
                first, cache = self._prefill(self.params,
                                             jnp.asarray(prompts))
                self.stats["prefill_calls"] += 1
                first = np.asarray(first)
                self.stats["host_syncs"] += 1
            for i, st in enumerate(group):
                s0 = st.req.prompt_len
                with TraceAnnotation("repro.serve.page_write",
                                     rid=st.req.rid):
                    one = jax.tree.map(lambda l, i=i: l[:, i:i + 1], cache)
                    # admit() scatters only the first s0 tokens of each
                    # page, so the causal-invisible right-pad never
                    # enters the cache
                    self.kv.admit(st.slot, one, s0, st.req.total_len)
                self._first_token(st, int(first[i, s0 - 1]))

    def _admit_prefix(self, st: RequestState) -> None:
        """Prefix-cache admission: share the resident prompt prefix,
        prefill only the uncached suffix, then index this request's own
        blocks for the next arrival. Token streams stay identical to cold
        prefill — the decode program recomputes exactly the KV and logits
        prefill would have produced at those positions."""
        req = st.req
        plan = self.kv.prefix.plan(req.prompt, req.total_len)
        if plan.cached_len == 0:
            # cold miss: single-request prefill, then index its blocks
            ps = self.ccfg.page_size
            if pages_needed(req.total_len, ps) > self.kv.available_pages:
                self.sched.requeue(st)   # gate-time plan went stale
                return
            s0 = req.prompt_len
            bucket = -(-s0 // ps) * ps if self._pad_buckets else s0
            with TraceAnnotation("repro.serve.prefill", rids=str(req.rid),
                                 tokens=bucket):
                prompts = np.zeros((1, bucket), np.int32)
                prompts[0, :s0] = req.prompt
                first, cache = self._prefill(self.params,
                                             jnp.asarray(prompts))
                self.stats["prefill_calls"] += 1
                first = np.asarray(first)
                self.stats["host_syncs"] += 1
            with TraceAnnotation("repro.serve.page_write", rid=req.rid):
                one = jax.tree.map(lambda l: l[:, 0:1], cache)
                self.kv.admit(st.slot, one, s0, req.total_len)
                self.kv.register_prompt(st.slot, req.prompt)
            self.stats["cache_miss_tokens"] += s0
            self._first_token(st, int(first[0, s0 - 1]))
            return
        try:
            with TraceAnnotation("repro.serve.page_write", rid=req.rid):
                self.kv.admit_shared(st.slot, plan, req.total_len)
        except MemoryError:
            self.sched.requeue(st)       # gate-time plan went stale
            return
        self.stats["cache_hit_tokens"] += plan.cached_len
        self.stats["cache_miss_tokens"] += req.prompt_len - plan.cached_len
        suffix = req.prompt[plan.cached_len:]
        with TraceAnnotation("repro.serve.suffix", rid=req.rid,
                             tokens=len(suffix)):
            first = self._feed_suffix(st.slot, suffix)
        with TraceAnnotation("repro.serve.page_write", rid=req.rid):
            self.kv.register_prompt(st.slot, req.prompt)
        self._first_token(st, first)

    def _feed_suffix(self, slot: int, suffix) -> int:
        """Prefill the uncached suffix through the decode program, one
        token per iteration at position ``kv_lens[slot]``.

        The page table is masked to this slot (other rows point at the
        null page with length 0) so co-resident requests are untouched,
        and the program is the same jitted ``_decode`` the steady loop
        runs — no new compilation shapes. The final suffix token's logits
        give the first generated token, the same position cold prefill
        reads them from.
        """
        B = self.ccfg.num_slots
        tbl = np.zeros_like(self.kv.page_table)
        tbl[slot] = self.kv.page_table[slot]
        tbl_dev = jnp.asarray(tbl)
        nxt = None
        for t in np.asarray(suffix, np.int32):
            toks = np.zeros((B, 1), np.int32)
            toks[slot, 0] = int(t)
            lens = np.zeros((B,), np.int32)
            lens[slot] = self.kv.kv_lens[slot]
            nxt, new_cache = self._decode(
                self.params, jnp.asarray(toks), self.kv.cache,
                jnp.asarray(lens), tbl_dev)
            self.kv.update(new_cache)
            self.kv.note_host_len(slot, int(self.kv.kv_lens[slot]) + 1)
            self.stats["suffix_steps"] += 1
        self.stats["host_syncs"] += 1
        return int(np.asarray(nxt)[slot])

    def _first_token(self, st: RequestState, tok: int) -> None:
        st.pending = tok
        st.generated.append(tok)
        if st.done:             # max_new_tokens == 1: no decode needed
            self._retire(st.slot)

    def _resume(self, st: RequestState) -> None:
        """Swap a preempted request back in; its pending token and
        generated stream survived on the host, so decode continues
        exactly where it stopped."""
        try:
            with TraceAnnotation("repro.serve.page_write", rid=st.req.rid):
                self.kv.swap_in(st.slot, st.swap, st.req.prompt,
                                st.req.total_len)
        except MemoryError:
            self.sched.requeue(st)
            return
        st.swap = None
        self.stats["resumed"] += 1

    def _preempt(self) -> None:
        """SLA rescue: while a strictly higher-priority request starves
        in the queue, swap the worst-scored active request's KV to host
        and hand its slot/pages over (bounded by the active count — each
        iteration preempts one victim, so no livelock)."""
        guard = len(self.sched.active)
        while guard > 0:
            with TraceAnnotation("repro.serve.schedule"):
                slot = self.sched.preemption_victim()
            if slot is None:
                return
            st = self.sched.active[slot]
            st.swap = self.kv.swap_out(slot)
            self.sched.preempt(slot)
            self.stats["preemptions"] += 1
            self._admit()
            guard -= 1

    def _retire(self, slot: int) -> None:
        self.kv.evict(slot)
        self.sched.retire(slot)
        self.stats["retired"] += 1

    # -- fault surface (DESIGN.md §15) ---------------------------------
    def abort(self, slot: int) -> RequestState:
        """Kill one in-flight request: its pages are freed and its state
        lands in ``sched.aborted`` — the generated-so-far tokens are
        LOST, never answered. This is the mid-decode crash primitive the
        e2e harness (repro.sim.e2e) drives; nothing else in the engine
        may observe the difference (co-resident slots keep decoding the
        same stream — regression-pinned in tests/test_e2e_faults.py)."""
        st = self.sched.active[slot]
        self.kv.evict(slot)
        self.sched.abort(slot)
        self.stats["aborted"] += 1
        return st

    def crash(self) -> List[int]:
        """Whole-replica crash: every active request is aborted and the
        waiting queue is dropped (a restarted server has neither). The
        engine itself stays usable — params and the (now empty) page pool
        survive, exactly like a process restart on warm weights. Returns
        the rids whose work was lost."""
        lost = [self.abort(slot).req.rid
                for slot in list(self.sched.active)]
        dropped = self.sched.drop_waiting()
        self.stats["aborted"] += len(dropped)
        return lost + [st.req.rid for st in dropped]

    def reset_prefix_cache(self) -> None:
        """Drop every index entry and reclaim parked pages (benchmarks:
        cold-cache timing with a warm jit)."""
        if self.kv.prefix is not None:
            self.kv.prefix.clear()

    # -- checkpoint-based restart (DESIGN.md §16) ----------------------
    def snapshot(self) -> Dict[str, np.ndarray]:
        """Restartable host image of the engine's data plane: every KV
        pool leaf plus the page table / lengths / monotone rid counter,
        flat-keyed for ``repro.checkpoint.Checkpointer``. Idle-only by
        contract — in-flight requests are never checkpointable (a
        crashed replica loses them via :meth:`crash` and the fleet
        controller requeues; DESIGN.md §16), so the image is exactly
        what a restarted process can honestly restore."""
        if not self.sched.idle:
            raise SnapshotInFlightError(len(self.sched.active),
                                        len(self.sched.waiting))
        flat: Dict[str, np.ndarray] = {
            "page_table": self.kv.page_table.copy(),
            "kv_lens": self.kv.kv_lens.copy(),
            "next_rid": np.asarray(self._next_rid, np.int64),
        }
        for pos, blk in enumerate(self.kv.cache):
            for part in ("mixer", "ffn"):
                for name, leaf in blk[part].items():
                    flat[f"kv/{pos}/{part}/{name}"] = np.asarray(leaf)
        return flat

    def restart(self, image: Optional[Dict[str, np.ndarray]] = None
                ) -> None:
        """Process-restart twin: throw away the scheduler and the paged
        cache, rebuild them fresh, and (with ``image``) reload the KV
        pools from a :meth:`snapshot` taken earlier — the checkpoint-
        based rejoin path of the fleet controller. The jitted programs
        survive (same shapes), the rid counter stays monotone across
        the restart (max of live and image — a rejoined replica must
        never reuse a rid the fleet already tracked), and a prefix
        cache restarts cold (its hash index is not part of the image)."""
        self.kv = PagedKVCache(self.cfg, self.ccfg,
                               enable_prefix=(self.prefix_cache == "on"),
                               mesh=self.mesh, rules=self.rules)
        self.sched = Scheduler(self.ccfg, policy=self.sched.policy)
        if image is not None:
            blocks = list(self.kv.cache)
            for pos, kind in enumerate(self.cfg.layer_pattern):
                blk = dict(blocks[pos])
                for part in ("mixer", "ffn"):
                    loaded = {}
                    for name, leaf in blk[part].items():
                        arr = jnp.asarray(image[f"kv/{pos}/{part}/{name}"],
                                          leaf.dtype)
                        if self.mesh is not None:
                            arr = jax.device_put(arr, leaf.sharding)
                        loaded[name] = arr
                    blk[part] = loaded
                blocks[pos] = blk
            self.kv.cache = tuple(blocks)
            self.kv.page_table = np.asarray(image["page_table"],
                                            np.int32).copy()
            self.kv.kv_lens = np.asarray(image["kv_lens"], np.int32).copy()
            self.kv._tables_dirty = True
            self._next_rid = max(self._next_rid, int(image["next_rid"]))
        self.stats["restarts"] = self.stats.get("restarts", 0) + 1

    def step(self) -> None:
        """One serving step: admit -> preempt (sla) -> decode superstep
        -> commit/retire.

        ``superstep_k == 1`` runs the original host-driven per-token loop
        verbatim (the bit-exact conformance path); ``superstep_k > 1``
        runs K budget-bounded decode iterations in one jitted scan and
        talks to the host once at the boundary.
        """
        with TraceAnnotation("repro.serve.step"):
            self.sched.clock += 1.0
            self._admit()
            self._preempt()
            self.stats["cow_forks"] = self.kv.cow_forks
            self.stats["swapped_pages"] = self.kv.swapped_pages
            if self.kv.prefix is not None:
                self.stats["prefix_evictions"] = self.kv.prefix.evictions
            if not self.sched.active:
                return
            if self.superstep_k == 1:
                out = self._step_single()
            else:
                k = self.sched.superstep_k(self.superstep_k)
                if k == 0:  # pragma: no cover - active slots have budget
                    return
                out = self._superstep_once(k)
            self._append_and_retire(out)

    def _superstep_once(self, k: int) -> np.ndarray:
        """K decode iterations in one dispatch; returns the (K, B) tokens
        after the one boundary sync."""
        active = list(self.sched.active)
        with TraceAnnotation("repro.serve.decode", k=k, active=len(active)):
            toks = np.zeros((self.ccfg.num_slots,), np.int32)
            remaining = np.zeros((self.ccfg.num_slots,), np.int32)
            for slot, st in self.sched.active.items():
                toks[slot] = st.pending
                remaining[slot] = st.req.max_new_tokens - len(st.generated)
            # page tables / lengths are cached device-side behind a dirty
            # flag — a decode-only superstep re-uses them; the lens carry
            # advances in-scan and is adopted back via commit_tokens
            out, new_cache, new_lens = self._superstep(
                self.params, jnp.asarray(toks), self.kv.cache,
                self.kv.kv_lens_dev, self.kv.page_table_dev,
                jnp.asarray(remaining), k=k)
            self.stats["decode_steps"] += k
            self.stats["supersteps"] += 1
            self.kv.update(new_cache)
            self.kv.commit_tokens(active, k, new_lens)
            out = np.asarray(out)            # (K, B): the one boundary sync
            self.stats["host_syncs"] += 1
            self.stats["table_uploads"] = self.kv.table_uploads
        return out

    def _step_single(self) -> np.ndarray:
        """The original one-token host loop (superstep_k=1 conformance);
        returns the (1, B) tokens."""
        active = list(self.sched.active)
        with TraceAnnotation("repro.serve.decode", k=1, active=len(active)):
            toks = np.zeros((self.ccfg.num_slots, 1), np.int32)
            for slot, st in self.sched.active.items():
                toks[slot, 0] = st.pending
            # page tables / lengths are cached device-side behind a dirty
            # flag — a decode-only step re-uses them instead of
            # re-uploading
            nxt, new_cache = self._decode(
                self.params, jnp.asarray(toks), self.kv.cache,
                self.kv.kv_lens_dev, self.kv.page_table_dev)
            self.stats["decode_steps"] += 1
            self.stats["supersteps"] += 1
            self.kv.update(new_cache)
            self.kv.commit_token(active)  # each slot's pending token landed
            nxt = np.asarray(nxt)
            self.stats["host_syncs"] += 1
            self.stats["table_uploads"] = self.kv.table_uploads
        return nxt[None]

    def _append_and_retire(self, out: np.ndarray) -> None:
        """Append each active slot's column of ``out`` (K, B) to its
        stream and retire the requests that are done."""
        with TraceAnnotation("repro.serve.retire") as span:
            n = 0
            for slot in list(self.sched.active):
                st = self.sched.active[slot]
                st.generated.extend(int(t) for t in out[:, slot])
                st.pending = int(out[-1, slot])
                if st.done:
                    self._retire(slot)
                    n += 1
            span.set_metadata(n=n)

    # ------------------------------------------------------------------
    def run(self, max_steps: int = 100_000) -> Dict[int, np.ndarray]:
        """Drive to completion; returns rid -> generated tokens."""
        steps = 0
        while not self.sched.idle:
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("serving loop did not drain")
        return {rid: np.asarray(st.generated, np.int32)
                for rid, st in self.sched.finished.items()}
