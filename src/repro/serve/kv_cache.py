"""Paged KV/SSM cache for serving (DESIGN.md §9).

The pad-to-max_len decode cache wastes O(num_slots * max_len) HBM on
whatever the *longest possible* request needs; the paged cache stores KV
in fixed-size physical pages and gives every admitted request a page
table, so memory scales with the tokens actually resident. Attention KV
(and MLA's latent cache) is paged along the sequence axis; recurrent
(SSM/RWKV) state has no sequence axis and is slot-indexed instead — one
row per serving slot, overwritten on admission.

Layout per pattern position (mirrors ``models.model.init_cache``; the
leading axis is ``n_periods`` so the stack scans):

- GQA:  ``{"k_pages", "v_pages"}: (P, N, n_kv, PS, hd)`` — head-major,
  so one KV head of one page is a contiguous ``(PS, hd)`` tile, the
  block the Pallas decode kernel reads (``kernels/decode_attention.py``)
- MLA:  ``{"ckv_pages": (P, N, PS, rank), "kr_pages": (P, N, PS, rope)}``
- mamba/rwkv: dense slot states, exactly ``init_cache`` with
  ``batch=num_slots``.

Physical page 0 is reserved as the *null page*: idle slots' page tables
point at it, so their (masked, garbage) decode writes land somewhere
harmless and never clobber a live request. The allocator therefore hands
out pages 1..N-1.

Logical page p of the sequence in slot s lives in physical page
``page_table[s, p]`` — shared by every layer (each layer has its own
pools, all addressed by the one table, vLLM-style).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.model import init_cache

PAGED_SUFFIX = "_pages"
HEAD_MAJOR = ("k_pages", "v_pages")     # (P, N, n_kv, PS, hd) pools


def _swap_page_rows(name: str, x):
    """Page rows ``(P, n, PS, ...)`` token-major <-> the pool's layout
    (for a head-major pool the transpose is its own inverse)."""
    return x.transpose(0, 1, 3, 2, 4) if name in HEAD_MAJOR else x


@dataclasses.dataclass(frozen=True)
class PagedCacheConfig:
    num_slots: int = 4            # concurrent decode batch size
    page_size: int = 16           # tokens per page
    num_pages: int = 64           # physical pages incl. the null page 0
    max_pages_per_seq: int = 16   # page-table width

    @property
    def max_seq_len(self) -> int:
        return self.page_size * self.max_pages_per_seq


def pages_needed(total_len: int, page_size: int) -> int:
    return -(-total_len // page_size)


class PageAllocator:
    """Free-list allocator over physical pages 1..num_pages-1 (page 0 is
    the reserved null page). Alloc/free are O(n) and checked: a page is
    never handed out twice, never freed twice, never freed while free.

    Pages are refcounted for the prefix cache (DESIGN.md §13):
    ``alloc`` hands a page out at refcount 1, ``share`` adds holders,
    ``release`` drops one — a page reaching refcount 0 stays *used*
    (its content may be cached) until someone calls ``free``, which
    refuses while other holders remain (refcount > 1). Without the
    prefix cache every page simply lives at refcount 1, and alloc/free
    behave exactly as before.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need at least one allocatable page + null")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._used: set = set()
        self._ref: Dict[int, int] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return len(self._used)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(
                f"page pool exhausted: want {n}, have {len(self._free)}")
        pages = [self._free.pop() for _ in range(n)]
        self._used.update(pages)
        for p in pages:
            self._ref[p] = 1
        return pages

    def share(self, pages: Sequence[int]) -> None:
        """Add one holder per page (a cached refcount-0 page revives)."""
        for p in pages:
            if p not in self._used:
                raise ValueError(f"cannot share unallocated page {p}")
            self._ref[p] += 1

    def release(self, pages: Sequence[int]) -> List[int]:
        """Drop one holder per page; returns the pages that reached
        refcount 0. Those stay *used* — the caller decides whether their
        content is cache-worthy (park) or dead (``free``)."""
        zero: List[int] = []
        for p in pages:
            if p not in self._used:
                raise ValueError(f"cannot release unallocated page {p}")
            if self._ref[p] <= 0:
                raise ValueError(f"release of unreferenced page {p}")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                zero.append(p)
        return zero

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            if p not in self._used:
                raise ValueError(f"double free / foreign page {p}")
            if self._ref[p] > 1:
                raise ValueError(
                    f"page {p} still shared (refcount {self._ref[p]})")
            self._used.remove(p)
            del self._ref[p]
            self._free.append(p)

    def check_invariants(self) -> bool:
        seen = set(self._free)
        assert len(seen) == len(self._free), "duplicate free pages"
        assert not (seen & self._used), "page both free and used"
        assert 0 not in seen and 0 not in self._used, "null page leaked"
        assert len(seen) + len(self._used) == self.num_pages - 1
        assert set(self._ref) == self._used, "refcounts out of sync"
        assert all(c >= 0 for c in self._ref.values()), "negative refcount"
        return True


@dataclasses.dataclass
class SwapState:
    """Host image of a preempted request's device state (DESIGN.md §13).

    ``leaf_pages`` holds, per attention pattern position and paged leaf
    name, the ``(P, n_pages, PS, ...)`` slice of the pool covering the
    request's *content-bearing* logical pages (``pages_needed(kv_len)``
    of them — the conservatively reserved trailing pages carry nothing
    and are re-allocated fresh on resume). ``slot_rows`` holds the
    recurrent layers' per-slot state rows. Arrays are numpy (host
    memory): a swapped-out request owns zero device pages.
    """
    kv_len: int
    n_pages: int
    leaf_pages: Dict[Any, np.ndarray]
    slot_rows: Dict[Any, np.ndarray]


def _paged_block(cfg: ArchConfig, ccfg: PagedCacheConfig, dt):
    """Paged mixer dict for one attention pattern position."""
    P, N, PS = cfg.n_periods, ccfg.num_pages, ccfg.page_size

    def z(shape):
        return jnp.zeros(shape, dt)

    if cfg.attention == "mla":
        m = cfg.mla
        return {"ckv_pages": z((P, N, PS, m.kv_lora_rank)),
                "kr_pages": z((P, N, PS, m.qk_rope_head_dim))}
    hd = cfg.resolved_head_dim
    return {"k_pages": z((P, N, cfg.n_kv_heads, PS, hd)),
            "v_pages": z((P, N, cfg.n_kv_heads, PS, hd))}


class PagedKVCache:
    """Owns the device cache pytree + the host-side allocator/page table.

    The engine passes ``.cache`` (pytree) / ``.page_table_dev`` /
    ``.kv_lens_dev`` into the jitted decode step and stores the returned
    pytree back via :meth:`update`; admission/eviction mutate the host
    bookkeeping and scatter/clear device pages.
    """

    def __init__(self, cfg: ArchConfig, ccfg: PagedCacheConfig,
                 enable_prefix: bool = False, mesh=None, rules=None):
        if cfg.encoder_decoder:
            raise NotImplementedError(
                "paged serving supports decoder-only archs")
        self.cfg = cfg
        self.ccfg = ccfg
        self.alloc = PageAllocator(ccfg.num_pages)
        self.prefix = None
        if enable_prefix:
            from repro.serve.prefix import PrefixIndex
            self.prefix = PrefixIndex(self.alloc, ccfg.page_size)
        self.cow_forks = 0
        self.swapped_pages = 0
        S = ccfg.num_slots
        self.page_table = np.zeros((S, ccfg.max_pages_per_seq), np.int32)
        self.kv_lens = np.zeros((S,), np.int32)
        self._slot_pages: Dict[int, List[int]] = {}
        # device mirrors of the host tables, refreshed only when an
        # admission/eviction dirties them (decode-only steps bump the
        # lengths on device instead of re-uploading — see commit_token)
        self._tables_dirty = True
        self._tbl_dev: Optional[jnp.ndarray] = None
        self._lens_dev: Optional[jnp.ndarray] = None
        self._active_dev: Optional[jnp.ndarray] = None
        self.table_uploads = 0        # perf counter (tests/benchmarks)
        dt = jnp.dtype(cfg.compute_dtype)

        # recurrent layers come straight from init_cache at batch=num_slots;
        # attention layers swap the (B, max_len) KV for page pools
        dense = init_cache(cfg, S, ccfg.page_size)  # seq extent unused
        blocks = []
        for pos, kind in enumerate(cfg.layer_pattern):
            if kind == "attn":
                blocks.append({"mixer": _paged_block(cfg, ccfg, dt),
                               "ffn": {}})
            else:
                blocks.append(dense[pos])
        self.cache = tuple(blocks)

        # serving mesh (DESIGN.md §14): place pool leaves per cache_specs
        # — KV pools sharded over the kv-head dim, MLA latent pools and
        # everything else replicated. mesh=None (the default) leaves the
        # cache byte-identical to the single-device layout.
        if rules is not None and mesh is None:
            raise ValueError(
                "rules= provided without mesh= — pass the mesh the rules "
                "describe, or drop rules for the replicated cache")
        self.mesh = mesh
        self.rules = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from repro.dist.sharding import MeshRules, cache_specs
            if rules is None:
                rules = MeshRules(
                    fsdp_axes=(),
                    axis_sizes={a: mesh.shape[a] for a in mesh.axis_names})
            self.rules = rules
            specs = cache_specs(rules, self.cache,
                                n_query_heads=cfg.n_heads)
            leaves, treedef = jax.tree_util.tree_flatten(self.cache)
            spec_leaves = treedef.flatten_up_to(specs)
            self.cache = jax.tree_util.tree_unflatten(treedef, [
                jax.device_put(x, NamedSharding(mesh, s))
                for x, s in zip(leaves, spec_leaves)])

    # -- device views ----------------------------------------------------
    # NB: explicit copies. On the CPU backend ``jnp.asarray(np_array)`` is
    # zero-copy, and the host arrays are mutated in place (commit_token /
    # admit) while a dispatched decode may still be reading the view.
    # The copies are cached behind a dirty flag: the steady decode-only
    # stream re-uses the device tables for every token, and only an
    # admission or eviction pays the host->device upload again.
    def _refresh_device_tables(self) -> None:
        self._tbl_dev = jnp.asarray(self.page_table.copy())
        self._lens_dev = jnp.asarray(self.kv_lens.copy())
        active = np.zeros((self.ccfg.num_slots,), np.int32)
        for s in self._slot_pages:
            active[s] = 1
        self._active_dev = jnp.asarray(active)
        self._tables_dirty = False
        self.table_uploads += 1

    @property
    def page_table_dev(self) -> jnp.ndarray:
        if self._tables_dirty:
            self._refresh_device_tables()
        return self._tbl_dev

    @property
    def kv_lens_dev(self) -> jnp.ndarray:
        if self._tables_dirty:
            self._refresh_device_tables()
        return self._lens_dev

    def update(self, new_cache) -> None:
        self.cache = new_cache

    # -- admission / eviction --------------------------------------------
    @property
    def available_pages(self) -> int:
        """Pages allocatable right now: the free list plus whatever the
        prefix LRU would give back under pressure."""
        n = self.alloc.n_free
        if self.prefix is not None:
            n += self.prefix.reclaimable
        return n

    def can_admit(self, total_len: int) -> bool:
        need = pages_needed(total_len, self.ccfg.page_size)
        return (need <= self.ccfg.max_pages_per_seq
                and need <= self.available_pages)

    def _alloc_pages(self, n: int) -> List[int]:
        """alloc() that spills into the prefix LRU: under pool pressure,
        refcount-0 cached pages are reclaimed (evicting their index
        entries) before the allocator is allowed to fail."""
        if self.prefix is not None and n > self.alloc.n_free:
            self.prefix.reclaim(n - self.alloc.n_free)
        return self.alloc.alloc(n)

    def admit(self, slot: int, prefill_cache, prompt_len: int,
              total_len: int) -> None:
        """Move one request's prefill cache (batch axis of size 1) into
        slot ``slot``, reserving pages for the whole ``total_len``
        (prompt + max new tokens — conservative vLLM-style reservation,
        so decode never blocks mid-flight on an empty pool)."""
        ccfg = self.ccfg
        ps = ccfg.page_size
        need = pages_needed(total_len, ps)
        if need > ccfg.max_pages_per_seq:
            raise ValueError(
                f"request of {total_len} tokens needs {need} pages > "
                f"table width {ccfg.max_pages_per_seq}")
        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} already occupied")
        pages = self._alloc_pages(need)
        self._slot_pages[slot] = pages
        row = np.zeros((ccfg.max_pages_per_seq,), np.int32)
        row[:need] = pages
        self.page_table[slot] = row
        self.kv_lens[slot] = prompt_len
        self._tables_dirty = True

        n_full = prompt_len // ps
        full_idx = np.asarray(pages[:n_full], np.int32)
        blocks = list(self.cache)
        for pos, kind in enumerate(self.cfg.layer_pattern):
            blk = dict(blocks[pos])
            pre = prefill_cache[pos]
            if kind == "attn":
                mix = dict(blk["mixer"])
                for name, pool in mix.items():
                    dense = pre["mixer"][name[: -len(PAGED_SUFFIX)]]
                    # dense: (P, 1, s0, ...). One indexed write covers
                    # every complete page; only the ragged tail (if any)
                    # needs its own partial-page write.
                    if n_full:
                        chunk = dense[:, 0, : n_full * ps]
                        chunk = chunk.reshape(
                            chunk.shape[0], n_full, ps, *chunk.shape[2:])
                        pool = pool.at[:, full_idx].set(
                            _swap_page_rows(name, chunk).astype(pool.dtype))
                    if prompt_len % ps:
                        tail = dense[:, 0, n_full * ps: prompt_len]
                        r = prompt_len % ps
                        if name in HEAD_MAJOR:
                            pool = pool.at[:, pages[n_full], :, :r].set(
                                tail.transpose(0, 2, 1, 3).astype(pool.dtype))
                        else:
                            pool = pool.at[:, pages[n_full], :r].set(
                                tail.astype(pool.dtype))
                    mix[name] = pool
                blk["mixer"] = mix
            else:
                # recurrent state: one row per slot
                blk["mixer"] = {
                    k: v.at[:, slot].set(
                        pre["mixer"][k][:, 0].astype(v.dtype))
                    for k, v in blk["mixer"].items()}
                blk["ffn"] = {
                    k: v.at[:, slot].set(
                        pre["ffn"][k][:, 0].astype(v.dtype))
                    for k, v in blk["ffn"].items()}
            blocks[pos] = blk
        self.cache = tuple(blocks)

    def evict(self, slot: int) -> None:
        """Release the slot's pages and point its table at the null page.

        Without the prefix cache this frees outright (the original
        semantics). With it, each page drops one reference: still-shared
        pages live on under their other holders, and refcount-0 indexed
        pages park in the LRU so the next request with the same prefix
        hits them.
        """
        pages = self._slot_pages.pop(slot, None)
        if pages is None:
            raise ValueError(f"slot {slot} not occupied")
        if self.prefix is not None:
            self.prefix.release(pages)
        else:
            self.alloc.free(pages)
        self.page_table[slot] = 0
        self.kv_lens[slot] = 0
        self._tables_dirty = True

    # -- prefix-cache admission / COW / swap (DESIGN.md §13) -------------
    def admit_shared(self, slot: int, plan, total_len: int) -> None:
        """Admit a request whose prompt prefix is already resident.

        The plan's shared pages become logical pages 0.. of the slot
        (refcount +1 each); private pages cover the rest of the
        conservative ``total_len`` reservation. If the plan says ``cow``
        (full-prompt hit: the engine's re-feed of the last prompt token
        will write into the final shared page), that page is forked to a
        private copy *before* any write can happen. Feasibility is
        checked up front so failure leaves no partial state — the engine
        requeues on MemoryError.
        """
        ccfg = self.ccfg
        need_total = pages_needed(total_len, ccfg.page_size)
        if need_total > ccfg.max_pages_per_seq:
            raise ValueError(
                f"request of {total_len} tokens needs {need_total} pages "
                f"> table width {ccfg.max_pages_per_seq}")
        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} already occupied")
        if plan.need_pages > self.prefix.headroom(plan.shared):
            raise MemoryError(
                f"page pool exhausted: want {plan.need_pages}, "
                f"have {self.prefix.headroom(plan.shared)}")
        self.prefix.acquire(plan.shared)
        shared = list(plan.shared)
        priv = self._alloc_pages(plan.need_pages)
        if plan.cow:
            copy = priv[0]
            self._copy_page(shared[-1], copy)
            self.prefix.release([shared[-1]])    # drop our pin on the orig
            shared[-1] = copy
            priv = priv[1:]
            self.cow_forks += 1
        pages = shared + priv
        assert len(pages) == need_total
        self._slot_pages[slot] = pages
        row = np.zeros((ccfg.max_pages_per_seq,), np.int32)
        row[:need_total] = pages
        self.page_table[slot] = row
        self.kv_lens[slot] = plan.cached_len
        self._tables_dirty = True

    def _copy_page(self, src: int, dst: int) -> None:
        """COW fork: copy physical page ``src`` to ``dst`` across every
        attention leaf of every layer (one page-row copy per pool)."""
        blocks = list(self.cache)
        for pos, kind in enumerate(self.cfg.layer_pattern):
            if kind != "attn":
                continue
            blk = dict(blocks[pos])
            mix = dict(blk["mixer"])
            for name, pool in mix.items():
                mix[name] = pool.at[:, dst].set(pool[:, src])
            blk["mixer"] = mix
            blocks[pos] = blk
        self.cache = tuple(blocks)

    def register_prompt(self, slot: int, prompt) -> int:
        """Index the slot's now-resident prompt blocks for future hits.
        Call after the prompt KV is fully written (post prefill / suffix
        feed). No-op without the prefix cache."""
        if self.prefix is None:
            return 0
        return self.prefix.register(prompt, self._slot_pages[slot])

    def note_host_len(self, slot: int, kv_len: int) -> None:
        """Host-side length bump during the suffix feed; device mirrors
        refresh lazily on next access."""
        self.kv_lens[slot] = kv_len
        self._tables_dirty = True

    def swap_out(self, slot: int) -> SwapState:
        """Preempt: image the slot's content-bearing pages + recurrent
        rows to host memory, then release every device page. The victim
        afterwards holds zero device pages; its table row points at the
        null page like any idle slot."""
        pages = self._slot_pages.get(slot)
        if pages is None:
            raise ValueError(f"slot {slot} not occupied")
        ps = self.ccfg.page_size
        kv_len = int(self.kv_lens[slot])
        n_pages = pages_needed(max(kv_len, 1), ps)
        idx = np.asarray(pages[:n_pages], np.int32)
        leaf_pages: Dict[Any, np.ndarray] = {}
        slot_rows: Dict[Any, np.ndarray] = {}
        for pos, kind in enumerate(self.cfg.layer_pattern):
            blk = self.cache[pos]
            if kind == "attn":
                for name, pool in blk["mixer"].items():
                    leaf_pages[(pos, name)] = np.asarray(pool[:, idx])
            else:
                for part in ("mixer", "ffn"):
                    for name, v in blk[part].items():
                        slot_rows[(pos, part, name)] = np.asarray(v[:, slot])
        if self.prefix is not None:
            self.prefix.release(pages)
        else:
            self.alloc.free(pages)
        del self._slot_pages[slot]
        self.page_table[slot] = 0
        self.kv_lens[slot] = 0
        self._tables_dirty = True
        self.swapped_pages += n_pages
        return SwapState(kv_len, n_pages, leaf_pages, slot_rows)

    def swap_in(self, slot: int, swap: SwapState, prompt,
                total_len: int) -> int:
        """Resume a preempted request into ``slot``.

        Full prompt blocks still resident in the prefix index are
        re-*shared* instead of re-uploaded (the hash chain guarantees
        content equality); everything else uploads from the host image
        in one indexed write per leaf. Returns the number of re-shared
        pages. Feasibility-checked up front; MemoryError leaves no
        partial state.
        """
        ccfg = self.ccfg
        ps = ccfg.page_size
        need_total = pages_needed(total_len, ps)
        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} already occupied")
        matched: List[int] = []
        if self.prefix is not None:
            from repro.serve.prefix import chunk_hashes
            full, _ = chunk_hashes(prompt, ps)
            for h in full:
                p = self.prefix.lookup(h)
                if p is None:
                    break
                matched.append(p)
        priv_need = need_total - len(matched)
        headroom = (self.prefix.headroom(matched)
                    if self.prefix is not None else self.alloc.n_free)
        if priv_need > headroom:
            raise MemoryError(
                f"page pool exhausted: want {priv_need}, have {headroom}")
        if matched:
            self.prefix.acquire(matched)
        priv = self._alloc_pages(priv_need)
        pages = matched + priv
        self._slot_pages[slot] = pages
        row = np.zeros((ccfg.max_pages_per_seq,), np.int32)
        row[:need_total] = pages
        self.page_table[slot] = row
        self.kv_lens[slot] = swap.kv_len
        self._tables_dirty = True

        m = len(matched)
        up_idx = np.asarray(pages[m:swap.n_pages], np.int32)
        blocks = list(self.cache)
        for pos, kind in enumerate(self.cfg.layer_pattern):
            blk = dict(blocks[pos])
            if kind == "attn":
                if m < swap.n_pages:
                    mix = dict(blk["mixer"])
                    for name, pool in mix.items():
                        img = swap.leaf_pages[(pos, name)][:, m:swap.n_pages]
                        mix[name] = pool.at[:, up_idx].set(
                            jnp.asarray(img, pool.dtype))
                    blk["mixer"] = mix
            else:
                for part in ("mixer", "ffn"):
                    blk[part] = {
                        name: v.at[:, slot].set(jnp.asarray(
                            swap.slot_rows[(pos, part, name)], v.dtype))
                        for name, v in blk[part].items()}
            blocks[pos] = blk
        self.cache = tuple(blocks)
        if self.prefix is not None:
            self.prefix.register(prompt, pages)
        return m

    def commit_token(self, slots: Sequence[int]) -> None:
        """Account the token the decode step just wrote for each slot.

        On the steady decode path (no occupancy change since the last
        refresh) the device lengths advance with one device-side add of
        the cached occupancy mask — no host->device re-upload per token.
        """
        for s in slots:
            self.kv_lens[s] += 1
        if not self._tables_dirty and self._lens_dev is not None:
            if set(slots) == set(self._slot_pages):
                self._lens_dev = self._lens_dev + self._active_dev
            else:                     # partial commit: fall back to upload
                self._tables_dirty = True

    def commit_tokens(self, slots: Sequence[int], k: int,
                      lens_dev: Optional[jnp.ndarray] = None) -> None:
        """Superstep commit: ``k`` tokens landed for each slot in
        ``slots`` inside one device-resident decode scan.

        The length bumps already happened *in the scan body* (the lens
        carry advances by the active mask every iteration); ``lens_dev``
        is that scanned-out carry, adopted as the cached device mirror so
        the steady superstep stream costs zero host->device uploads and
        zero device adds outside the jitted scan. The host array stays
        the source of truth for admission/eviction bookkeeping.
        """
        for s in slots:
            self.kv_lens[s] += k
        if (lens_dev is not None and not self._tables_dirty
                and set(slots) == set(self._slot_pages)):
            self._lens_dev = lens_dev
        else:          # occupancy changed under us: re-upload next access
            self._tables_dirty = True

    # -- debug / test helpers --------------------------------------------
    def gather_dense(self, slot: int, pos: int, name: str) -> jnp.ndarray:
        """Contiguous (P, kv_len, ...) view of one slot's paged leaf."""
        ps = self.ccfg.page_size
        ln = int(self.kv_lens[slot])
        pool = self.cache[pos]["mixer"][name]
        tbl = self.page_table[slot]
        out = _swap_page_rows(
            name, pool[:, tbl[: pages_needed(max(ln, 1), ps)]])
        out = out.reshape(out.shape[0], -1, *out.shape[3:])
        return out[:, :ln]
