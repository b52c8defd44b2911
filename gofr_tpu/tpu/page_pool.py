"""Shared device-resident KV page pool (ISSUE 6).

One pool backs every KV byte of the paged serving path: prefill output,
the radix prefix cache, and decode appends all address the same
``(L, num_pages, page, Hkv, Dh)`` arrays (int8 caches add the scale
planes ``(L, num_pages, page, Hkv)``). A pool of another cache form is
given its ``leaf_specs`` (name -> per-token trailing shape, dtype: what
a model module's ``cache_leaves(cfg)`` answers; a latent-attention
module's is one ``(L, num_pages, page, C)`` leaf). The dense engine kept one
``(max_slots, max_len, ...)`` cache whose HBM cost was the *worst-case*
sequence length times the slot count; here HBM is ``num_pages × page``
tokens regardless of ``max_len``, and slot count scales with the actual
token footprint of live traffic (the ragged-paged-attention layout from
PAPERS.md: "Ragged Paged Attention", arxiv 2604.15464; sizing by real
footprint instead of static worst case follows the batch-size/latency
study, arxiv 1812.11731).

A module whose layers keep caches of different kinds (sliding-window
layers beside global ones) answers ``cache_leaves`` **by layer kind**:
``{kind: {"layers": n, "window": tokens or None, "leaves": {name: spec}}}``.
The pool then keeps, a kind, the stacked leaves ``(layers of that kind,
that kind's num_pages, page, ...)``, a free list, refcounts and the
counts; ``leaves`` is ``{kind: {name: array}}`` and a slot has a page
table a kind, because a window kind lets go of the pages that fall
behind its window (``release_behind``) while a global kind keeps the
whole context. One kind is the case above: same leaves, same calls.

A kind may be **per slot** instead (``"per_slot": True`` in its answer: a
state-space layer's recurrent state): what a *slot* holds in a layer
whatever its context, ``leaves`` name -> (shape, dtype). The pool keeps
it as ``(layers, slots, *shape)`` arrays in ``leaves[kind]`` beside the
paged kinds', counted in ``pool_bytes``, with no pages, no free list, no
refcounts and no table (``slot_kinds``, apart from ``kinds``: every loop
over ``kinds`` is a loop over pages). Row ``i`` is slot ``i``'s; its
owner's insert writes the whole row when the slot is claimed and its
decode tick rewrites it, so nothing of a last tenant outlives a claim.
The pool only counts which rows are claimed (``claim_slot`` /
``release_slot``).

Host-side state is a free list plus a per-page refcount, a kind:

- ``alloc`` hands out pages at refcount 1 (the allocating owner — an
  engine slot or a prefix-trie node).
- ``retain``/``release`` move shared ownership: a slot's page that the
  prefix trie adopts is retained once by the trie, so the page outlives
  the slot; release drops a ref and returns the page to the free list at
  zero.
- ``alloc`` takes an optional ``reclaim`` callback (the prefix store's
  LRU leaf eviction): it is invoked while the free list is short and may
  release pages; allocation is all-or-nothing and never blocks.

``num_pages`` doubles as the out-of-bounds sentinel id: scatters use
``mode="drop"`` so a sentinel entry writes nothing, and gathers clamp —
the clamped garbage is always masked by ``cache_len`` downstream.

Device arrays live in ``leaves``; owners that run donating executables
(the engine's decode tick / paged insert) write the returned arrays
back. All dispatches are serialized on the engine loop, so handle churn
is single-writer; JAX dataflow orders in-flight readers before the
donated buffer is reused.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

__all__ = ["PagePool", "HBMBudget", "CacheKind", "SlotKind", "cache_kinds",
           "slot_kinds"]

# the one kind of a pool whose module answers ``cache_leaves`` flat
ONE_KIND = "kv"


def kv_leaf_specs(cfg) -> Dict[str, tuple]:
    """What one token leaves in a layer of a k/v cache: name -> (trailing
    shape, dtype[, fill]). The pool's form when it is given no other."""
    import jax.numpy as jnp

    tail = (cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_int8:
        return {"k": (tail, jnp.int8), "v": (tail, jnp.int8),
                "ks": (tail[:-1], jnp.float32, 1.0),
                "vs": (tail[:-1], jnp.float32, 1.0)}
    return {"k": (tail, cfg.dtype), "v": (tail, cfg.dtype)}


@dataclasses.dataclass
class CacheKind:
    """One kind of layer cache: how many layers keep it, what a token
    leaves in one of them, and how far back they attend (``window``
    tokens; None: the whole context). The host-side ownership of the
    kind's pages lives here too (``PagePool`` fills it)."""
    name: str
    layers: int
    leaves: Dict[str, tuple]
    window: Optional[int] = None
    num_pages: int = 0
    page_bytes: int = 0
    free: List[int] = dataclasses.field(default_factory=list)
    refs: Any = None
    allocs: int = 0            # cumulative, as the pool's
    stalls: int = 0
    freed_behind: int = 0      # pages let go because the window passed them

    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self.free)


@dataclasses.dataclass
class SlotKind:
    """One per-slot kind of layer cache: how many layers keep it and
    what a slot holds in one of them (name -> (shape, dtype)). ``claimed``
    is the set of rows whose slot is claimed (``PagePool`` fills it)."""
    name: str
    layers: int
    leaves: Dict[str, tuple]
    slots: int = 0
    slot_bytes: int = 0
    claimed: set = dataclasses.field(default_factory=set)
    claimed_peak: int = 0
    resets: int = 0            # times the arrays were built anew


def cache_kinds(cfg, leaf_specs: Optional[Dict[str, Any]] = None
                ) -> List[CacheKind]:
    """A module's ``cache_leaves(cfg)`` answer as a list of its *paged*
    kinds. A flat answer (name -> spec tuple; None: the pool's own k/v
    form) is one kind over all ``cfg.n_layers``; an answer by kind is
    taken as it is, in its own order, the per-slot kinds left to
    ``slot_kinds``."""
    specs = dict(leaf_specs or kv_leaf_specs(cfg))
    if not by_kind(specs):
        return [CacheKind(ONE_KIND, cfg.n_layers, specs)]
    return [CacheKind(name, int(kind["layers"]), dict(kind["leaves"]),
                      kind.get("window"))
            for name, kind in specs.items() if not kind.get("per_slot")]


def slot_kinds(leaf_specs: Optional[Dict[str, Any]]) -> List[SlotKind]:
    """The per-slot kinds of a ``cache_leaves`` answer (none in a flat
    one)."""
    if not by_kind(leaf_specs):
        return []
    return [SlotKind(name, int(kind["layers"]), dict(kind["leaves"]))
            for name, kind in leaf_specs.items() if kind.get("per_slot")]


def by_kind(leaf_specs: Optional[Dict[str, Any]]) -> bool:
    """Is this ``cache_leaves`` answer one by layer kind?"""
    return bool(leaf_specs) and all(
        isinstance(spec, dict) for spec in leaf_specs.values())


class PagePool:
    """Refcounted device page pool shared by prefill, prefix cache, and
    decode. ``num_pages`` may be given directly (a number, or a number a
    kind) or derived from ``budget_bytes`` (HBM cap across every leaf;
    one kind only: a pool of several kinds is told how its owner splits
    the bytes). Every ownership call takes ``kind``; left out it means
    the pool's first kind, which is the only one of a flat pool.
    ``slots`` is how many rows a per-slot kind's arrays have (the
    owner's ``max_slots``)."""

    def __init__(self, cfg, page: int = 32,
                 num_pages: Union[None, int, Dict[str, int]] = None,
                 budget_bytes: Optional[int] = None,
                 mesh=None, metrics=None,
                 leaf_specs: Optional[Dict[str, Any]] = None,
                 slots: int = 0):
        import threading

        import jax
        import numpy as np

        self._jax = jax
        self._np = np
        # Serializes donating executions against the pool leaves AND the
        # host-side ownership tables (free list, refcounts). One engine's
        # dispatches are already serialized on its loop, but co-resident
        # engines (multi-model tenancy) each run cold dispatches in
        # executor threads: engine A's donation deletes the handle
        # engine B captured unless call + leaves write-back form one
        # critical section. alloc/retain/release/reset take this lock
        # internally (reentrant), so callers on the engine loop stay
        # lock-free while staying race-free.
        self.lock = threading.RLock()
        self.cfg = cfg
        self.mesh = mesh
        self.metrics = metrics
        self.page = int(page)
        self.leaf_specs = dict(leaf_specs or kv_leaf_specs(cfg))
        # leaves nest by kind exactly when the module answered by kind
        self.by_kind = by_kind(self.leaf_specs)
        self.kinds: Dict[str, CacheKind] = {
            kind.name: kind for kind in cache_kinds(cfg, self.leaf_specs)}
        for kind in self.kinds.values():
            if mesh is not None and (self.by_kind or set(kind.leaves)
                                     - {"k", "v", "ks", "vs"}):
                raise ValueError(
                    f"PagePool: a mesh shards one kind of k/v leaves by "
                    f"kv-head; kind {kind.name!r} with leaves "
                    f"{sorted(kind.leaves)} has no sharding rule")
            kind.page_bytes = self._kind_page_bytes(kind, self.page)
        self.slot_kinds: Dict[str, SlotKind] = {
            kind.name: kind for kind in slot_kinds(self.leaf_specs)}
        for kind in self.slot_kinds.values():
            if mesh is not None or slots <= 0:
                raise ValueError(
                    f"PagePool: per-slot kind {kind.name!r} needs its "
                    f"owner's slot count (slots=) and has no sharding "
                    f"rule (mesh=None)")
            kind.slots = int(slots)
            kind.slot_bytes = self.slot_bytes_of(kind)
        # one page of every kind: what a token position costs the pool
        self.page_bytes = sum(k.page_bytes for k in self.kinds.values())
        if isinstance(num_pages, dict):
            for name, kind in self.kinds.items():
                kind.num_pages = int(num_pages[name])
        elif num_pages is not None:
            for kind in self.kinds.values():
                kind.num_pages = int(num_pages)
        elif budget_bytes is not None and len(self.kinds) == 1:
            self._first.num_pages = max(
                1, int(budget_bytes) // self.page_bytes)
        else:
            raise ValueError(
                "PagePool needs num_pages or budget_bytes (several kinds: "
                "num_pages, a number a kind)")
        # cumulative counters (survive reset: pool history, not contents)
        self.writes = 0        # page-rows scattered into the pool
        self.leaves: Dict[str, Any] = {}
        self._reset_subscribers: List[Callable[[], None]] = []
        self.reset()
        for kind in self.slot_kinds.values():
            kind.resets = 0        # the first build is no reset

    @property
    def _first(self) -> CacheKind:
        return next(iter(self.kinds.values()))

    def as_leaves(self, per_kind: Dict[str, Any]):
        """A value a kind in the form ``leaves`` has, which is the form
        the module's programs take their page tables and page ids in:
        nested by kind where the module answered by kind, else the one
        kind's value itself."""
        return per_kind if self.by_kind else per_kind[self._first.name]

    def map_kinds(self, fn: Callable[..., Any], *trees):
        """``fn`` over each kind's part of trees in ``leaves``' form."""
        if not self.by_kind:
            return fn(*trees)
        return {name: fn(*(tree[name] for tree in trees))
                for name in self.kinds}

    def _kind(self, kind: Optional[str]) -> CacheKind:
        return self._first if kind is None else self.kinds[kind]

    @property
    def num_pages(self) -> int:
        """Pages of every kind together (one kind: its pages)."""
        return sum(k.num_pages for k in self.kinds.values())

    @num_pages.setter
    def num_pages(self, n: int) -> None:
        # tests shrink a one-kind pool to force eviction, then reset()
        if len(self.kinds) != 1:
            raise ValueError("set kinds[name].num_pages on a pool of "
                             "several kinds")
        self._first.num_pages = int(n)

    @property
    def sentinel(self) -> int:
        """Out-of-bounds page id: dropped by scatters, clamped (and then
        length-masked) by gathers. A kind's own is ``sentinel_of``."""
        return self._first.num_pages

    def sentinel_of(self, kind: Optional[str] = None) -> int:
        return self._kind(kind).num_pages

    @property
    def allocs(self) -> int:
        return sum(k.allocs for k in self.kinds.values())

    @property
    def stalls(self) -> int:
        return sum(k.stalls for k in self.kinds.values())

    @staticmethod
    def _kind_page_bytes(kind: CacheKind, page: int) -> int:
        import jax.numpy as jnp

        per_token = sum(
            math.prod(spec[0]) * jnp.dtype(spec[1]).itemsize
            for spec in kind.leaves.values())
        return kind.layers * page * per_token

    @staticmethod
    def slot_bytes_of(kind: SlotKind) -> int:
        """HBM bytes one slot's row occupies across the kind's layers."""
        import jax.numpy as jnp

        return kind.layers * sum(
            math.prod(spec[0]) * jnp.dtype(spec[1]).itemsize
            for spec in kind.leaves.values())

    @staticmethod
    def _page_bytes(cfg, page: int,
                    leaf_specs: Optional[Dict[str, Any]] = None) -> int:
        """HBM bytes one page occupies across every cache leaf (of every
        kind: one page of each)."""
        return sum(PagePool._kind_page_bytes(kind, page)
                   for kind in cache_kinds(cfg, leaf_specs))

    def _init_leaves(self) -> None:
        import jax.numpy as jnp

        def fresh_kind(kind: CacheKind):
            lead = (kind.layers, kind.num_pages, self.page)
            return {name: jnp.full(lead + tuple(spec[0]),
                                   spec[2] if len(spec) > 2 else 0, spec[1])
                    for name, spec in kind.leaves.items()}

        def fresh_slots(kind: SlotKind):
            lead = (kind.layers, kind.slots)
            return {name: jnp.zeros(lead + tuple(spec[0]), spec[1])
                    for name, spec in kind.leaves.items()}

        def fresh():
            if not self.by_kind:
                return fresh_kind(self._first)
            return {name: fresh_slots(self.slot_kinds[name])
                    if name in self.slot_kinds
                    else fresh_kind(self.kinds[name])
                    for name in self.leaf_specs}

        if self.mesh is None:
            self.leaves = fresh()
            return
        # any slot gathers any page, so rows cannot shard over dp;
        # kv-heads shard over tp exactly like the dense cache. The leaves
        # are BORN sharded (jit with out_shardings): a pool sized to what
        # the mesh has left never fits whole on one device first.
        from gofr_tpu.parallel.sharding import (
            llama_prefix_pool_specs, named_shardings, prune_specs)
        specs = prune_specs(
            llama_prefix_pool_specs(kv_int8="ks" in self.leaf_specs),
            self.mesh)
        self.leaves = self._jax.jit(
            fresh, out_shardings=named_shardings(self.mesh, specs))()

    def reset(self) -> None:
        """Fresh device buffers, empty ownership. Called at engine
        device-state reset: a failed donating executable may have
        poisoned any in-flight handle. Honors a caller-resized
        ``num_pages`` (tests shrink pools to force eviction). When the
        pool is shared by several engines (multi-model tenancy), every
        subscriber is notified so co-resident owners can drop their now
        dangling page ids and device handles."""
        with self.lock:
            for kind in self.kinds.values():
                kind.free = list(range(kind.num_pages))
                kind.refs = self._np.zeros((kind.num_pages,),
                                           self._np.int32)
            for kind in self.slot_kinds.values():
                kind.claimed = set()
                kind.resets += 1
            self._init_leaves()
            self._set_gauges()
            callbacks = list(self._reset_subscribers)
        for callback in callbacks:
            callback()

    def subscribe(self, callback: Callable[[], None]) -> None:
        """Register a reset observer. A co-resident engine uses this to
        learn that another owner tore the pool down (its own page tables
        now point at freed pages and must be re-sentineled)."""
        if callback not in self._reset_subscribers:
            self._reset_subscribers.append(callback)

    # -- ownership ----------------------------------------------------------
    def alloc(self, n: int = 1,
              reclaim: Optional[Callable[[], bool]] = None,
              kind: Optional[str] = None) -> Optional[List[int]]:
        """Allocate ``n`` pages of ``kind`` at refcount 1,
        all-or-nothing. While the free list is short, ``reclaim()`` (if
        given) is called to release evictable pages; it returns False
        when it has nothing left. On failure returns None and counts a
        stall — never blocks.

        Self-serializing: the free list and refcounts mutate under the
        pool's own (reentrant) lock, so loop-thread allocation cannot
        race another owner's release — co-resident engines share one
        pool but not one thread. ``reclaim`` runs under the lock too;
        eviction callbacks re-enter ``release`` harmlessly (RLock)."""
        with self.lock:
            k = self._kind(kind)
            while len(k.free) < n and reclaim is not None \
                    and reclaim():
                pass
            if len(k.free) < n:
                k.stalls += 1
                if self.metrics is not None:
                    self.metrics.increment_counter(
                        "app_tpu_kv_pages_stalled_total")
                return None
            ids = [k.free.pop() for _ in range(n)]
            for pid in ids:
                k.refs[pid] = 1
            k.allocs += n
            self._set_gauges()
            return ids

    def retain(self, page_ids: Sequence[int],
               kind: Optional[str] = None) -> None:
        with self.lock:
            refs = self._kind(kind).refs
            for pid in page_ids:
                refs[pid] += 1

    def release(self, page_ids: Sequence[int],
                kind: Optional[str] = None) -> None:
        """Drop one ref per page; refcount 0 returns the page to its
        kind's free list. Releasing an already-free page is a no-op
        (reset guards)."""
        with self.lock:
            k = self._kind(kind)
            for pid in page_ids:
                if k.refs[pid] > 0:
                    k.refs[pid] -= 1
                    if k.refs[pid] == 0:
                        k.free.append(pid)
            self._set_gauges()

    def release_behind(self, page_ids: Sequence[int], kind: str) -> None:
        """``release`` for pages a window kind's slot has decoded past:
        counted apart (``kinds.<kind>.freed_behind``), so a run can say
        whether its traffic ever passed the window."""
        with self.lock:
            self.release(page_ids, kind)
            self.kinds[kind].freed_behind += len(page_ids)

    def claim_slot(self, slot: int) -> None:
        """Count row ``slot`` of every per-slot kind as claimed. The
        row's contents are its owner's to write (the insert)."""
        if not self.slot_kinds:
            return
        with self.lock:
            for kind in self.slot_kinds.values():
                kind.claimed.add(int(slot))
                kind.claimed_peak = max(kind.claimed_peak,
                                        len(kind.claimed))
            self._set_gauges()

    def release_slot(self, slot: int) -> None:
        """Row ``slot`` is claimed no longer (a no-op if it was not)."""
        if not self.slot_kinds:
            return
        with self.lock:
            for kind in self.slot_kinds.values():
                kind.claimed.discard(int(slot))
            self._set_gauges()

    @staticmethod
    def pad_table(table, block: int, sentinel: int):
        """Pad a host page table's width to a multiple of ``block`` with
        sentinel entries (ragged-paged-attention export: the Pallas
        kernel walks the table in page blocks, so its width must tile;
        the sentinel tail is skipped by the kernel's length guard exactly
        like any other dead entry). Returns the input unchanged when the
        width already tiles. table: (B, P) int32 ndarray."""
        import numpy as np

        width = table.shape[1]
        block = max(int(block), 1)
        pad = (-width) % block
        if pad == 0:
            return table
        return np.concatenate(
            [table, np.full((table.shape[0], pad), sentinel,
                            table.dtype)], axis=1)

    def note_writes(self, pages: int) -> None:
        """Count page-rows an owner's scatter actually wrote (sentinel
        entries excluded) — the zero-copy-admission proof reads this."""
        if pages <= 0:
            return
        self.writes += pages
        if self.metrics is not None:
            self.metrics.delta_updown_counter(
                "app_tpu_kv_pages_written_total", float(pages))

    # -- introspection ------------------------------------------------------
    def free_pages_of(self, kind: Optional[str] = None) -> int:
        return len(self._kind(kind).free)

    @property
    def free_pages(self) -> int:
        return sum(len(k.free) for k in self.kinds.values())

    @property
    def used_pages(self) -> int:
        return self.num_pages - self.free_pages

    @property
    def state_bytes(self) -> int:
        """Bytes of the per-slot kinds: every slot's row, claimed or not."""
        return sum(k.slots * k.slot_bytes for k in self.slot_kinds.values())

    @property
    def pool_bytes(self) -> int:
        return self.state_bytes + sum(
            k.num_pages * k.page_bytes for k in self.kinds.values())

    def refs(self, pid: int, kind: Optional[str] = None) -> int:
        return int(self._kind(kind).refs[pid])

    def _set_gauges(self) -> None:
        if self.metrics is None:
            return
        for kind in self.kinds.values():
            # a pool of several kinds labels its gauges by kind
            labels = {"kind": kind.name} if self.by_kind else {}
            self.metrics.set_gauge("app_tpu_kv_pages_used",
                                   float(kind.used_pages), **labels)
            self.metrics.set_gauge("app_tpu_kv_pages_capacity",
                                   float(kind.num_pages), **labels)
        for kind in self.slot_kinds.values():
            self.metrics.set_gauge("app_tpu_state_slots_claimed",
                                   float(len(kind.claimed)), kind=kind.name)
            self.metrics.set_gauge("app_tpu_state_slots_capacity",
                                   float(kind.slots), kind=kind.name)

    def stats(self) -> Dict[str, Any]:
        """The totals over every paged kind under the names they always
        had (``pool_bytes`` with the per-slot kinds' bytes in it), and
        each kind's own under ``kinds.<kind>``."""
        kinds: Dict[str, Any] = {
            name: {"layers": k.layers, "window": k.window,
                   "num_pages": k.num_pages, "used_pages": k.used_pages,
                   "page_bytes": k.page_bytes, "allocs": k.allocs,
                   "stalls": k.stalls, "freed_behind": k.freed_behind}
            for name, k in self.kinds.items()}
        for name, k in self.slot_kinds.items():
            kinds[name] = {"layers": k.layers, "per_slot": True,
                           "slots": k.slots, "slot_bytes": k.slot_bytes,
                           "bytes": k.slots * k.slot_bytes,
                           "claimed": len(k.claimed),
                           "claimed_peak": k.claimed_peak,
                           "resets": k.resets}
        return {
            "page_tokens": self.page,
            "num_pages": self.num_pages,
            "used_pages": self.used_pages,
            "free_pages": self.free_pages,
            "page_bytes": self.page_bytes,
            "bytes_per_token": self.page_bytes // self.page,
            "pool_bytes": self.pool_bytes,
            "occupancy": (round(self.used_pages / self.num_pages, 6)
                          if self.num_pages else 0.0),
            "allocs": self.allocs,
            "writes": self.writes,
            "stalls": self.stalls,
            "kinds": kinds,
        }


class HBMBudget:
    """Byte-granular HBM arbiter for multi-model tenancy.

    Engines with the *same* KV geometry share one :class:`PagePool`
    instance directly (page ids are interchangeable). Heterogeneous
    co-residents (different head counts, dtypes, page sizes) cannot share
    pages, so the registry carves the chip's KV budget in bytes instead:
    each model's carve becomes its own pool's ``budget_bytes``. The
    arbiter only does conservative bookkeeping — it never talks to the
    device — but it turns "two models silently OOM-ing each other" into
    an explicit, observable admission failure at load time.
    """

    def __init__(self, total_bytes: int):
        if total_bytes <= 0:
            raise ValueError("HBMBudget needs a positive byte budget")
        self.total_bytes = int(total_bytes)
        self._carves: Dict[str, int] = {}

    @property
    def carved_bytes(self) -> int:
        return sum(self._carves.values())

    @property
    def free_bytes(self) -> int:
        return self.total_bytes - self.carved_bytes

    def carve(self, name: str, nbytes: int) -> int:
        """Reserve ``nbytes`` for ``name``; raises when the remaining
        budget cannot cover it (fail at load, not mid-traffic)."""
        nbytes = int(nbytes)
        if nbytes <= 0:
            raise ValueError(f"carve({name!r}) needs a positive size")
        if name in self._carves:
            raise ValueError(f"model {name!r} already holds a carve")
        if nbytes > self.free_bytes:
            raise ValueError(
                f"HBM budget exhausted: {name!r} wants {nbytes} bytes, "
                f"{self.free_bytes} of {self.total_bytes} remain")
        self._carves[name] = nbytes
        return nbytes

    def release(self, name: str) -> None:
        self._carves.pop(name, None)

    def stats(self) -> Dict[str, Any]:
        return {
            "total_bytes": self.total_bytes,
            "carved_bytes": self.carved_bytes,
            "free_bytes": self.free_bytes,
            "carves": dict(self._carves),
        }
