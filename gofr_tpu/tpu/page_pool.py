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

Host-side state is a free list plus a per-page refcount:

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

from typing import Any, Callable, Dict, List, Optional, Sequence

__all__ = ["PagePool", "HBMBudget"]


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


class PagePool:
    """Refcounted device page pool shared by prefill, prefix cache, and
    decode. ``num_pages`` may be given directly or derived from
    ``budget_bytes`` (HBM cap across every leaf)."""

    def __init__(self, cfg, page: int = 32,
                 num_pages: Optional[int] = None,
                 budget_bytes: Optional[int] = None,
                 mesh=None, metrics=None,
                 leaf_specs: Optional[Dict[str, tuple]] = None):
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
        if mesh is not None and set(self.leaf_specs) - {"k", "v", "ks",
                                                        "vs"}:
            raise ValueError(
                f"PagePool: a mesh shards k/v leaves by kv-head; leaves "
                f"{sorted(self.leaf_specs)} have no sharding rule")
        self.page_bytes = self._page_bytes(cfg, self.page, self.leaf_specs)
        if num_pages is not None:
            self.num_pages = int(num_pages)
        elif budget_bytes is not None:
            self.num_pages = max(1, int(budget_bytes) // self.page_bytes)
        else:
            raise ValueError("PagePool needs num_pages or budget_bytes")
        # cumulative counters (survive reset: pool history, not contents)
        self.writes = 0        # page-rows scattered into the pool
        self.stalls = 0        # failed allocations (free list exhausted)
        self.allocs = 0
        self.leaves: Dict[str, Any] = {}
        self._free: List[int] = []
        self._refs = np.zeros((self.num_pages,), np.int32)
        self._reset_subscribers: List[Callable[[], None]] = []
        self.reset()

    @property
    def sentinel(self) -> int:
        """Out-of-bounds page id: dropped by scatters, clamped (and then
        length-masked) by gathers."""
        return self.num_pages

    @staticmethod
    def _page_bytes(cfg, page: int,
                    leaf_specs: Optional[Dict[str, tuple]] = None) -> int:
        """HBM bytes one page occupies across every cache leaf."""
        import math

        import jax.numpy as jnp

        per_token = sum(
            math.prod(spec[0]) * jnp.dtype(spec[1]).itemsize
            for spec in (leaf_specs or kv_leaf_specs(cfg)).values())
        return cfg.n_layers * page * per_token

    def _init_leaves(self) -> None:
        import jax.numpy as jnp

        lead = (self.cfg.n_layers, self.num_pages, self.page)

        def fresh():
            return {name: jnp.full(lead + tuple(spec[0]),
                                   spec[2] if len(spec) > 2 else 0, spec[1])
                    for name, spec in self.leaf_specs.items()}

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
            self._free = list(range(self.num_pages))
            self._refs = self._np.zeros((self.num_pages,), self._np.int32)
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
              reclaim: Optional[Callable[[], bool]] = None
              ) -> Optional[List[int]]:
        """Allocate ``n`` pages at refcount 1, all-or-nothing. While the
        free list is short, ``reclaim()`` (if given) is called to release
        evictable pages; it returns False when it has nothing left. On
        failure returns None and counts a stall — never blocks.

        Self-serializing: the free list and refcounts mutate under the
        pool's own (reentrant) lock, so loop-thread allocation cannot
        race another owner's release — co-resident engines share one
        pool but not one thread. ``reclaim`` runs under the lock too;
        eviction callbacks re-enter ``release`` harmlessly (RLock)."""
        with self.lock:
            while len(self._free) < n and reclaim is not None \
                    and reclaim():
                pass
            if len(self._free) < n:
                self.stalls += 1
                if self.metrics is not None:
                    self.metrics.increment_counter(
                        "app_tpu_kv_pages_stalled_total")
                return None
            ids = [self._free.pop() for _ in range(n)]
            for pid in ids:
                self._refs[pid] = 1
            self.allocs += n
            self._set_gauges()
            return ids

    def retain(self, page_ids: Sequence[int]) -> None:
        with self.lock:
            for pid in page_ids:
                self._refs[pid] += 1

    def release(self, page_ids: Sequence[int]) -> None:
        """Drop one ref per page; refcount 0 returns the page to the free
        list. Releasing an already-free page is a no-op (reset guards)."""
        with self.lock:
            for pid in page_ids:
                if self._refs[pid] > 0:
                    self._refs[pid] -= 1
                    if self._refs[pid] == 0:
                        self._free.append(pid)
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
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def pool_bytes(self) -> int:
        return self.num_pages * self.page_bytes

    def refs(self, pid: int) -> int:
        return int(self._refs[pid])

    def _set_gauges(self) -> None:
        if self.metrics is not None:
            self.metrics.set_gauge("app_tpu_kv_pages_used",
                                   float(self.used_pages))
            self.metrics.set_gauge("app_tpu_kv_pages_capacity",
                                   float(self.num_pages))

    def stats(self) -> Dict[str, Any]:
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
