"""Continuous-batching generation engine for the Llama /generate path.

North star config 5 (BASELINE.json): "Llama-2-7B /generate, tensor-parallel
across v5e-8, KV-cache in HBM ... continuous batching on the generate loop"
(SURVEY.md §7.7). The design is slot-based continuous batching:

- One static-shape KV cache of ``max_slots`` sequences lives in HBM for
  the engine's lifetime (no per-request allocation). With a ``mesh`` it is
  sharded: slots over ``dp``, kv-heads over ``tp``
  (parallel/sharding.llama_cache_specs); params get the Megatron
  column/row specs (llama_param_specs) so XLA inserts one all-reduce per
  block over ICI.
- A new request claims a free slot. Admissions are *batched*: all
  requests pending at the top of a loop iteration prefill together in one
  executable (count padded to a ladder, prompts right-padded to a length
  bucket). Prefill is split into two executables — a pure-compute forward
  producing the prompt KV, and a cheap scatter that inserts it into the
  big cache — so the expensive half needs no exclusive cache ownership.
- A single decode executable advances ALL active slots ``K`` tokens per
  tick (``lax.scan`` inside one program, K chosen adaptively from a
  compiled ladder up to ``steps_per_tick``). Requests join and leave
  mid-flight without recompiles or barriers.
- The loop is *pipelined M deep*: up to ``max_inflight_ticks`` ticks are
  dispatched (JAX async dispatch) before the oldest tick's tokens are
  fetched to host, and every fetch runs concurrently in its own worker
  thread. Device→host token fetches therefore overlap both the device
  compute AND each other — on hosts where the D2H round trip rivals the
  tick compute time (PCIe under load), fetch latency amortizes across M
  ticks instead of serializing the loop. Tokens always publish in
  dispatch order (FIFO), so per-slot ordering and eos/budget semantics
  are unchanged; per-slot ``inflight`` accounting keeps speculative
  depth from overshooting any budget.
- Inactive slots are frozen in the decode executable (cache_len does not
  advance), so an idle slot's fill never grows between requests.
- Per-slot host state (remaining budget, eos, emitted tokens, generation
  counter) stays in numpy; device state is (cache, cache_len, last_token)
  plus per-slot sampling state (temperature, top_k, top_p, PRNG key —
  ops/sampling). A tick whose active slots are all greedy runs the same
  argmax executable as before; any sampled slot switches the tick to the
  sampling variant, where greedy rows still resolve to argmax in-program.
- Tokens stream: ``generate_stream`` yields ids as each tick's fetch
  lands (per-slot asyncio.Queue), so time-to-first-token is the prefill
  latency, not the full completion. ``generate`` keeps the gather-all
  future API on the same plumbing.

Everything here is static-shape XLA: the engine never traces after the
executable ladders are warm.
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from functools import partial
from types import SimpleNamespace
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from gofr_tpu.aio import spawn_logged
from gofr_tpu.slo import DeadlineExceeded, current_deadline
from gofr_tpu.tpu import faults
from gofr_tpu.tpu.compile_ledger import (ExecutableLedger, ShapeStats,
                                         charge_device_time, suggest_ladder)
from gofr_tpu.tpu.constrain import GrammarWalker
from gofr_tpu.tpu.flightrecorder import FlightRecorder, RequestRecord
from gofr_tpu.tpu.page_pool import (ONE_KIND, by_kind, cache_kinds,
                                    slot_kinds)
from gofr_tpu.tpu.sched import (ClassQueues, DEFAULT_CLASS_WEIGHTS,
                                brownout_shed_classes, deadline_class)
from gofr_tpu.trace import Span, current_span, extract_traceparent

DEFAULT_PROMPT_BUCKETS = (32, 128, 512)

# adaptive-γ controller (speculative decode): windowed acceptance is
# evaluated every N spec ticks; below the shrink threshold the γ cap
# halves (a diverging draft wastes the whole verify forward), above the
# grow threshold it climbs back toward the configured γ
_SPEC_WINDOW_TICKS = 16
_SPEC_SHRINK_BELOW = 0.5
_SPEC_GROW_ABOVE = 0.8

# sentinel pushed onto a streaming queue when the request completes
_DONE = object()

# adopt-dedupe ledger (ISSUE 14): replayed adoptions within this window
# return the original stream instead of claiming pages twice. Matches the
# exporter-side HandoffTable default TTL so both halves of a handoff
# forget a transfer id at the same time.
_ADOPT_LEDGER_TTL_S = 120.0
_ADOPT_LEDGER_CAP = 256


def weight_formats(fn, operands, donate_argnums=()):
    """The layouts the compiler picks for the leaves of ``fn``'s first
    operand when they are left to it: (that operand's tree of
    ``Format``, the compiled program's temporaries in bytes or None,
    the compiled program, which takes that operand in those formats).

    ``operands`` may be abstract (``jax.ShapeDtypeStruct``), each leaf of
    the first with its ``sharding``; every other operand keeps the layout
    it arrives in. An executable's parameter layouts are fixed when it is
    compiled, so a layer loop that wants a stacked weight with another
    dimension minor transposes the whole stack at every call. Compiled
    with ``Layout.AUTO`` the program states the layout it reads in
    place."""
    import jax
    from jax.experimental.layout import Format, Layout

    auto = jax.tree.map(lambda leaf: Format(Layout.AUTO, leaf.sharding),
                        operands[0])
    compiled = jax.jit(
        fn, in_shardings=(auto,) + (None,) * (len(operands) - 1),
        donate_argnums=donate_argnums).lower(*operands).compile()
    analysis = compiled.memory_analysis()
    return (compiled.input_formats[0][0],
            getattr(analysis, "temp_size_in_bytes", None), compiled)


def _relay(leaves, wanted):
    """``leaves`` as new arrays in the ``Format``s ``wanted``, by one
    program compiled in this process. An executable that writes a result
    in another layout than the default must not come from the persistent
    compilation cache: loaded from there its result reports the default
    layout (jax 0.9.0: on XLA:CPU, where the data is not in it and
    reads wrongly through ``jit``, and on a v5e), where one compiled
    here reports what it is. So the program goes under a name of its
    own, which no ``jax.device_put`` of another process has cached, and
    is not written (the floor on compile times decides that, and is put
    back)."""
    import jax

    def relaid(*xs):
        return xs

    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    try:
        # graftcheck: ignore[GT003] — once an engine, and a program of its
        # own each time is the point: nothing cached is to be found
        return jax.jit(relaid, out_shardings=tuple(wanted))(*leaves)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)


class BrownoutShed(RuntimeError):
    """Admission refused by the brownout ladder (slo.BrownoutLadder):
    the replica is shedding this SLO class to protect interactive
    traffic. Retryable elsewhere — handlers map it to 503."""
    status_code = 503


class Sampling:
    """Per-request sampling parameters. ``temperature <= 0`` is greedy;
    ``top_k == 0`` and ``top_p >= 1`` disable their filters. ``seed=None``
    (the default) draws fresh entropy so two identical sampled requests
    differ; pass an explicit seed for reproducible completions."""
    __slots__ = ("temperature", "top_k", "top_p", "seed")

    def __init__(self, temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: Optional[int] = None):
        import os
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = (int(seed) if seed is not None
                     else int.from_bytes(os.urandom(4), "little"))

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


class TokenStream:
    """Async iterator over one request's generated tokens.

    Owns explicit cancellation (``cancel()`` sync, ``aclose()`` async):
    abandoning the stream frees the engine slot whether or not iteration
    ever started — a plain async-generator ``finally`` cannot give that
    guarantee (PEP 525: an unstarted generator's ``aclose`` skips the
    body). HTTP/gRPC handlers can pass ``cancel`` as ``Stream.on_close``
    so even a never-started response stream releases its slot."""

    __slots__ = ("_engine", "_queue", "_future", "_done", "_buffer")

    def __init__(self, engine: "GenerationEngine", queue: asyncio.Queue,
                 future: asyncio.Future):
        self._engine = engine
        self._queue = queue
        self._future = future
        self._done = False
        # batched token shipping (ISSUE 9): the engine may enqueue one
        # *list* of tokens per decode tick instead of one item per token;
        # __anext__ drains the chunk locally so per-token iteration keeps
        # working unchanged while the queue traffic is per-tick
        self._buffer: List[int] = []

    def __aiter__(self) -> "TokenStream":
        return self

    async def __anext__(self) -> int:
        if self._buffer:
            return self._buffer.pop(0)
        if self._done:
            raise StopAsyncIteration
        item = await self._queue.get()
        if item is _DONE:
            self._finish()
            raise StopAsyncIteration
        if isinstance(item, BaseException):
            self._finish()
            raise item
        if isinstance(item, list):
            self._buffer = item[1:]
            return item[0]
        return item

    async def chunks(self) -> "AsyncIterator[List[int]]":
        """Iterate token **deltas** — every list is all tokens that landed
        since the last yield (one decode tick's worth under
        ``coalesce_stream``). The streaming layer ships each delta as one
        coalesced frame instead of a frame per token."""
        while True:
            if self._buffer:
                chunk, self._buffer = self._buffer, []
                yield chunk
                continue
            if self._done:
                return
            item = await self._queue.get()
            if item is _DONE:
                self._finish()
                return
            if isinstance(item, BaseException):
                self._finish()
                raise item
            chunk = item if isinstance(item, list) else [item]
            # drain whatever else already arrived — one frame per wakeup
            while True:
                try:
                    extra = self._queue.get_nowait()
                except asyncio.QueueEmpty:
                    break
                if extra is _DONE:
                    yield chunk
                    self._finish()
                    return
                if isinstance(extra, BaseException):
                    yield chunk
                    self._finish()
                    raise extra
                chunk.extend(extra if isinstance(extra, list) else [extra])
            yield chunk

    def _finish(self) -> None:
        self._done = True
        # keep the engine's failure (if any) from surfacing as an
        # "exception was never retrieved" warning on the paired future
        if not self._future.done():
            self._future.cancel()
        elif not self._future.cancelled():
            self._future.exception()

    def cancel(self) -> None:
        """Abandon the request: free its slot (or unqueue it). Idempotent;
        safe from any completion path, including before first iteration."""
        if not self._done:
            self._engine._cancel_stream(self._queue)
            self._finish()

    async def aclose(self) -> None:
        self.cancel()


class _Flight:
    """Per-request observability context threaded from submit to finish:
    the span identifying the request (the HTTP request span when the call
    came through the middleware, else the ``queue.wait`` span's trace), the
    open ``queue.wait`` span, and the flight-recorder record. Also carries
    the request's absolute deadline (monotonic seconds, None = no SLO)
    captured at submit time — admission re-checks it so a request whose
    budget was eaten by queue wait is shed before prefill."""
    __slots__ = ("link_span", "qspan", "record", "deadline")

    def __init__(self, link_span: Optional[Span], qspan: Optional[Span],
                 record: RequestRecord, deadline: Optional[float] = None):
        self.link_span = link_span
        self.qspan = qspan
        self.record = record
        self.deadline = deadline


class _Slot:
    __slots__ = ("future", "remaining", "eos_id", "tokens", "active", "gen",
                 "inflight", "queue", "temperature", "fill", "submitted_at",
                 "deadline", "record", "req_span", "phase_span", "chains",
                 "nodes", "cls", "spec_proposed", "spec_accepted", "grammar",
                 "migrating")

    def __init__(self):
        self.migrating = False  # quiescing for export: joins no new tick
        # paged KV: the pool pages this slot owns, a cache kind: kind ->
        # [first table column they fill, the pages from there on]. The
        # columns before it hold pinned prefix nodes' pages, or nothing
        # any more: a window kind's first column moves up as the slot
        # decodes past its pages
        self.chains: Dict[str, List[Any]] = {}
        self.nodes: List[Any] = []   # paged KV: pinned prefix-trie nodes
        self.cls = "batch"           # SLO class (tpu.sched.deadline_class)
        self.grammar = None          # constrained decoding: GrammarWalker
        self.spec_proposed = 0       # speculative decode: draft tokens
        self.spec_accepted = 0       # ... and how many the target kept
        self.future: Optional[asyncio.Future] = None
        self.submitted_at = 0.0    # request submit time → TTFT histogram
        self.deadline: Optional[float] = None  # abs monotonic SLO deadline
        self.remaining = 0
        self.eos_id: Optional[int] = None
        self.tokens: List[int] = []
        self.active = False
        self.gen = 0          # bumped on claim: stale tick tokens are dropped
        self.inflight = 0     # tokens dispatched on device, not yet published
        self.queue: Optional[asyncio.Queue] = None   # streaming consumers
        self.temperature = 0.0   # host copy: picks greedy vs sampled tick
        self.fill = 0         # host mirror of device cache_len (exact: set
                              # at admission, +k per participated tick) —
                              # what the page covers and row counts read
        self.record: Optional[RequestRecord] = None  # flight recorder entry
        self.req_span: Optional[Span] = None   # request span (link target)
        self.phase_span: Optional[Span] = None  # open prefill/decode span

    def held_pages(self) -> int:
        """Pool pages this slot holds, of every kind, pinned prefix
        nodes included."""
        return len(self.nodes) + sum(
            len(pages) for _, pages in self.chains.values())


def _kind_label(kind: str) -> str:
    """A cache kind in an error's text: the one kind of a k/v pool reads
    "KV"."""
    return "KV" if kind == ONE_KIND else kind


class _Fetch:
    """One dispatched device op whose tokens are being fetched to host in a
    worker thread. ``kind`` is "prefill" (payload: [(slot, gen, row)]),
    "tick" (payload: [(slot, gen)]), or "spec" (payload: ([(slot, gen)],
    gamma); the fetch lands (tokens, accept_counts)). ``span`` is the open
    engine-step span (dispatch → publish), finished when the fetch
    lands. ``dispatched_at`` is stamped before the program is handed to
    the runtime and ``landed_at`` where the fetch lands: on the worker
    thread, as the last act of ``tpu.fetch.<kind>`` (a host annotation
    that ends at that instant, so the landing lies on a profiler
    capture's clock beside the device's own events; the name is outside
    ``tpu.engine.*`` because it spans the device's work from another
    thread and would take every idle gap from the loop's phases). The
    two stamps are all ``_Timeline`` needs. ``work`` is what a tick
    tells the timeline about itself (``_dispatch_tick``). ``anatomy``
    carries a sampled tick's share of the loop clock to ``_publish``
    (None on unsampled ticks): the pass that dispatched it stores its
    admit and dispatch laps here, and ``_publish`` adds the device wait
    and its own lap before handing the dict to telemetry. ``family``
    names the compiled-executable family the dispatch hit (ISSUE 17):
    the entry's interval also lands in the per-executable roofline
    ledger under it."""
    __slots__ = ("task", "kind", "payload", "span", "dispatched_at",
                 "landed_at", "anatomy", "family", "work", "_get")

    def __init__(self, kind: str, payload, get, dispatched_at: float,
                 span: Optional[Span] = None, family: Optional[str] = None,
                 work=None):
        self.task = None
        self.kind = kind
        self.payload = payload
        self.span = span
        self.dispatched_at = dispatched_at
        self.landed_at = dispatched_at
        self.anatomy = None
        self.family = family
        self.work = work
        self._get = get

    def start(self, loop, annotation) -> "_Fetch":
        """Start the fetch in a worker thread; ``task`` resolves to what
        ``get`` returned."""
        self.task = loop.run_in_executor(None, self._land, annotation)
        return self

    def _land(self, annotation):
        with annotation("tpu.fetch." + self.kind):
            host = self._get()
            self.landed_at = time.monotonic()
        return host


class _LoopClock:
    """The engine loop's one clock: who holds the serving thread.

    Every pass of ``_loop_body`` is cut into contiguous phases. ``admit``
    (admission staging, prefill / insert dispatch), ``dispatch`` (the
    decode or speculative tick up to its ``_Fetch``) and ``publish``
    (every ``_publish`` of the pass) *hold* the event loop's thread, and
    so do the two laps cut out of them where the thread can block in the
    runtime (``lap``): ``enqueue`` (a warm call of a program: a tick, a
    speculative tick, a prefill group with its insert) and ``upload``
    (``_upload_group``), so ``admit`` and ``dispatch`` are the rest of
    their phases. ``wait`` (awaiting the oldest fetch, the 1 ms sleep of
    a pass that dispatched nothing, a first-time compile running off the
    loop) and ``park`` (no work) yield the thread to the other
    coroutines: HTTP parsing, handlers, SSE framing, socket writes.

    ``enter`` stamps a boundary with ``time.monotonic()`` and
    ``time.thread_time()`` (CPU of the calling thread, which must be the
    event loop's), charges the lap since the last stamp to the phase it
    closes, and opens ``jax.profiler.TraceAnnotation("tpu.engine.<phase>")``
    (a TraceMe no-op without a live capture) so the same spans lie on the
    device trace's clock. The phases are contiguous, so the wall totals
    sum to the time since the loop started; CPU that accrues in ``wait``
    and ``park`` is everything else on the thread (``yield_cpu_s``), and
    wall less CPU of a holding phase is time the engine kept the thread
    while doing nothing (blocked in the runtime, or waiting for the GIL).
    A phase's longest single lap is kept with its CPU and when it ended
    (``longest``): a stall of seconds names the phase it sat in.
    Readers: ``stats()["loop"]``, the profiler's trace, and the sampled
    tick ring of ``/debug/timez`` (a view of the same stamps)."""

    PHASES = ("admit", "dispatch", "publish", "enqueue", "upload", "wait",
              "park")
    HOLDING = PHASES[:5]
    __slots__ = ("wall", "cpu", "longest", "passes", "phase", "at",
                 "_cpu_at", "_span", "_annotation")

    def __init__(self, annotation):
        self.wall = dict.fromkeys(self.PHASES, 0.0)
        self.cpu = dict.fromkeys(self.PHASES, 0.0)
        # phase -> (wall, cpu, time.time() at its end) of its longest lap
        self.longest: Dict[str, Tuple[float, float, float]] = {}
        self.passes = 0
        self.phase: Optional[str] = None    # None: the loop is not running
        self.at = 0.0                       # monotonic stamp of the last boundary
        self._cpu_at = 0.0
        self._span = None
        self._annotation = annotation

    def enter(self, phase: Optional[str]) -> Tuple[float, float]:
        """Close the open phase at a fresh stamp and open ``phase``
        (``None`` stops the clock). Returns the closed lap as (wall, cpu)
        seconds; entering the open phase again splits a lap."""
        now, cpu = time.monotonic(), time.thread_time()
        was, lap = self.phase, (0.0, 0.0)
        if was is not None:
            lap = (now - self.at, cpu - self._cpu_at)
            self.wall[was] += lap[0]
            self.cpu[was] += lap[1]
            if lap[0] > self.longest.get(was, (-1.0,))[0]:
                self.longest[was] = (lap[0], lap[1], time.time())
        self.at, self._cpu_at = now, cpu
        if phase != was:
            if self._span is not None:
                self._span.__exit__(None, None, None)
            # a TraceMe starts when it is made; held across awaits, so not
            # a ``with`` block: the next boundary closes it
            self._span = (None if phase is None
                          else self._annotation("tpu.engine." + phase))
            self.phase = phase
        return lap

    def stamp(self) -> Tuple[float, float]:
        """The last boundary's (monotonic, thread CPU) readings: two
        stamps' difference is everything the thread did between them,
        whatever phases and laps that took."""
        return self.at, self._cpu_at

    @contextmanager
    def lap(self, phase: str):
        """Cut ``phase`` out of the open holding phase: enter it, and go
        back to the phase that was open when the block ends. Under a
        yielding phase (a first-time compile dispatching from a worker
        thread while the loop waits, ``_off_loop``) or a stopped clock
        the block is not the loop thread's to stamp and runs unclocked."""
        was = self.phase
        if was not in self.HOLDING:
            yield
            return
        self.enter(phase)
        try:
            yield
        finally:
            self.enter(was)

    def stats(self) -> Dict[str, Any]:
        """Cumulative seconds, all monotone but ``longest``. The open
        phase's wall time up to now is folded in, so ``wall_s`` is the
        time the loop has run; its CPU lands at the next boundary."""
        wall = dict(self.wall)
        if self.phase is not None:
            wall[self.phase] += max(0.0, time.monotonic() - self.at)
        out: Dict[str, Any] = {"passes": self.passes,
                               "wall_s": sum(wall.values())}
        for phase in self.PHASES:
            out[phase + "_s"] = wall[phase]
        for phase in self.HOLDING:
            out[phase + "_cpu_s"] = self.cpu[phase]
        out["held_s"] = sum(wall[p] for p in self.HOLDING)
        out["held_cpu_s"] = sum(self.cpu[p] for p in self.HOLDING)
        out["yield_cpu_s"] = self.cpu["wait"] + self.cpu["park"]
        out["longest"] = {
            phase: {"wall_s": lap[0], "cpu_s": lap[1], "at": lap[2]}
            for phase, lap in self.longest.items()}
        return out


class _Timeline:
    """The device's timeline as the engine sees it, always on like
    ``_LoopClock``. The device runs the engine's programs in dispatch
    order and every program's token fetch is stamped where it lands
    (``_Fetch``), so an entry's *interval*, its landing less the later of
    the previous entry's landing and its own dispatch, is that program's
    device time on the host's clock wherever the device had work queued,
    which under load is always. Where it had none the interval starts at
    the dispatch and holds the enqueue. A prefill entry's fetch lands
    when the prefill executable ends: its insert runs after and falls
    into the next entry's interval. Intervals are disjoint, so their sum
    over one device cannot pass the loop's wall time.

    ``land`` is called once an entry, in dispatch order, from
    ``_publish``. Everything is cumulative and monotone but ``longest``
    (a kind's longest interval with its executable family and when it
    landed); a reader takes deltas over its window.

    ``busy_s`` all intervals; ``tick_s``, ``ticks``, ``tick_steps`` (the
    K of each tick summed: ``decode_steps`` counts ticks) and
    ``tick_tokens`` (tokens the ticks published to live requests) the
    decode ticks; ``prefill_s``, ``prefill_groups``; ``spec_s``;
    ``rows_live`` / ``rows_gathered`` (paged gather path only, a layer):
    cache rows under the participants' lengths, the new row with them,
    over the rows ``max_slots x pages_per_slot x kv_page`` a step's page
    gather brings in (the whole table); ``stalled_slot_s``: prefill
    intervals times the decoding slots each stopped
    (``GenerationEngine._stall``)."""

    COUNTS = ("busy_s", "tick_s", "ticks", "tick_steps", "tick_tokens",
              "prefill_s", "prefill_groups", "spec_s", "rows_live",
              "rows_gathered", "stalled_slot_s")
    __slots__ = COUNTS + ("longest", "_landed_at")

    def __init__(self):
        self.busy_s = self.tick_s = self.prefill_s = self.spec_s = 0.0
        self.stalled_slot_s = 0.0
        self.ticks = self.tick_steps = self.tick_tokens = 0
        self.prefill_groups = self.rows_live = self.rows_gathered = 0
        self.longest: Dict[str, Dict[str, Any]] = {}
        self._landed_at = 0.0

    def land(self, entry: _Fetch) -> float:
        """Book a landed entry; returns its interval in seconds."""
        start = max(self._landed_at, entry.dispatched_at)
        interval = max(0.0, entry.landed_at - start)
        # two fetches' threads may stamp out of order by microseconds
        self._landed_at = max(start, entry.landed_at)
        self.busy_s += interval
        if entry.kind == "prefill":
            self.prefill_s += interval
            self.prefill_groups += 1
        elif entry.kind == "spec":
            self.spec_s += interval
        else:
            steps, live, gathered = entry.work
            self.tick_s += interval
            self.ticks += 1
            self.tick_steps += steps
            self.rows_live += live
            self.rows_gathered += gathered
        longest = self.longest.get(entry.kind)
        if longest is None or interval > longest["s"]:
            self.longest[entry.kind] = {
                "s": interval, "family": entry.family, "at": time.time()}
        return interval

    def stats(self) -> Dict[str, Any]:
        out = {name: getattr(self, name) for name in self.COUNTS}
        out["longest"] = {kind: dict(entry)
                          for kind, entry in self.longest.items()}
        return out


class GenerationEngine:
    def __init__(self, cfg, params, max_slots: int = 8,
                 max_len: Optional[int] = None,
                 prompt_buckets=DEFAULT_PROMPT_BUCKETS,
                 steps_per_tick: int = 1,
                 max_inflight_ticks: int = 2,
                 mesh=None,
                 window_ladder: Optional[bool] = None,
                 prefix_cache: bool = False,
                 prefix_cache_bytes: int = 64 << 20,
                 prefix_page: int = 32,
                 paged_kv: bool = False,
                 kv_page: int = 32,
                 kv_pages: Optional[int] = None,
                 kv_pool_bytes: Optional[int] = None,
                 kv_page_reserve: Optional[int] = None,
                 page_pool=None,
                 ragged_attn: str = "auto",
                 max_group_tokens: Optional[int] = None,
                 model_module=None,
                 model_name: str = "generate",
                 draft_cfg=None, draft_params=None,
                 spec_gamma: int = 4,
                 class_weights: Optional[Dict[str, float]] = None,
                 coalesce_uploads: bool = False,
                 coalesce_stream: bool = False,
                 token_table=None,
                 grammar_cache_entries: int = 32,
                 logger=None, metrics=None, tracer=None, recorder=None,
                 slo=None):
        import jax
        import jax.numpy as jnp

        from gofr_tpu.models import llama
        from gofr_tpu.tpu.compile_cache import configure_compile_cache

        configure_compile_cache()
        self._jax = jax
        self._jnp = jnp
        # the served model module: llama by default; anything exposing the
        # serving contract (docs/tpu/model-serving.md: init_cache /
        # prefill / decode_step[_paged] with a compatible Config, and
        # optionally cache_leaves and STEP_COUNTERS) plugs in —
        # models/moe.py and models/mla_moe.py are the takers
        self._llama = llama if model_module is None else model_module
        self.model_name = str(model_name)
        named = getattr(self._llama, "__name__", repr(self._llama))
        # what the module's cache holds (cache_leaves; None: the pool's
        # own k/v form). A per-slot kind (a recurrent state a slot, not
        # pages) lives in the page pool's manager beside the paged kinds
        # and is refused, by name, wherever only pages are handled
        cache_leaves = getattr(self._llama, "cache_leaves", None)
        self._leaf_specs: Optional[Dict[str, Any]] = (
            cache_leaves(cfg) if cache_leaves else None)
        per_slot = sorted(k.name for k in slot_kinds(self._leaf_specs))
        if per_slot:
            state = f"model_module {named}: per-slot cache kind {per_slot}"
            for given, why in (
                    (not paged_kv, "is kept by the page pool's manager "
                     "(paged_kv=True); the dense cache has rows of tokens"),
                    (prefix_cache, "cannot use prefix_cache: prefix pages "
                     "hold no state, so a hit would skip the tokens that "
                     "made it"),
                    (draft_cfg is not None, "cannot use speculative "
                     "decode: a rejected draft would have to roll the "
                     "state back"),
                    (mesh is not None, "has no sharding rule (mesh=None)"),
                    (page_pool is not None, "is a row a slot of this "
                     "engine: a shared page_pool's slots are not")):
                if given:
                    raise ValueError(f"{state} {why}")
        if model_module is not None and model_module is not llama:
            wanted = ("init_cache", "prefill",
                      "decode_step_paged" if paged_kv else "decode_step")
            missing = [name for name in wanted
                       if not hasattr(model_module, name)]
            if missing:
                raise ValueError(
                    f"model_module {named} lacks the serving entry "
                    f"point(s) {', '.join(missing)}"
                    + (" (paged_kv=True decodes through decode_step_paged)"
                       if "decode_step_paged" in missing else ""))
            if mesh is not None:
                raise ValueError(
                    f"model_module {named}: the sharding specs are "
                    f"llama's; other model modules serve unsharded "
                    f"(mesh=None)")
            if prefix_cache:
                raise ValueError(
                    f"model_module {named}: prefix_cache needs llama's "
                    f"suffix prefill over k/v pages")
            if draft_cfg is not None:
                raise ValueError(
                    f"model_module {named}: speculative decode needs "
                    f"llama's verify step over k/v leaves")
        # what one token leaves in the module's cache (cache_leaves; None:
        # the pool's own k/v form). kv_wire ships k/v pages (export,
        # adoption, session migration): a module whose cache leaves are
        # of another form cannot use it
        # a module whose layers keep caches of different kinds answers by
        # kind (page_pool.cache_kinds): the pool keeps pages and a slot a
        # page table a kind, and a window kind's pages go back to the pool
        # as the slot decodes past them
        self._by_kind = by_kind(self._leaf_specs)
        if self._by_kind and not paged_kv:
            raise ValueError(
                f"model_module {named}: cache leaves by layer kind "
                f"{sorted(self._leaf_specs)} are served from the page "
                f"pool (paged_kv=True); the dense cache has one kind")
        self._kv_wire_refusal: Optional[str] = (
            f"model_module {named}: per-slot cache kind {per_slot} has no "
            f"snapshot yet, and kv_wire (prefill export, adoption, session "
            f"migration) ships pages alone"
            if per_slot else
            f"model_module {named}: its cache has a page table a layer "
            f"kind {sorted(self._leaf_specs)}, kv_wire (prefill export, "
            f"adoption, session migration) ships one table's k/v pages"
            if self._by_kind else
            None if self._leaf_specs is None
            or {"k", "v"} <= set(self._leaf_specs) else
            f"model_module {named}: its cache leaves "
            f"{sorted(self._leaf_specs)} are not k/v, which kv_wire "
            f"(prefill export, adoption, session migration) ships")
        # what a decode step of this module counts (STEP_COUNTERS: names
        # like "moe.held_pairs"); the tick sums them over its K steps and
        # active rows and returns them beside the tokens. A module that
        # declares none compiles to the tick it always compiled to
        self._step_counters: Tuple[str, ...] = tuple(
            getattr(self._llama, "STEP_COUNTERS", ()))
        self._step_totals = [0] * len(self._step_counters)
        # upper bound on rows x bucket of one admission group (None: a
        # group is bounded by max_slots alone). A group over it is split
        # in arrival order, and the warm-up skips the rungs no group can
        # reach: the prefill's temporaries, not the cache, are what a
        # small-cache model runs out of
        self.max_group_tokens = (None if max_group_tokens is None
                                 else max(1, int(max_group_tokens)))
        self.cfg = cfg
        self.mesh = mesh
        if mesh is not None and "dp" in mesh.shape:
            dp = mesh.shape["dp"]
            max_slots = -(-max_slots // dp) * dp   # round up: dp-divisible
        self.max_slots = max_slots
        self.max_len = max_len or cfg.max_seq_len
        self.prompt_buckets = tuple(
            b for b in sorted(prompt_buckets) if b <= self.max_len)
        # ladder of fused-steps-per-tick executables (1,2,4,...,K): the loop
        # picks the largest rung ≤ the smallest remaining budget so budget
        # is never overshot, and drops to 1 while admissions are waiting.
        self.steps_per_tick = max(1, int(steps_per_tick))
        self._k_ladder = [1]
        while self._k_ladder[-1] * 2 <= self.steps_per_tick:
            self._k_ladder.append(self._k_ladder[-1] * 2)
        # unified paged KV (ISSUE 6): decode attends pool pages addressed
        # through a per-slot page table instead of a dense
        # (max_slots, max_len) cache row — HBM scales with the pool, not
        # max_len x max_slots, and admission scales with free pages.
        self.paged = bool(paged_kv)
        self.kv_page = int(kv_page)
        if self.paged:
            if self.max_len % self.kv_page:
                raise ValueError(
                    f"paged_kv: max_len {self.max_len} must be a multiple "
                    f"of kv_page {self.kv_page}")
            bad = [b for b in self.prompt_buckets if b % self.kv_page]
            if bad:
                raise ValueError(
                    f"paged_kv: prompt buckets {bad} are not multiples of "
                    f"kv_page {self.kv_page} (page-aligned inserts need "
                    f"page-aligned buckets)")
            if mesh is not None and mesh.shape.get("dp", 1) > 1:
                raise ValueError(
                    "paged_kv: the shared page pool cannot shard pages "
                    "over dp (any slot may gather any page); use a "
                    "tp-only mesh")
        # every decode tick reads the whole page table (paged) or cache
        # row (dense). window_ladder= is taken only in its no-ladder form,
        # for benchmark/configs/xing4-29b.json and dots3-note-ep32.json,
        # which still pass it
        if window_ladder not in (None, False):
            raise ValueError(
                f"window_ladder={window_ladder!r}: the attention-window "
                f"ladder was removed; every decode tick reads the whole "
                f"page table or cache row (pass None or False)")
        # admission-count ladder: 1,2,4,... up to max_slots. max_slots is
        # always the top rung even when it is not a power of two (e.g.
        # GENERATE_SLOTS=12 or dp-rounding 9→12): _admit_pending can group
        # up to max_slots same-bucket requests and must find a rung.
        self._n_ladder = [1]
        while self._n_ladder[-1] * 2 <= max_slots:
            self._n_ladder.append(self._n_ladder[-1] * 2)
        if self._n_ladder[-1] != max_slots:
            self._n_ladder.append(max_slots)
        self.logger = logger
        self.metrics = metrics
        # prompt-bucket fit accounting (ISSUE 3): the engine's static
        # shapes are prompt-length buckets, so its padding waste is
        # prompt tokens, not batch rows — same ShapeStats machinery
        self.shapes = ShapeStats(metrics)
        self.tracer = tracer   # None → span emission off, recorder still on
        self.recorder: FlightRecorder = recorder or FlightRecorder()
        self.slo = slo         # SLOTracker: goodput/outcome accounting
        # zero-copy data plane (ISSUE 9): the transfer coalescer packs a
        # tick/admission's half-dozen small device uploads into ONE H2D
        # transfer (bit-exact bitcast split on device — greedy output is
        # token-identical either way); coalesce_stream batches token
        # queue puts per tick instead of per token. The StagingPool here
        # is the H2D meter shared with adopted-KV uploads.
        from gofr_tpu.tpu.staging import StagingPool, TransferCoalescer
        self.coalesce_uploads = bool(coalesce_uploads)
        self.coalesce_stream = bool(coalesce_stream)
        self._h2d = StagingPool(metrics, depth=1)
        self._coalescer = TransferCoalescer(metrics, pool=self._h2d)
        # grammar-constrained decoding (ISSUE 11): compiled grammars are
        # cached per canonical source (regex / JSON schema); per-state
        # vocab bias rows are cached inside each CompiledGrammar. The
        # token byte table defaults to the raw-byte identity (ids 0..255
        # = bytes) matching the repo's byte-level BPE base; pass the
        # tokenizer's table for merged vocabularies.
        from gofr_tpu.tpu.constrain import GrammarCache, token_byte_table
        self._token_table = (list(token_table) if token_table is not None
                             else token_byte_table(
                                 vocab_size=cfg.vocab_size))
        self.grammar_cache = GrammarCache(
            self._token_table, max_entries=grammar_cache_entries)
        self._constrained_requests = 0
        self._constrained_ticks = 0

        if mesh is not None:
            from gofr_tpu.ops.quant import quantized_specs
            from gofr_tpu.parallel.sharding import (
                llama_cache_specs, llama_param_specs, prune_specs,
                shard_pytree)
            specs = quantized_specs(llama_param_specs(), params)
            self.params = shard_pytree(
                params, mesh, prune_specs(specs, mesh))
        else:
            self.params = jax.device_put(params)
        # where a one-device engine's operands live (_jit); a mesh's
        # operands carry their own shardings
        self._placement = None
        if mesh is None:
            held = jax.tree.leaves(self.params)[0].sharding
            if len(held.device_set) == 1:
                self._placement = held
        self.cache = None
        self._pool = None
        self._tables: Dict[str, Any] = {}
        if self.paged:
            from gofr_tpu.tpu.page_pool import PagePool, kv_leaf_specs
            self.pages_per_slot = self.max_len // self.kv_page
            if page_pool is not None:
                # multi-model tenancy: co-resident engines with the same
                # KV geometry address one literal pool instance — page
                # ids are interchangeable, occupancy is chip-global
                if page_pool.page != self.kv_page:
                    raise ValueError(
                        f"shared page_pool page size {page_pool.page} != "
                        f"engine kv_page {self.kv_page}")
                mine = self._leaf_specs or kv_leaf_specs(cfg)
                if (mine != page_pool.leaf_specs
                        or cfg.n_layers != page_pool.cfg.n_layers):
                    raise ValueError(
                        f"shared page_pool holds {page_pool.cfg.n_layers} "
                        f"layers of cache leaves {page_pool.leaf_specs}, "
                        f"this engine's model {cfg.n_layers} of {mine}: "
                        f"layers and leaves (names, per-token shapes, "
                        f"dtypes) must agree; heterogeneous models need "
                        f"their own pools carved from an HBMBudget")
                self._pool = page_pool
            elif self._by_kind:
                # a number of pages a kind: as given (kv_pages by kind),
                # or what every slot needs at max_len (a window kind:
                # its window and a page at each end), scaled to the
                # byte budget where one is given
                kinds = cache_kinds(cfg, self._leaf_specs)
                pages = {k.name: max_slots * self._slot_pages(k.window)
                         for k in kinds}
                if isinstance(kv_pages, dict):
                    pages = {k.name: int(kv_pages[k.name]) for k in kinds}
                elif kv_pages is not None:
                    raise ValueError(
                        f"model_module {named}: kv_pages is a number of "
                        f"pages a cache kind, {sorted(pages)}")
                elif kv_pool_bytes is not None:
                    # the per-slot kinds' state is not to scale: the
                    # pages get the budget less it
                    state = max_slots * sum(
                        PagePool.slot_bytes_of(k)
                        for k in slot_kinds(self._leaf_specs))
                    full = sum(pages[k.name] * PagePool._kind_page_bytes(
                        k, self.kv_page) for k in kinds)
                    share = min(1.0, max(0, int(kv_pool_bytes) - state)
                                / full)
                    pages = {name: max(1, int(n * share))
                             for name, n in pages.items()}
                self._pool = PagePool(cfg, page=self.kv_page,
                                      num_pages=pages, mesh=mesh,
                                      metrics=metrics,
                                      leaf_specs=self._leaf_specs,
                                      slots=max_slots)
            elif kv_pages is not None:
                self._pool = PagePool(cfg, page=self.kv_page,
                                      num_pages=int(kv_pages), mesh=mesh,
                                      metrics=metrics,
                                      leaf_specs=self._leaf_specs)
            elif kv_pool_bytes is not None:
                self._pool = PagePool(cfg, page=self.kv_page,
                                      budget_bytes=int(kv_pool_bytes),
                                      mesh=mesh, metrics=metrics,
                                      leaf_specs=self._leaf_specs)
            else:
                # capacity parity with the dense cache by default; real
                # deployments size by HBM budget and admit MORE slots than
                # dense could (slots now cost actual tokens, not max_len)
                self._pool = PagePool(
                    cfg, page=self.kv_page,
                    num_pages=max_slots * self.pages_per_slot, mesh=mesh,
                    metrics=metrics, leaf_specs=self._leaf_specs)
            # reserve watermark: pages admission must leave free for
            # in-flight decode growth of already-admitted slots, of each
            # cache kind (by default an eighth of the smallest kind)
            self._kv_reserve = (
                int(kv_page_reserve) if kv_page_reserve is not None
                else min(max_slots, min(
                    kind.num_pages
                    for kind in self._pool.kinds.values()) // 8))
            # per-slot page tables, one a cache kind (host master copies;
            # the device upload is cached against _table_version)
            self._fresh_tables()
            self._table_version = 0
            self._table_cache: Optional[Tuple[int, Any]] = None
            self._page_stalls = 0
            # shared-pool reset fan-out: when a co-resident engine rebuilds
            # the pool, this engine's page ids dangle — _on_pool_reset
            # fails outstanding work and re-sentinels the table
            self._in_pool_reset = False
            self._pool.subscribe(self._on_pool_reset)
        elif mesh is not None:
            from gofr_tpu.parallel.sharding import (  # noqa: F811
                llama_cache_specs, prune_specs, shard_pytree)
            cache = llama.init_cache(cfg, max_slots, self.max_len)
            self.cache = shard_pytree(
                cache, mesh,
                prune_specs(llama_cache_specs(kv_int8=cfg.kv_int8), mesh))
        else:
            self.cache = jax.device_put(
                llama.init_cache(cfg, max_slots, self.max_len))
        # fused ragged paged attention (ISSUE 13): "auto" takes the
        # Pallas kernel where it can win and is known to compile — TPU
        # devices, a geometry Mosaic tiles, no mesh (a pallas_call has no
        # partitioning rule, so GSPMD would replicate the whole pool into
        # it); "on" forces it everywhere — interpret mode off-TPU, which
        # is how CPU tier-1 tests and benches exercise the kernel path,
        # and the compiler's own error on a TPU geometry it rejects.
        self.ragged_attn = str(ragged_attn).lower()
        if self.ragged_attn not in ("auto", "on", "off"):
            raise ValueError(
                f"ragged_attn must be auto|on|off, got {ragged_attn!r}")
        if self.ragged_attn == "on" and not self.paged:
            raise ValueError("ragged_attn='on' requires paged_kv=True "
                             "(the kernel walks the page pool)")
        self._ragged, self.attn_reason = self._resolve_ragged()
        self._walk = self._ragged_walk() if self._ragged else None
        if logger is not None:
            logger.info("engine %s attention: %s", self.model_name,
                        self.attention_paths())
        self._lay_out_weights()
        if logger is not None:
            logger.info("engine %s weights: %s", self.model_name,
                        self._weights)
        self.cache_len = jnp.zeros((max_slots,), jnp.int32)
        self.last_token = jnp.zeros((max_slots,), jnp.int32)
        # per-slot sampling state (ops/sampling): scattered at admission,
        # carried/advanced by the sampled decode executable
        self.temps = jnp.zeros((max_slots,), jnp.float32)
        self.top_ks = jnp.zeros((max_slots,), jnp.int32)
        self.top_ps = jnp.ones((max_slots,), jnp.float32)
        self.sample_keys = jnp.zeros((max_slots, 2), jnp.uint32)

        # -- speculative draft-verify decode (ISSUE 7) -----------------------
        self.spec = draft_cfg is not None and draft_params is not None
        self.spec_gamma = max(1, int(spec_gamma))
        self.draft_cfg = draft_cfg
        self.draft_params = None
        self._draft_cache = None
        self._g_ladder: List[int] = []
        if self.spec:
            if mesh is not None:
                raise ValueError(
                    "speculative decode does not compose with a mesh yet "
                    "(the draft has no sharding specs)")
            if getattr(draft_cfg, "vocab_size", None) != cfg.vocab_size:
                raise ValueError(
                    "draft and target models must share a vocabulary "
                    f"({getattr(draft_cfg, 'vocab_size', None)} vs "
                    f"{cfg.vocab_size})")
            self.draft_params = jax.device_put(draft_params)
            # the draft cache is always dense: the draft is small, and a
            # dense (max_slots, max_len) row per slot keeps draft decode
            # independent of the target's paging scheme. Both models share
            # one cache_len — the draft always prefills the full prompt,
            # so their committed lengths never diverge.
            self._draft_cache = jax.device_put(
                llama.init_cache(draft_cfg, max_slots, self.max_len))
            self._g_ladder = [1]
            while self._g_ladder[-1] * 2 <= self.spec_gamma:
                self._g_ladder.append(self._g_ladder[-1] * 2)
            if self._g_ladder[-1] != self.spec_gamma:
                self._g_ladder.append(self.spec_gamma)
        self._gamma_cap = self.spec_gamma if self.spec else 0
        self._spec_ticks = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_window_proposed = 0
        self._spec_window_accepted = 0

        self._slots = [_Slot() for _ in range(max_slots)]
        self._free: List[int] = list(range(max_slots))
        # SLO-class weighted-fair admission (ISSUE 7): the pending queue
        # pops by per-class virtual time, so interactive traffic drains
        # ahead of batch in proportion to its weight — the per-class tick
        # budget falls out of admission (every admitted slot rides every
        # tick), so WFQ at this gate IS the tick-share mechanism
        self.class_weights = dict(class_weights or DEFAULT_CLASS_WEIGHTS)
        self._pending: ClassQueues = ClassQueues(self.class_weights)
        self._task: Optional[asyncio.Task] = None
        self._wake = asyncio.Event()
        self._steps = 0
        self._prefills = 0
        self.max_inflight_ticks = max(1, int(max_inflight_ticks))
        self._publishq: "deque" = deque()   # FIFO of _Fetch entries
        # page-gated admissions (paged path): requests that fit a slot but
        # not the pool's free pages wait here, FIFO ahead of _pending.
        # Bounded: past the cap the deepest class sheds its own newest
        # entry first (strictly within class before cross-class)
        self._overflow: "deque" = deque()
        self._overflow_cap = max(16, 4 * max_slots)
        self._shed_by_class: Dict[str, int] = {}
        self._ticks_inflight = 0
        self._cancelled_queues: set = set()  # ids of abandoned stream queues
        # chaos plane (ISSUE 14): idempotent-adopt ledger (dedupe id →
        # (stored_at, stream)), brownout rung applied by slo.BrownoutLadder
        # via set_brownout, and poison-slot quarantine accounting
        self._adopt_ledger: Dict[str, Tuple[float, "TokenStream"]] = {}
        self._adopt_dedup_hits = 0
        self._brownout = 0
        self._quarantined: Dict[str, int] = {}
        # the loop clock (always on): the serving thread's phases, wall
        # and thread CPU, read by stats()["loop"], the profiler's trace
        # and the sampled tick ring; and the device's timeline beside it,
        # fed where a fetch lands, read by stats()["timeline"]
        self._annotation = jax.profiler.TraceAnnotation
        self._clock = _LoopClock(self._annotation)
        self._timeline = _Timeline()
        # continuous telemetry plane (ISSUE 16): when a TimeSeriesStore is
        # attached, every Nth decode tick hands it that tick's share of
        # the loop clock. Unsampled ticks pay one attribute load plus a
        # modulo — nothing else changes when telemetry is off (None).
        self.telemetry = None
        self._tick_seq = 0
        self._tick_every = 64
        # operating-point plane (ISSUE 19): every serving knob the
        # auto-tuner may move is mutated ONLY through
        # apply_operating_point (graftcheck GT014). slots_cap is an
        # admission cap below max_slots — the slot arrays and compiled
        # executables stay sized by max_slots (not live-resizable), but
        # admission stops claiming slots past the cap, which is the
        # live-tunable half of the slots×K tradeoff.
        self.slots_cap: Optional[int] = None
        self._op_source = "seed"
        self._op_generation = 0
        self._op_applied_at: Optional[float] = None
        # shape signatures (prompt_buckets, steps_per_tick) whose
        # executables are known compiled — the seed shape is, by the
        # warmup/lazy-compile contract that predates this plane
        self._op_prewarmed = {(self.prompt_buckets, self.steps_per_tick)}
        # executable-compile accounting: every jit-cache miss charges
        # one compile as warmup-class (inside warmup()/prewarm) or
        # serving-class (on the serving path) — the engine-side twin of
        # the executor's CompileLedger.serving_compiles signal
        self._warming = 0
        self._compile_events: List[Tuple[float, str, str]] = []
        self._compiles_by_class = {"warmup": 0, "serving": 0}

        # prefill keyed (nb, bucket, biased); the biased form takes the
        # grammar's start-state rows as one more operand (ISSUE 11)
        self._prefill_fns: Dict[Tuple[int, int, bool], Any] = {}
        self._insert_fns: Dict[Tuple[int, int], Any] = {}
        # paged-path insert keyed (nb, bucket, plen)
        self._insert_paged_fns: Dict[Tuple[int, int, int], Any] = {}
        # every decode tick, keyed (k, sampled, biased). Unconstrained
        # serving never builds a biased key, so its warm keys are its own
        self._tick_fns: Dict[Tuple[int, bool, bool], Any] = {}
        # prefix KV reuse (ISSUE 4): page-granular prefix store + the
        # suffix-only prefill/insert executable families keyed
        # (nb, prefix_pages, suffix_bucket). The prefix-pages ladder
        # (1,2,4,... plus the max) bounds the executable set; a cached
        # prefix rounds DOWN to a rung and the remainder rides the suffix.
        self._suffix_prefill_fns: Dict[Tuple[int, int, int], Any] = {}
        self._suffix_insert_fns: Dict[Tuple[int, int, int], Any] = {}
        # speculative-decode families: one fused draft-propose/target-verify
        # executable per γ rung — the "(nb, γ) verify rung" of ISSUE 7 —
        # plus KV-only draft prefill/insert per (nb, bucket)
        self._spec_fns: Dict[int, Any] = {}
        self._draft_prefill_fns: Dict[Tuple[int, int], Any] = {}
        self._draft_insert_fns: Dict[Tuple[int, int], Any] = {}
        # disaggregated serving (ISSUE 8): page-adoption scatter keyed by
        # page count, plus export/adopt counters for the handoff proof
        self._adopt_fns: Dict[int, Any] = {}
        self._kv_exports = 0
        self._kv_adoptions = 0
        # live decode→decode migration (ISSUE 12): sessions shipped out
        # mid-stream and sessions resumed from a peer's snapshot
        self._session_exports = 0
        self._session_adoptions = 0
        # device-time attribution (ISSUE 10): a program's interval on the
        # timeline (_Timeline: landing to landing) split evenly across
        # its participating slots and charged to {model, slo class}. The
        # intervals are disjoint, so the shares sum to at most wall time.
        self._device_seconds: Dict[Tuple[str, str], float] = {}
        # executable-level roofline attribution (ISSUE 17): the same
        # interval, keyed by compiled-executable family
        # instead of slo class — both views share one charge helper so
        # their totals agree by construction
        self.exec_ledger = ExecutableLedger(metrics=metrics)
        # workload capture (ISSUE 17): a TrafficRecorder attached via
        # attach_workload; None keeps admission byte-identical
        self.workload = None
        self._prefill_rows = 0            # rows of the admission groups
        self._prefill_bucket_tokens = 0   # bucket rows*cols dispatched to
        self._prefill_real_tokens = 0     # prefill vs real prompt tokens
        self._prefix = None
        self._p_ladder: List[int] = []
        if prefix_cache and self.prompt_buckets:
            from gofr_tpu.tpu.prefix_cache import PrefixStore
            if self.paged:
                # unified pool: prefix pages ARE decode pages, so the
                # prefix page size must be the pool page size (a hit is a
                # page-table entry, not a copy)
                prefix_page = self.kv_page
            max_pages = max(self.prompt_buckets) // prefix_page
            if max_pages > 0:
                self._p_ladder = [1]
                while self._p_ladder[-1] * 2 <= max_pages:
                    self._p_ladder.append(self._p_ladder[-1] * 2)
                if self._p_ladder[-1] != max_pages:
                    self._p_ladder.append(max_pages)
                self._prefix = PrefixStore(
                    cfg, page=prefix_page,
                    budget_bytes=prefix_cache_bytes,
                    max_pages=max_pages, pool=self._pool,
                    mesh=mesh, metrics=metrics)
            elif logger is not None:
                logger.warn(
                    "prefix cache disabled: page size %d exceeds the "
                    "largest prompt bucket %d", prefix_page,
                    max(self.prompt_buckets))

    # -- compiled steps -----------------------------------------------------
    @property
    def _kv(self):
        """The cache leaves the donating executables consume and return:
        the pool's on the paged path, the dense cache otherwise. The one
        name both kinds (and ``prewarm_operating_point``'s dummy state)
        are read and written back by."""
        return self._pool.leaves if self.paged else self.cache

    @_kv.setter
    def _kv(self, leaves) -> None:
        if self.paged:
            self._pool.leaves = leaves
        else:
            self.cache = leaves

    def _jit(self, fn, donate_argnums=()):
        """``jax.jit`` for an executable of the engine. On one device
        every operand is stated to live there, so a program is compiled
        once whatever its operands' history: ``jit`` keeps a compiled
        program per set of committed operands, a re-laid weight is
        committed and so is every output of a program that took one,
        while fresh slot state and uploads are not, and the second call
        of a tick would otherwise compile it again, on the serving
        path. A weight's own layout is taken as it is found."""
        return self._jax.jit(fn, donate_argnums=donate_argnums,
                             in_shardings=self._placement)

    def _prefill_fn(self, nb: int, lb: int, biased: bool = False):
        """Pure-compute prompt forward for ``nb`` prompts of bucket ``lb``:
        (params, tokens (nb,lb), lengths (nb,), temps, top_ks, top_ps,
        seeds) → (first_tokens (nb,), small cache dict (leaves
        (L,nb,lb,...) — k/v plus int8 scale planes when cfg.kv_int8),
        keys (nb,2)). The first token is sampled per-row (greedy rows
        resolve to argmax in-program, ops/sampling); ``keys`` are the
        advanced per-row PRNG keys decode continues from. No cache
        involvement, so it can be dispatched while decode ticks are in
        flight.

        ``biased`` (constrained requests, ISSUE 11) takes one more
        operand, a per-row additive logit-bias matrix (nb, vocab) applied
        before the first token is sampled — the grammar's start-state
        mask steers the first token exactly like every decode step
        after it."""
        fn = self._prefill_fns.get((nb, lb, biased))
        if fn is None:
            jax, jnp, llama, cfg = (self._jax, self._jnp, self._llama,
                                    self.cfg)
            from gofr_tpu.ops.sampling import sample_batch

            def prefill_batch(params, tokens, lengths, temps, top_ks,
                              top_ps, seeds, bias=None):
                small = llama.init_cache(cfg, nb, lb)
                logits, small, _ = llama.prefill(params, cfg, tokens, small,
                                                 lengths=lengths)
                keys = jax.vmap(jax.random.PRNGKey)(seeds)
                if biased:
                    logits = logits + bias
                first, keys = sample_batch(logits, temps, top_ks, top_ps,
                                           keys)
                return first, small, keys

            fn = self._jit(prefill_batch)
            self._prefill_fns[(nb, lb, biased)] = fn
            self._note_compile("prefill_bias" if biased else "prefill",
                               (nb, lb))
        return fn

    def _padding_group(self, nb: int, lb: int) -> Dict[str, Any]:
        """An admission group of ``nb`` x ``lb`` that is all padding,
        under the names ``_admit_pending`` uploads a real one by: what
        the warm-ups compile prefill and insert with. Every row's slot
        index is ``max_slots`` and every page id the sentinel, so the
        insert drops all of it."""
        jnp = self._jnp
        group = dict(padded=jnp.zeros((nb, lb), jnp.int32),
                     lengths=jnp.ones((nb,), jnp.int32),
                     slots=jnp.full((nb,), self.max_slots, jnp.int32),
                     temps=jnp.zeros((nb,), jnp.float32),
                     top_ks=jnp.zeros((nb,), jnp.int32),
                     top_ps=jnp.ones((nb,), jnp.float32),
                     seeds=jnp.zeros((nb,), jnp.uint32))
        if self.paged:
            ids = (nb * (lb // self.kv_page),)
            group["flat_ids"] = self._pool.as_leaves(
                {name: jnp.full(ids, kind.num_pages, jnp.int32)
                 for name, kind in self._pool.kinds.items()})
        return group

    def _run_prefill(self, nb: int, lb: int, dev: Dict[str, Any]):
        """Call the prefill of an ``nb`` x ``lb`` group on its device
        arrays ``dev``: the biased one where the group carries grammar
        rows (``dev["bias"]``). Returns (first, small, keys)."""
        bias = dev.get("bias")
        return self._prefill_fn(nb, lb, bias is not None)(
            self.params, dev["padded"], dev["lengths"], dev["temps"],
            dev["top_ks"], dev["top_ps"], dev["seeds"],
            *([] if bias is None else [bias]))

    def _run_insert(self, nb: int, lb: int, plen: int, dev: Dict[str, Any],
                    first, small, keys, state=None) -> None:
        """Call the insert that publishes a prefill (``first``, ``small``,
        ``keys``) of the group ``dev`` into the cache and the claimed
        rows' slot state, and write its outputs back: into the engine's
        own state, or into a dummy ``state`` (``_run_tick``). On the paged
        path the caller holds the pool lock across the prefill and this
        (a suffix prefill reads the leaves this donates)."""
        s = self if state is None else state
        fn = (self._insert_paged_fn(nb, lb, plen) if self.paged
              else self._insert_fn(nb, lb))
        page_ids = [dev["flat_ids"]] if self.paged else []
        (s._kv, s.cache_len, s.last_token, s.temps, s.top_ks, s.top_ps,
         s.sample_keys) = fn(
            s._kv, small, *page_ids, dev["slots"], dev["lengths"], first,
            s.cache_len, s.last_token, s.temps, s.top_ks, s.top_ps,
            s.sample_keys, dev["temps"], dev["top_ks"], dev["top_ps"], keys)

    def _insert_fn(self, nb: int, lb: int):
        """Cheap scatter publishing a prefill into the big cache, including
        the claimed rows' sampling state. Padding entries carry slot index
        ``max_slots`` (out of bounds → dropped)."""
        fn = self._insert_fns.get((nb, lb))
        if fn is None:

            def insert(cache, small, slots, lengths, first,
                       cache_len, last_token, temps, top_ks, top_ps,
                       sample_keys, new_t, new_k, new_p, new_keys):
                # uniform over cache leaves: k/v (L,B,T,H,D) and — int8
                # caches — scale planes (L,B,T,H) share the (L,B,T) prefix
                cache = {name: cache[name].at[:, slots, :lb].set(
                    small[name], mode="drop") for name in cache}
                cache_len = cache_len.at[slots].set(lengths, mode="drop")
                last_token = last_token.at[slots].set(first, mode="drop")
                temps = temps.at[slots].set(new_t, mode="drop")
                top_ks = top_ks.at[slots].set(new_k, mode="drop")
                top_ps = top_ps.at[slots].set(new_p, mode="drop")
                sample_keys = sample_keys.at[slots].set(new_keys,
                                                        mode="drop")
                return (cache, cache_len, last_token, temps,
                        top_ks, top_ps, sample_keys)

            fn = self._jit(insert, donate_argnums=(0, 5, 6, 7, 8, 9, 10))
            self._insert_fns[(nb, lb)] = fn
            self._note_compile("insert", (nb, lb))
        return fn

    def _suffix_prefill_fn(self, nb: int, p: int, lb: int):
        """Suffix-only prompt forward (prefix KV reuse): gathers ``p``
        cached pages per row from the prefix pool and runs the llama
        prefill over only the suffix bucket ``lb``, with RoPE positions
        offset by the static prefix length. Same contract as
        ``_prefill_fn`` otherwise — (first_tokens, suffix small cache,
        advanced keys). The pool is read, never written."""
        fn = self._suffix_prefill_fns.get((nb, p, lb))
        if fn is None:
            jax, llama, cfg = self._jax, self._llama, self.cfg
            from gofr_tpu.ops.sampling import sample_batch
            plen = p * self._prefix.page

            def suffix_prefill(params, pool, page_ids, tokens, lengths,
                               temps, top_ks, top_ps, seeds):
                # (L, N, page, ...) pages -> (L, nb, plen, ...) prefix KV
                prefix = {
                    name: pool[name][:, page_ids].reshape(
                        pool[name].shape[0], nb, plen,
                        *pool[name].shape[3:])
                    for name in pool}
                small = llama.init_cache(cfg, nb, lb)
                logits, small, _ = llama.prefill(
                    params, cfg, tokens, small, lengths=lengths,
                    prefix=prefix, prefix_len=plen)
                keys = jax.vmap(jax.random.PRNGKey)(seeds)
                first, keys = sample_batch(logits, temps, top_ks, top_ps,
                                           keys)
                return first, small, keys

            fn = self._jit(suffix_prefill)
            self._suffix_prefill_fns[(nb, p, lb)] = fn
            self._note_compile("suffix_prefill", (nb, p, lb))
        return fn

    def _suffix_insert_fn(self, nb: int, p: int, lb: int):
        """Widened insert scatter for the suffix path: writes the ``p``
        prefix pages into cache rows [0, plen) AND the fresh suffix KV
        into [plen, plen+lb) for each claimed slot, in one executable.
        cache_len becomes prefix + suffix length. The pool argument is
        never donated (in-flight suffix prefills may still read it)."""
        fn = self._suffix_insert_fns.get((nb, p, lb))
        if fn is None:
            plen = p * self._prefix.page

            def insert(cache, pool, page_ids, small, slots, lengths, first,
                       cache_len, last_token, temps, top_ks, top_ps,
                       sample_keys, new_t, new_k, new_p, new_keys):
                pref = {
                    name: pool[name][:, page_ids].reshape(
                        pool[name].shape[0], nb, plen,
                        *pool[name].shape[3:])
                    for name in pool}
                cache = {name: cache[name]
                         .at[:, slots, :plen].set(pref[name], mode="drop")
                         .at[:, slots, plen:plen + lb].set(
                             small[name], mode="drop")
                         for name in cache}
                cache_len = cache_len.at[slots].set(plen + lengths,
                                                    mode="drop")
                last_token = last_token.at[slots].set(first, mode="drop")
                temps = temps.at[slots].set(new_t, mode="drop")
                top_ks = top_ks.at[slots].set(new_k, mode="drop")
                top_ps = top_ps.at[slots].set(new_p, mode="drop")
                sample_keys = sample_keys.at[slots].set(new_keys,
                                                        mode="drop")
                return (cache, cache_len, last_token, temps,
                        top_ks, top_ps, sample_keys)

            fn = self._jit(insert,
                         donate_argnums=(0, 7, 8, 9, 10, 11, 12))
            self._suffix_insert_fns[(nb, p, lb)] = fn
            self._note_compile("suffix_insert", (nb, p, lb))
        return fn

    def _tick_operands(self, sampled: bool, biased: bool) -> Tuple[str, ...]:
        """A decode tick's operands in call order. The order is written
        here and nowhere else: ``_tick_fn`` unpacks and donates by it,
        ``_run_tick`` packs by it."""
        return (("params", "token", "cache")
                + (("table",) if self.paged else ())
                + ("cache_len", "active")
                + (("bias",) if biased else ())
                + (("temps", "top_ks", "top_ps", "keys") if sampled else ()))

    def _tick_names(self, k: int, biased: bool) -> Tuple[str, str]:
        """(compile-ledger kind, roofline-ledger family) of a tick:
        ``decode_paged_bias``, ``decode_paged_bias[k=1]``. Device time
        lands on the granularity the jit cache is keyed by."""
        kind = ("decode" + ("_paged" if self.paged else "")
                + ("_bias" if biased else ""))
        return kind, f"{kind}[k={k}]"

    def _tick_fn(self, k_steps: int, sampled: bool, biased: bool):
        """Decode-tick executable: ``k_steps`` fused steps in one
        ``lax.scan``, built from three parts (``_tick_program``).

        The step: on the dense cache ``decode_step`` over each slot's
        whole cache row; on the pool (ISSUE 6) ``decode_step_paged``
        through the whole ``(max_slots, pages_per_slot)`` page table
        (with the ragged kernel active the step attends pool pages in
        place). Inactive rows scatter to the sentinel page id and drop.
        A module that declares ``STEP_COUNTERS`` returns their sums over
        the K steps as one more output; the others' ticks are the
        program they always were.

        The choice: argmax (the greedy serving hot path), or
        ``sample_batch``, which additionally carries per-slot (temps,
        top_ks, top_ps, keys) and advances keys only for rows active in
        the tick, so a slot's token stream is a pure function of its seed
        (ops/sampling).

        The bias (constrained decoding, ISSUE 11): an additive
        (max_slots, vocab) float32 matrix — the grammar masks, 0 for
        allowed tokens and NEG_BIAS for the rest — applied before the
        choice. The active mask then arrives as int32 so mask + bias
        share one coalesced H2D frame; the executable converts to bool
        in-program (bit-exact). Constrained slots only ride k=1 ticks
        (their mask is valid for exactly the next position), so
        ``k_steps`` is 1 on the serving path.

        Operands: ``_tick_operands``. Outputs: (tokens (K, B), cache,
        cache_len[, keys][, counters])."""
        key = (k_steps, sampled, biased)
        if key not in self._tick_fns:
            if key in self._decided:
                # the steady tick was compiled when it decided the
                # weights' layouts: that program is the tick
                self._tick_fns[key] = self._decided.pop(key)
            else:
                tick, donated = self._tick_program(*key)
                self._tick_fns[key] = self._jit(tick,
                                                donate_argnums=donated)
            self._note_compile(self._tick_names(k_steps, biased)[0],
                               (k_steps, sampled))
        return self._tick_fns[key]

    def _lay_out_weights(self) -> None:
        """Put each leaf of ``self.params`` into the layout the steady
        decode tick reads it in, once, before anything is compiled.

        The steady tick (greedy, the widest K) is
        compiled on shapes alone with the weights' layouts left to the
        compiler (``weight_formats``); each leaf whose layout differs is
        put into the one the tick stated, and every executable built
        afterwards is compiled for the leaves as they then are: ``jit``
        takes a committed array's layout as it finds it. The steady tick
        decides alone; prefill, insert, the shorter and the sampled
        ticks take what they are given. Shapes, dtypes, names and values
        stay, and the caller's tree is neither consumed nor donated: a
        re-laid leaf is a new array, and the engine holds the old one
        no longer. A mesh engine goes through the same
        call with its ``NamedSharding``s; the draft's weights are not
        the steady tick's operands and keep their layouts. Where the
        tick wants the default layouts (the CPU's compiler does) nothing
        moves and every program is the one it was. On one device the
        program that decided is kept and is the steady tick when
        ``_tick_fn`` is asked for it: one program fewer to trace and
        load at warm-up, and the one program in which every layout is
        the compiler's own choice.

        ``stats()["weights"]``: the leaves and bytes moved, and the
        deciding program's temporaries (``memory_analysis()``)."""
        jax, jnp = self._jax, self._jnp

        def abstract(leaf):
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype,
                sharding=self._placement or leaf.sharding)

        key = (self._k_ladder[-1], False, False)
        tick, donated = self._tick_program(*key)
        row = (self.max_slots,)
        operands = dict(
            params=jax.tree.map(abstract, self.params),
            token=jax.ShapeDtypeStruct(row, jnp.int32),
            cache=jax.tree.map(abstract, self._kv),
            table=(jax.tree.map(
                lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype),
                self._empty_table()) if self.paged else None),
            cache_len=jax.ShapeDtypeStruct(row, jnp.int32),
            active=jax.ShapeDtypeStruct(row, jnp.bool_))
        formats, temp_bytes, compiled = weight_formats(
            tick, tuple(operands[name]
                        for name in self._tick_operands(False, False)),
            donated)
        # on one device that program is the steady tick (_tick_fn). A
        # mesh's is not: it fixed a sharding for every operand that came
        # without one, and state a later program returns has another
        self._decided = {key: compiled} if self._placement else {}
        leaves, treedef = jax.tree.flatten(self.params)
        self._weights = {"relaid_leaves": 0, "relaid_bytes": 0,
                         "temp_bytes": temp_bytes}
        differ = {i: wanted for i, wanted
                  in enumerate(treedef.flatten_up_to(formats))
                  if leaves[i].format.layout != wanted.layout}
        puts = _relay([leaves[i] for i in differ],
                      differ.values()) if differ else ()
        for (i, wanted), put in zip(differ.items(), puts):
            if put.format.layout == wanted.layout:
                self._weights["relaid_leaves"] += 1
                self._weights["relaid_bytes"] += put.nbytes
                leaves[i] = put
            else:
                # it says one layout and may lie in another: not served
                # from, and the deciding program, which wants it, is not
                # the tick
                self._decided = {}
                if self.logger is not None:
                    self.logger.warn(
                        "engine %s weights: a leaf asked for as %s came "
                        "back as %s and keeps its layout", self.model_name,
                        wanted.layout, put.format.layout)
        self.params = treedef.unflatten(leaves)

    def _tick_program(self, k_steps: int, sampled: bool, biased: bool):
        """``_tick_fn``'s tick before it is jitted, and the positions of
        the operands it donates: what ``_lay_out_weights`` compiles with
        the weights' layouts left open."""
        jnp, llama, cfg = self._jnp, self._llama, self.cfg
        from jax import lax

        from gofr_tpu.ops.sampling import sample_batch
        names = self._tick_operands(sampled, biased)
        counted = self.paged and bool(self._step_counters)
        if self.paged:
            step_kw = {"ragged": True} if self._ragged else {}
            if counted:
                step_kw["counters"] = True

            def step(params, token, pool, table, cache_len, active):
                """(logits, pool, new_len, what the step counted: the
                per-step sums the scan stacks, () for a module that
                declares no STEP_COUNTERS)."""
                out = llama.decode_step_paged(
                    params, cfg, token, pool, table, cache_len, active,
                    **step_kw)
                return out if counted else out + ((),)
        else:
            def step(params, token, cache, table, cache_len, active):
                return llama.decode_step(
                    params, cfg, token, cache, cache_len) + ((),)

        def tick(*operands):
            o = dict(zip(names, operands))
            params, table = o["params"], o.get("table")
            active = o["active"].astype(bool) if biased else o["active"]

            def one(carry, _):
                token, cache, cache_len, *keys = carry
                logits, cache, new_len, counts = step(
                    params, token, cache, table, cache_len, active)
                if biased:
                    logits = logits + o["bias"]
                if sampled:
                    next_token, new_keys = sample_batch(
                        logits, o["temps"], o["top_ks"], o["top_ps"],
                        keys[0])
                else:
                    next_token = logits.argmax(axis=-1)
                next_token = next_token.astype(token.dtype)
                # freeze inactive slots: cache_len stays put and the
                # carried token is unchanged (ADVICE r1: no unbounded
                # cache_len growth on idle slots)
                new_len = jnp.where(active, new_len, cache_len)
                next_token = jnp.where(active, next_token, token)
                if sampled:
                    # inactive rows keep their key: emitted-token index
                    # == number of participating steps, so sequences
                    # are seed-deterministic under any tick batching
                    keys = [jnp.where(active[:, None], new_keys,
                                      keys[0])]
                return (next_token, cache, new_len, *keys), (next_token,
                                                             counts)

            carry = (o["token"], o["cache"], o["cache_len"]) + (
                (o["keys"],) if sampled else ())
            (_, *state), (tokens, counts) = lax.scan(
                one, carry, None, length=k_steps)
            outs = (tokens, *state)
            return outs + (counts.sum(axis=0),) if counted else outs

        # the benchmark's trace readers find the decode executables
        # by this name (jit_decode_k...)
        tick.__name__ = "decode_k_sampled" if sampled else "decode_k"
        donated = ("cache", "cache_len") + (("keys",) if sampled else ())
        return tick, tuple(names.index(n) for n in donated)

    def _run_tick(self, k: int, sampled: bool, active, bias=None,
                  state=None):
        """Call the tick ``(k, sampled, bias given)`` and write its
        outputs back: the one place a decode executable is called.
        ``active`` is the device mask (int32 beside a ``bias``, see
        ``_tick_fn``). Without ``state`` the tick runs on the engine's own
        slot state — on the pool under its lock (co-resident engines'
        donations must not interleave with ours) and through the slots'
        page table; ``prewarm_operating_point`` passes a dummy ``state``
        (_kv, cache_len, last_token, temps, top_ks, top_ps,
        sample_keys), which compiles the same executable and touches
        nothing of the engine's. Returns (tokens (K, B) on the device,
        the step counters' sums as a 1-tuple, or ())."""
        biased = bias is not None
        fn = self._tick_fn(k, sampled, biased)
        own = state is None
        s = self if own else state
        with self._pool.lock if own and self.paged else nullcontext():
            table = None
            if self.paged:
                table = self._table_dev() if own else self._empty_table()
            operands = dict(
                params=self.params, token=s.last_token, cache=s._kv,
                table=table, cache_len=s.cache_len, active=active, bias=bias,
                temps=s.temps, top_ks=s.top_ks, top_ps=s.top_ps,
                keys=s.sample_keys)
            out = fn(*(operands[name]
                       for name in self._tick_operands(sampled, biased)))
            tokens_dev, s._kv, s.cache_len = out[:3]
        rest = out[3:]
        if sampled:
            s.sample_keys, rest = rest[0], rest[1:]
        s.last_token = tokens_dev[-1]
        return tokens_dev, rest

    def _insert_paged_fn(self, nb: int, lb: int, plen: int):
        """Paged-path insert: scatters a prefill's small cache directly
        into freshly allocated pool pages (no dense cache exists). The
        small cache rows [0, lb) are reshaped into ``lb // kv_page``
        page-sized chunks per row and scattered to the flat page-id
        vector (row-major (nb, n_pages)); sentinel ids drop. ``plen`` is
        the static prefix length already resident in pool pages (0 for
        full prefills) — only cache_len accounting needs it, the prefix
        KV itself is never copied (the zero-copy admission property).
        The pool IS donated: the engine loop serializes pool-aliasing
        dispatches, and PjRt usage-events order in-flight non-donating
        readers (suffix prefills) before the aliased write."""
        fn = self._insert_paged_fns.get((nb, lb, plen))
        if fn is None:
            page = self.kv_page
            n_pages = lb // page

            def insert(pool, small, flat_ids, slots, lengths, first,
                       cache_len, last_token, temps, top_ks, top_ps,
                       sample_keys, new_t, new_k, new_p, new_keys):
                # small leaves: (L, nb, lb, ...) -> (L, nb*n_pages, page,
                # ...); pool leaves: (L, N, page, ...). One scatter per
                # leaf publishes the whole group's KV into its pages,
                # a kind at a time; a window kind's ids hold the sentinel
                # for the columns behind its window: nothing older is
                # written
                def scatter(leaves, small, ids):
                    return {name: leaves[name].at[:, ids].set(
                        small[name].reshape(
                            small[name].shape[0], nb * n_pages, page,
                            *small[name].shape[3:]),
                        mode="drop") for name in leaves}

                held = pool
                pool = self._pool.map_kinds(scatter, pool, small,
                                            flat_ids)
                # a per-slot kind: the group's states whole into the
                # claimed slots' rows (a padding row's slot is
                # max_slots: dropped), so a slot claimed again holds
                # nothing of its last tenant
                for name in self._pool.slot_kinds:
                    pool[name] = {
                        leaf: rows.at[:, slots].set(
                            small[name][leaf].astype(rows.dtype),
                            mode="drop")
                        for leaf, rows in held[name].items()}
                cache_len = cache_len.at[slots].set(plen + lengths,
                                                    mode="drop")
                last_token = last_token.at[slots].set(first, mode="drop")
                temps = temps.at[slots].set(new_t, mode="drop")
                top_ks = top_ks.at[slots].set(new_k, mode="drop")
                top_ps = top_ps.at[slots].set(new_p, mode="drop")
                sample_keys = sample_keys.at[slots].set(new_keys,
                                                        mode="drop")
                return (pool, cache_len, last_token, temps,
                        top_ks, top_ps, sample_keys)

            fn = self._jit(insert, donate_argnums=(0, 6, 7, 8, 9, 10, 11))
            self._insert_paged_fns[(nb, lb, plen)] = fn
            self._note_compile("insert_paged", (nb, lb, plen))
        return fn

    def _adopt_fn(self, n_pages: int):
        """Disaggregated handoff (ISSUE 8): scatter ``n_pages`` migrated
        KV pages — shipped by a prefill replica, already page-shaped on
        host — into this engine's pool plus the adopting slot's device
        rows (cache_len, last_token, sampling state), in one donating
        executable per page count. No prompt forward runs here: adoption
        is a memcpy-class operation, which is what keeps
        ``prefill_bucket_tokens`` at zero for migrated requests."""
        fn = self._adopt_fns.get(n_pages)
        if fn is None:

            def adopt(pool, pages, ids, slot, length, first, cache_len,
                      last_token, temps, top_ks, top_ps, sample_keys,
                      new_t, new_k, new_p, new_key):
                pool = {name: pool[name].at[:, ids].set(pages[name])
                        for name in pool}
                cache_len = cache_len.at[slot].set(length)
                last_token = last_token.at[slot].set(first)
                temps = temps.at[slot].set(new_t)
                top_ks = top_ks.at[slot].set(new_k)
                top_ps = top_ps.at[slot].set(new_p)
                sample_keys = sample_keys.at[slot].set(new_key)
                return (pool, cache_len, last_token, temps, top_ks,
                        top_ps, sample_keys)

            fn = self._jit(adopt, donate_argnums=(0, 6, 7, 8, 9, 10, 11))
            self._adopt_fns[n_pages] = fn
            self._note_compile("adopt", n_pages)
        return fn

    def _draft_prefill_fn(self, nb: int, lb: int):
        """KV-only draft prefill: runs the draft model over the FULL
        prompt bucket and returns its small cache — no sampling, no first
        token (the target's prefill owns both). The draft has no prefix
        store, so even a prefix-hit group prefills the draft from token
        zero; the shared ``cache_len`` set by the target insert equals the
        draft's covered length either way."""
        fn = self._draft_prefill_fns.get((nb, lb))
        if fn is None:
            llama, dcfg = self._llama, self.draft_cfg

            def draft_prefill(dparams, tokens, lengths):
                small = llama.init_cache(dcfg, nb, lb)
                _, small, _ = llama.prefill(dparams, dcfg, tokens, small,
                                            lengths=lengths)
                return small

            fn = self._jit(draft_prefill)
            self._draft_prefill_fns[(nb, lb)] = fn
            self._note_compile("draft_prefill", (nb, lb))
        return fn

    def _draft_insert_fn(self, nb: int, lb: int):
        """Scatter a draft prefill's small cache into the big draft cache.
        Only the draft cache is donated — lengths/last-token state is
        owned by the target insert."""
        fn = self._draft_insert_fns.get((nb, lb))
        if fn is None:

            def insert(dcache, small, slots):
                return {name: dcache[name].at[:, slots, :lb].set(
                    small[name], mode="drop") for name in dcache}

            fn = self._jit(insert, donate_argnums=(0,))
            self._draft_insert_fns[(nb, lb)] = fn
            self._note_compile("draft_insert", (nb, lb))
        return fn

    def _spec_fn(self, g: int):
        """Fused draft-propose/target-verify tick (ISSUE 7): the draft
        scans ``g + 1`` decode steps proposing ``g`` tokens (the extra
        step writes the last proposal's KV so a full acceptance leaves the
        draft cache covering every committed position), the target scores
        all ``g + 1`` positions in ONE batched verify forward, and
        rejection sampling commits the longest target-consistent prefix
        plus a bonus token — between 1 and ``g + 1`` tokens per tick.

        Per-row greedy (temperature 0) degenerates to argmax-prefix
        matching and is token-identical to plain decode; sampled rows
        preserve the target DISTRIBUTION (not the plain-tick sample path —
        key consumption differs). Inactive rows freeze exactly like
        ``_tick_fn``: their garbage KV writes land at frozen positions
        that are always overwritten before they can be attended.

        The verify step is the part that differs by cache kind. Dense:
        ``verify_step`` over the whole cache row. Paged: the draft stays
        dense (the draft model is small, a dense row per slot keeps it
        independent of the target's paging) and the target verifies
        through the whole page table via ``verify_step_paged`` — inactive
        rows scatter to the sentinel page and drop.

        Contract: (params, dparams, last_token, cache, dcache, [table,]
        cache_len, active, temps, top_ks, top_ps, keys) → (tokens
        (g+1, B), accepts (B,), cache, dcache, new_len, new_last,
        new_keys); row b commits ``accepts[b] + 1`` tokens and cache_len
        advances by the same."""
        if g not in self._spec_fns:
            jax, jnp, llama, cfg = (self._jax, self._jnp, self._llama,
                                    self.cfg)
            dcfg = self.draft_cfg
            paged = self.paged
            from jax import lax

            from gofr_tpu.ops.sampling import (filtered_log_probs_batch,
                                               speculative_accept)
            if paged:
                step_kw = {"ragged": True} if self._ragged else {}

                def verify(params, tokens, pool, cache_len, active, table):
                    return llama.verify_step_paged(
                        params, cfg, tokens, pool, table, cache_len, active,
                        **step_kw)
            else:
                def verify(params, tokens, cache, cache_len, active):
                    return llama.verify_step(
                        params, cfg, tokens, cache, cache_len)

            def spec_tick(params, dparams, last_token, cache, dcache,
                          *operands):
                *table, cache_len, active, temps, top_ks, top_ps, keys = \
                    operands
                split = jax.vmap(
                    lambda key: jax.random.split(key, g + 2))(keys)
                draft_keys = jnp.moveaxis(split[:, :g + 1], 0, 1)
                accept_keys = split[:, g + 1]

                def draft_step(carry, step_keys):
                    token, dcache, dlen = carry
                    logits, dcache, new_len = llama.decode_step(
                        dparams, dcfg, token, dcache, dlen)
                    q_logp = filtered_log_probs_batch(logits, temps,
                                                      top_ks, top_ps)
                    choice = jax.vmap(jax.random.categorical)(
                        step_keys, q_logp).astype(jnp.int32)
                    proposal = jnp.where(temps > 0.0, choice,
                                         logits.argmax(-1).astype(jnp.int32))
                    new_len = jnp.where(active, new_len, dlen)
                    proposal = jnp.where(active, proposal, token)
                    return (proposal, dcache, new_len), (proposal, q_logp)

                (_, dcache, _), (proposals, q_logps) = lax.scan(
                    draft_step, (last_token, dcache, cache_len), draft_keys)
                draft_tokens = proposals[:g].T           # (B, g)
                q_logp = jnp.moveaxis(q_logps[:g], 0, 1)  # (B, g, V)
                verify_tokens = jnp.concatenate(
                    [last_token[:, None], draft_tokens], axis=1)
                t_logits, cache = verify(params, verify_tokens, cache,
                                         cache_len, active, *table)
                out, accepts, carry = speculative_accept(
                    t_logits, q_logp, draft_tokens, temps, top_ks, top_ps,
                    accept_keys)
                accepts = jnp.where(active, accepts, 0)
                chosen = jnp.take_along_axis(
                    out, accepts[:, None], axis=1)[:, 0].astype(jnp.int32)
                new_last = jnp.where(active, chosen, last_token)
                new_len = jnp.where(active, cache_len + accepts + 1,
                                    cache_len)
                new_keys = jnp.where(active[:, None], carry, keys)
                return (out.T, accepts, cache, dcache, new_len, new_last,
                        new_keys)

            # cache, dcache, cache_len, keys: the table shifts the last two
            self._spec_fns[g] = self._jit(
                spec_tick, donate_argnums=(3, 4, 5 + paged, 10 + paged))
            self._note_compile("spec_paged" if paged else "spec", (g,))
        return self._spec_fns[g]

    def _run_spec(self, g: int, active):
        """Call the speculative tick of rung ``g`` on the engine's slot
        state and write its outputs back (``_run_tick``'s twin: the pool
        lock and the slots' table on the paged path). Returns (tokens
        (g+1, B), accepts (B,)) on the device."""
        fn = self._spec_fn(g)
        with self._pool.lock if self.paged else nullcontext():
            table = [self._table_dev()] if self.paged else []
            (toks_dev, accepts_dev, self._kv, self._draft_cache,
             self.cache_len, self.last_token, self.sample_keys) = fn(
                self.params, self.draft_params, self.last_token, self._kv,
                self._draft_cache, *table, self.cache_len, active,
                self.temps, self.top_ks, self.top_ps, self.sample_keys)
        return toks_dev, accepts_dev

    def _slot_pages(self, window: Optional[int]) -> int:
        """Pages a slot can hold of a cache kind: every column of its
        table, or a window kind's window with a partial page at each end
        and the steps of a tick before the release."""
        if window is None:
            return self.pages_per_slot
        return min(self.pages_per_slot, window // self.kv_page + 2)

    def _fresh_tables(self) -> None:
        """All-sentinel host page tables, one a cache kind (each filled
        with its kind's sentinel)."""
        shape = (self.max_slots, self.pages_per_slot)
        self._tables = {name: np.full(shape, kind.num_pages, np.int32)
                        for name, kind in self._pool.kinds.items()}

    def _empty_table(self):
        """A device table that holds no page: what the warm-ups and the
        deciding compile run a tick with."""
        jnp = self._jnp
        shape = (self.max_slots, self.pages_per_slot)
        return self._pool.as_leaves(
            {name: jnp.full(shape, kind.num_pages, jnp.int32)
             for name, kind in self._pool.kinds.items()})

    def _table_dev(self):
        """Device copy of the whole page table (of each kind's table),
        cached against ``_table_version``: every tick reads all
        ``pages_per_slot`` columns, never a live page count, so one
        program serves every fill (graftcheck GT003 page-width rule)."""
        cached = self._table_cache
        if cached is not None and cached[0] == self._table_version:
            return cached[1]
        dev = self._pool.as_leaves(
            {name: self._jnp.asarray(table)
             for name, table in self._tables.items()})
        self._table_cache = (self._table_version, dev)
        return dev

    def _resolve_ragged(self) -> Tuple[bool, str]:
        """(run the ragged kernel?, why) — the one place the decode
        attention path is chosen; ``attention_paths`` reports it."""
        if not self.paged:
            return False, "paged_kv is off"
        if self.ragged_attn == "off":
            return False, "ragged_attn=off"
        import inspect

        from gofr_tpu.ops.pallas import ragged_tileable
        step = self._llama.decode_step_paged
        if "ragged" not in inspect.signature(step).parameters:
            if self.ragged_attn == "on":
                raise ValueError(
                    "ragged_attn='on': the model module's "
                    "decode_step_paged does not take ragged=")
            return False, "the model module has no ragged= decode step"
        if self.ragged_attn == "on":
            return True, "ragged_attn=on"
        cfg = self.cfg
        platform = (self.mesh.devices.flat[0] if self.mesh is not None
                    else self._jax.devices()[0]).platform
        if platform != "tpu":
            return False, (f"auto: devices are {platform}, the kernel "
                           f"is selected on tpu only")
        if self.mesh is not None:
            return False, ("auto: pallas_call has no partitioning rule, "
                           "a mesh would replicate the pool into it")
        kinds = self._pool.kinds.values()
        kv_itemsize = min(self._jnp.dtype(kind.leaves["k"][1]).itemsize
                          for kind in kinds)
        # a leaf of one row a token keeps its heads side by side
        packed = all(len(kind.leaves["k"][0]) == 1 for kind in kinds)
        if not ragged_tileable(cfg.head_dim, cfg.n_heads, cfg.n_kv_heads,
                               self.kv_page, kv_itemsize, packed=packed):
            return False, (
                f"auto: head_dim {cfg.head_dim}, heads {cfg.n_heads}:"
                f"{cfg.n_kv_heads}, page {self.kv_page} do not tile "
                f"(head_dim % 128, heads % 8, page % 16, kv heads % "
                f"{4 // kv_itemsize}; or bfloat16 heads of 64 in pairs "
                f"in a packed pool)")
        return True, "auto: tpu devices and the geometry tiles"

    def attention_paths(self) -> Dict[str, Any]:
        """Which attention formulation each executable family runs and
        why — logged once at start, so no selection is silent."""
        # a family's config says which buckets its prefill takes through
        # the flash kernel, by its own rule (flash_block); one without
        # the method has no kernel path (models/moe.py)
        block_of = getattr(self.cfg, "flash_block", lambda bucket: None)
        flash = [b for b in self.prompt_buckets if block_of(b) is not None]
        paths = {"decode": self.attn_path, "why": self.attn_reason,
                 "prefill_flash_buckets": flash,
                 "prefill_dense_buckets": [b for b in self.prompt_buckets
                                           if b not in flash]}
        if self._walk:
            paths["ragged_walk"] = self._walk
        return paths

    def _ragged_walk(self) -> Dict[str, Dict[str, Any]]:
        """What the ragged kernel's page walk is at the decode call's
        shape, a cache kind: the kernel's form follows from the shapes
        (``walk_sizes``: pages a block, blocks in flight, whether a
        slot's scores are kept so that K leaves HBM once, KV heads a
        product), so this is where it shows."""
        from gofr_tpu.ops.pallas.ragged_paged_attention import (
            kernel_heads, walk_sizes)
        walks = {}
        for name, kind in self._pool.kinds.items():
            shape, dtype = kind.leaves["k"][:2]
            kv_heads, head_dim = kernel_heads(shape)
            walks[name] = walk_sizes(
                self.kv_page, kv_heads, head_dim, self.cfg.n_heads,
                self._jnp.dtype(dtype).itemsize,
                self.pages_per_slot)._asdict()
        return walks

    @property
    def attn_path(self) -> str:
        """Which decode-attention formulation ticks run: ``ragged``
        (fused Pallas kernel over pool pages), ``gather`` (paged KV
        through the materialized gather view), or ``dense`` (per-slot
        cache rows). Reported per tick via
        ``app_tpu_attn_kernel_total{path=...}`` and in statusz/xlaz."""
        if not self.paged:
            return "dense"
        return "ragged" if self._ragged else "gather"

    async def warmup(self, prompt_counts: Tuple[int, ...] = (1,),
                     ks: Optional[Tuple[int, ...]] = None,
                     sampling: bool = False,
                     windows: Optional[str] = None) -> None:
        """Pre-compile the decode ladder and prefill/insert executables so
        the serving path never traces (executor.warmup analog). ``ks``
        restricts which decode rungs to precompile (default: the whole
        ladder); an unwarmed rung still compiles lazily off-loop if the
        scheduler ever picks it. ``sampling=True`` additionally warms the
        sampled decode variants (temperature/top-k/top-p requests): one
        tick a k rung, two with ``sampling``, whatever ``max_len`` is.

        ``windows`` is taken only as ``None`` or ``"all"``, which mean
        the same: the adapters under ``benchmark/adapters/`` still pass
        ``"all"``, from when ticks had attention-window rungs to warm.

        Must run before ``start()``: warmup mutates cache/cache_len/
        last_token through donated-buffer executables, and racing the
        engine loop would dispatch against invalidated arrays."""
        if self._task is not None:
            raise RuntimeError(
                "warmup() must be called before start(): it mutates engine "
                "device state outside the engine loop")
        jnp = self._jnp
        loop = asyncio.get_running_loop()
        if ks is None:
            rungs = list(self._k_ladder)
        else:
            unknown = [k for k in ks if k not in self._k_ladder]
            if unknown or not ks:
                raise ValueError(
                    f"warmup ks={unknown or ks} are not k-ladder rungs "
                    f"{self._k_ladder}; nothing would be warmed for them")
            rungs = [k for k in self._k_ladder if k in ks]
        if windows not in (None, "all"):
            raise ValueError(
                f"warmup windows={windows!r}: the attention-window ladder "
                f"was removed, every tick reads the whole table; pass "
                f"None or 'all'")
        if self.logger is not None:
            self.logger.info(
                "engine warmup: compiling %d decode executables "
                "(ks=%s sampling=%s)",
                len(rungs) * (2 if sampling else 1), rungs, sampling)

        def compile_all():
            active = jnp.zeros((self.max_slots,), bool)
            for k in rungs:
                for sampled in (False, True) if sampling else (False,):
                    self._run_tick(k, sampled, active)
            if self.spec:
                # the speculative ladder: one fused draft+verify executable
                # per γ rung. Inactive-row garbage writes land at frozen
                # positions that every later insert covers.
                for g in self._g_ladder:
                    self._run_spec(g, active)
            for lb in self.prompt_buckets:
                # rungs over the admission bound are unreachable, and
                # counts that share a rung warm it once
                reachable = dict.fromkeys(
                    next(x for x in self._n_ladder if x >= n)
                    for n in prompt_counts)
                for nb in reachable:
                    if nb > self._group_rows(lb):
                        continue
                    dev = self._padding_group(nb, lb)
                    # no name holds the group's prefill once it is
                    # inserted: the next rung's program loads beside
                    # nothing of this one (2 GB at 16 x 1024 of a 7B)
                    self._run_insert(nb, lb, 0, dev,
                                     *self._run_prefill(nb, lb, dev))
                    if self.spec:
                        dsmall = self._draft_prefill_fn(nb, lb)(
                            self.draft_params, dev["padded"], dev["lengths"])
                        self._draft_cache = self._draft_insert_fn(nb, lb)(
                            self._draft_cache, dsmall, dev["slots"])
            self._jax.block_until_ready(self._kv)

        def compile_locked():
            # warmup mutates the (possibly shared) pool leaves repeatedly;
            # hold the pool lock so a co-resident engine's traffic never
            # interleaves with our donating warmup executions. The
            # _warming flag classes every compile in here as warmup (not
            # serving) in the engine's compile ledger.
            self._warming += 1
            try:
                with self._pool.lock if self.paged else nullcontext():
                    compile_all()
            finally:
                self._warming -= 1

        await loop.run_in_executor(None, compile_locked)

    # -- public API ---------------------------------------------------------
    async def start(self) -> None:
        if self._task is None:
            self._task = spawn_logged(self._loop(), self.logger,
                                      "generate.engine_loop",
                                      metrics=self.metrics)

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
            self._clock.enter(None)      # the loop is gone: stop its clock

    def _validate(self, prompt_ids, max_new_tokens: int) -> Tuple[List[int],
                                                                  int]:
        prompt = list(int(t) for t in prompt_ids)
        bucket = next((b for b in self.prompt_buckets if b >= len(prompt)),
                      None)
        if bucket is None:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds largest bucket "
                f"{self.prompt_buckets[-1]}")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError("prompt + max_new_tokens exceeds cache length")
        self.shapes.record("prompt", len(prompt), bucket)
        return prompt, bucket

    def _new_flight(self, prompt: List[int], budget: int) -> _Flight:
        """Open the request's observability context at submit time: a
        ``queue.wait`` child span under the caller's current span (the HTTP
        request span when called from a handler — contextvars carry it into
        this coroutine) and a flight-recorder record."""
        parent = current_span() if self.tracer is not None else None
        qspan = (self.tracer.start_span("queue.wait", parent=parent)
                 if self.tracer is not None else None)
        link_span = parent if parent is not None else qspan
        record = RequestRecord(
            model=self.model_name, prompt_len=len(prompt), budget=budget,
            trace_id=link_span.trace_id if link_span is not None else None,
            span_id=link_span.span_id if link_span is not None else None)
        self.recorder.start(record)
        # the submitting context's deadline (X-Request-Deadline-Ms) rides
        # with the flight — checked again at admission time
        return _Flight(link_span, qspan, record, deadline=current_deadline())

    def set_brownout(self, level: int) -> None:
        """Apply a brownout rung (``slo.BrownoutLadder`` apply_fn): 0
        healthy, 1 shed batch-class admissions, 2 also cap speculative
        γ at 1, 3 also disable speculative dispatch. Enforcement lives
        engine-side so the ladder works for any caller (watchdog, tests,
        an operator endpoint)."""
        level = max(0, min(int(level), 3))
        if level == self._brownout:
            return
        previous, self._brownout = self._brownout, level
        if self.logger is not None:
            log = self.logger.warn if level > previous else self.logger.info
            log("engine %s: brownout level %d -> %d", self.model_name,
                previous, level)

    def _brownout_gate(self, cls: str, flight: _Flight) -> None:
        """Brownout admission shed (ISSUE 14): refuse classes the current
        rung sheds BEFORE queueing — a 503 the client can retry on another
        replica beats queue time on one that will shed the request
        anyway. Shares the shed accounting with the overflow breaker."""
        if not self._brownout or cls not in brownout_shed_classes(
                self._brownout):
            return
        if flight.qspan is not None:
            flight.qspan.set_status("ERROR")
            flight.qspan.finish()
        self.recorder.finish(flight.record, "expired")
        self._shed_by_class[cls] = self._shed_by_class.get(cls, 0) + 1
        if self.slo is not None:
            self.slo.record_outcome("expired", cls=cls, model=self.model_name)
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_tpu_sched_shed_total", model=self.model_name, cls=cls)
        raise BrownoutShed(
            f"brownout level {self._brownout}: shedding {cls!r} admissions")

    def _adopt_ledger_get(self, dedupe: str) -> Optional["TokenStream"]:
        """Idempotent-adopt lookup: a replayed transfer id inside the TTL
        returns the stream the first adoption produced instead of
        claiming a second slot and page set for the same KV."""
        now = time.monotonic()
        if len(self._adopt_ledger) > _ADOPT_LEDGER_CAP:
            self._adopt_ledger = {
                key: entry for key, entry in self._adopt_ledger.items()
                if now - entry[0] < _ADOPT_LEDGER_TTL_S}
        hit = self._adopt_ledger.get(dedupe)
        if hit is None or now - hit[0] >= _ADOPT_LEDGER_TTL_S:
            return None
        self._adopt_dedup_hits += 1
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_tpu_adopt_dedup_total", model=self.model_name)
        if self.logger is not None:
            self.logger.warn(
                "engine %s: replayed adoption %s served from the dedupe "
                "ledger", self.model_name, dedupe)
        return hit[1]

    def _compile_grammar(self, response_format, eos_id):
        """Resolve a request's ``response_format`` through the per-engine
        grammar cache (raises :class:`~gofr_tpu.tpu.constrain.
        GrammarError`, a ValueError, on malformed input — callers map it
        to a 400 before any slot is claimed)."""
        if response_format is None:
            return None
        grammar = self.grammar_cache.get(response_format, eos_id)
        self._constrained_requests += 1
        return grammar

    async def generate(self, prompt_ids, max_new_tokens: int,
                       eos_id: Optional[int] = None,
                       sampling: Optional[Sampling] = None,
                       response_format: Optional[dict] = None) -> List[int]:
        """Generate up to ``max_new_tokens`` ids (stops early on eos_id).
        Concurrent callers share decode steps (continuous batching).
        ``sampling`` defaults to greedy decoding. ``response_format``
        (``{"type": "regex"|"json_schema", ...}``) constrains decoding to
        a grammar: per-step token masks bias the logits so the output is
        grammar-valid, and generation finishes as soon as the match is
        complete."""
        prompt, bucket = self._validate(prompt_ids, max_new_tokens)
        grammar = self._compile_grammar(response_format, eos_id)
        future = asyncio.get_running_loop().create_future()
        flight = self._new_flight(prompt, max_new_tokens)
        cls = deadline_class(flight.deadline)
        if self.workload is not None:
            self.workload.admit(flight.record, cls, flight.deadline)
        self._brownout_gate(cls, flight)
        await self._pending.put((prompt, bucket, max_new_tokens, eos_id,
                                 sampling or Sampling(), future, None,
                                 time.monotonic(), flight, cls, grammar),
                                cls)
        self._set_queue_gauges()
        self._wake.set()
        return await future

    async def generate_stream(self, prompt_ids, max_new_tokens: int,
                              eos_id: Optional[int] = None,
                              sampling: Optional[Sampling] = None,
                              response_format: Optional[dict] = None):
        """Returns a :class:`TokenStream` yielding token ids as they are
        produced. Validation and admission happen eagerly (before the
        first ``__anext__``), so a bad request raises *here* — callers can
        still return an error status before any stream bytes are written.

        Tokens are published per tick-fetch, so the first yield lands
        after prefill (time-to-first-token) instead of after the full
        completion. Raises the engine failure if the request's slot dies
        mid-flight (same semantics as ``generate``). Cancelling the stream
        (``aclose``/``cancel`` — e.g. the HTTP client disconnected) frees
        the request's slot instead of decoding the rest of the budget into
        an unread queue."""
        prompt, bucket = self._validate(prompt_ids, max_new_tokens)
        grammar = self._compile_grammar(response_format, eos_id)
        queue: asyncio.Queue = asyncio.Queue()
        future = asyncio.get_running_loop().create_future()
        flight = self._new_flight(prompt, max_new_tokens)
        cls = deadline_class(flight.deadline)
        if self.workload is not None:
            self.workload.admit(flight.record, cls, flight.deadline)
        self._brownout_gate(cls, flight)
        await self._pending.put((prompt, bucket, max_new_tokens, eos_id,
                                 sampling or Sampling(), future, queue,
                                 time.monotonic(), flight, cls, grammar),
                                cls)
        self._set_queue_gauges()
        self._wake.set()
        return TokenStream(self, queue, future)

    # -- disaggregated serving: prefill export / KV adoption (ISSUE 8) ------
    async def prefill_export(self, prompt_ids,
                             sampling: Optional[Sampling] = None,
                             traceparent: Optional[str] = None):
        """Prefill-replica half of the disaggregated handoff: run the
        prompt forward ONCE and export its KV as a page-aligned
        :class:`~gofr_tpu.tpu.kv_wire.KVPayload` instead of inserting it
        into a local slot. The payload carries the first sampled token
        and the advanced PRNG key, so the adopting decode replica
        continues token-identically without recomputing a single prompt
        position. No slot is claimed and the engine loop does not need
        to be running — exports ride the same compiled ``_prefill_fn``
        family the local admission path uses, so a replica serving role
        ``both`` shares its warm executables with local traffic.

        Works for dense and paged engines alike (export reads the
        prefill's small cache, never the pool): a prefill-only replica
        can run dense with ``max_len`` = largest bucket while its decode
        peers run paged."""
        from gofr_tpu.tpu import kv_wire
        if self._kv_wire_refusal:
            raise ValueError(self._kv_wire_refusal)
        sampling = sampling or Sampling()
        prompt, bucket = self._validate(prompt_ids, 1)
        page = self.kv_page
        n_pages = -(-len(prompt) // page)
        jnp, cfg = self._jnp, self.cfg
        # a router-supplied traceparent joins this export to the disagg
        # request's trace — same remote-parent rule as adopt_kv, so the
        # prefill and decode flight records share one trace_id and the
        # tracez stitcher can find both halves
        remote = extract_traceparent(traceparent) if traceparent else None
        span = None
        if self.tracer is not None:
            parent = current_span()
            span = self.tracer.start_span("prefill.export", parent=parent,
                                          remote_parent=remote)
        trace_id = span.trace_id if span is not None else None
        if trace_id is None and remote is not None:
            trace_id = remote.get("trace_id")
        record = RequestRecord(
            model=self.model_name, prompt_len=len(prompt), budget=1,
            trace_id=trace_id,
            span_id=span.span_id if span is not None else None)
        self.recorder.start(record)
        record.admitted()
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        fn = self._prefill_fn(1, bucket)
        codec = kv_wire.codec_for_cfg(cfg)
        names = kv_wire.leaf_names(codec)
        span_tokens = n_pages * page

        def export():
            # host staging (np.asarray both ways) lives entirely in this
            # closure — it runs on a worker thread via run_in_executor
            lengths = np.asarray([len(prompt)], np.int32)
            temps = np.asarray([max(sampling.temperature, 0.0)],
                               np.float32)
            top_ks = np.asarray([sampling.top_k], np.int32)
            top_ps = np.asarray([sampling.top_p], np.float32)
            seeds = np.asarray([sampling.seed & 0xFFFFFFFF], np.uint32)
            first, small, keys = fn(
                self.params, jnp.asarray(padded), jnp.asarray(lengths),
                jnp.asarray(temps), jnp.asarray(top_ks),
                jnp.asarray(top_ps), jnp.asarray(seeds))
            # device->host staging happens in THIS worker thread, never
            # on the event loop (graftcheck GT006): the whole closure is
            # dispatched via run_in_executor below
            host = {}
            for name in names:
                leaf = np.asarray(small[name])[:, 0]   # (L, bucket, ...)
                shape = (leaf.shape[0], span_tokens) + leaf.shape[2:]
                out = np.zeros(shape, leaf.dtype)
                if name in ("ks", "vs"):
                    out[:] = 1.0   # pool scale planes initialize to ones
                copy = min(span_tokens, leaf.shape[1])
                out[:, :copy] = leaf[:, :copy]
                # tail rows past the prompt are attention-masked by
                # cache_len downstream; zeros here, garbage in the
                # monolithic path — either way they never contribute
                host[name] = out.reshape(
                    (out.shape[0], n_pages, page) + out.shape[2:])
            key_row = np.asarray(keys)[0]
            return (int(np.asarray(first)[0]), host,
                    (int(key_row[0]), int(key_row[1])))

        loop = asyncio.get_running_loop()
        first, host, key = await loop.run_in_executor(None, export)
        self._prefills += 1
        self._prefill_bucket_tokens += bucket
        self._prefill_rows += 1
        self._prefill_real_tokens += len(prompt)
        self._kv_exports += 1
        record.first_token()
        record.tokens = 1
        self.recorder.finish(record, "exported")
        if span is not None:
            span.set_attribute("prompt_len", len(prompt))
            span.set_attribute("bucket", bucket)
            span.set_attribute("pages", n_pages)
            span.finish()
        return kv_wire.KVPayload(
            codec=codec, dtype=host["k"].dtype.name, page=page,
            tokens=len(prompt), n_layers=cfg.n_layers,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            n_pages=n_pages, first_token=first, sample_key=key,
            model=self.model_name, leaves=host)

    async def adopt_kv(self, payload, max_new_tokens: int,
                       eos_id: Optional[int] = None,
                       sampling: Optional[Sampling] = None,
                       submitted_at: Optional[float] = None,
                       traceparent: Optional[str] = None,
                       transfer_s: float = 0.0,
                       transfer_bytes: int = 0,
                       resume: bool = False,
                       dedupe: Optional[str] = None) -> TokenStream:
        """Decode-replica half of the handoff: admit an exported
        :class:`~gofr_tpu.tpu.kv_wire.KVPayload` straight into the page
        pool as page-table entries and start decoding from its first
        token — zero prefill dispatches (``prefill_bucket_tokens`` does
        not move). The pages are allocated at refcount 1 exactly like a
        local admission; the slot releases them through the normal
        ``_release_slot_kv`` path, so drain/free-list accounting cannot
        tell a migrated request from a local one.

        ``traceparent`` stitches the remote prefill trace across the
        hop; ``transfer_s``/``transfer_bytes`` let the transport surface
        the wire cost on this request's flight record and the
        ``app_tpu_kv_transfer_*`` series. Raises :class:`KVWireError`
        on geometry/codec mismatch and ``RuntimeError`` when no slot or
        pages are free (router backpressure, not a request error).

        ``dedupe`` makes the adoption idempotent (ISSUE 14): a transport
        that times out AFTER the engine admitted the pages may retry with
        the same id and gets the original stream back instead of a
        double-claim — exactly-once admission under at-least-once
        delivery."""
        from gofr_tpu.tpu import kv_wire
        from gofr_tpu.tpu.sched import CLASS_MIGRATED
        if self._kv_wire_refusal:
            raise ValueError(self._kv_wire_refusal)
        if dedupe is not None:
            prior = self._adopt_ledger_get(dedupe)
            if prior is not None:
                return prior
        if not self.paged:
            raise ValueError("adopt_kv needs paged_kv=True (migrated KV "
                             "is admitted as page-table entries)")
        sampling = sampling or Sampling()
        cfg = self.cfg
        if payload.page != self.kv_page:
            raise kv_wire.KVWireError(
                f"payload page size {payload.page} != engine kv_page "
                f"{self.kv_page}")
        if (payload.n_layers, payload.n_kv_heads, payload.head_dim) != \
                (cfg.n_layers, cfg.n_kv_heads, cfg.head_dim):
            raise kv_wire.KVWireError(
                f"payload geometry (L={payload.n_layers}, "
                f"Hkv={payload.n_kv_heads}, Dh={payload.head_dim}) does "
                f"not match this model")
        if payload.codec != kv_wire.codec_for_cfg(cfg):
            raise kv_wire.KVWireError(
                f"payload codec {payload.codec} does not match the pool "
                "storage format (no transcoding on adopt)")
        # a SESSION snapshot's first_token was already delivered to the
        # client by the exporting replica — publishing it again would
        # duplicate a token; only adopt_session may admit one
        if bool(payload.flags & kv_wire.FLAG_SESSION) != resume:
            raise kv_wire.KVWireError(
                "session-flagged payloads must be adopted via "
                "adopt_session (and prefill payloads via adopt_kv)")
        if max_new_tokens < 1:
            raise ValueError("adopt_kv needs max_new_tokens >= 1")
        if payload.tokens + max_new_tokens > self.max_len:
            raise ValueError("migrated prompt + max_new_tokens exceeds "
                             "cache length")
        need = payload.n_pages
        if need + self._kv_reserve > self._pool.num_pages:
            raise RuntimeError(
                f"migrated prompt needs {need} KV pages but the pool "
                f"holds {self._pool.num_pages} (reserve "
                f"{self._kv_reserve}); it can never be adopted")
        if not self._free:
            raise RuntimeError("no free slot to adopt migrated KV into")
        while (self._pool.free_pages - need < self._kv_reserve
                and self._prefix is not None and self._prefix.evict_one()):
            pass
        if self._pool.free_pages - need < self._kv_reserve:
            raise RuntimeError(
                f"kv page pool short for adoption: {need} pages wanted, "
                f"{self._pool.free_pages} free (reserve "
                f"{self._kv_reserve})")
        ids = self._pool.alloc(
            need, reclaim=(self._prefix.evict_one
                           if self._prefix is not None else None))
        if ids is None:
            raise RuntimeError(
                f"kv page pool exhausted at adoption: {need} pages "
                f"wanted, {self._pool.free_pages} free")

        # observability: the adopt span joins the remote prefill trace
        # when the transport forwarded a traceparent
        span = None
        remote = extract_traceparent(traceparent) if traceparent else None
        if self.tracer is not None:
            span = self.tracer.start_span(
                "kv_adopt", remote_parent=remote,
                parent=None if remote else current_span())
            span.set_attribute("tokens", payload.tokens)
            span.set_attribute("pages", need)
            if transfer_bytes:
                span.set_attribute("transfer_bytes", transfer_bytes)
        trace_id = span.trace_id if span is not None else None
        if trace_id is None and remote is not None:
            # tracer disabled: still tag the record with the router's
            # trace_id so the tracez stitcher finds this half
            trace_id = remote.get("trace_id")
        record = RequestRecord(
            model=self.model_name, prompt_len=payload.tokens,
            budget=max_new_tokens,
            trace_id=trace_id,
            span_id=span.span_id if span is not None else None)
        self.recorder.start(record)
        record.admitted()
        record.pages_held = need
        record.kv_transfer_s = float(transfer_s)
        record.kv_transfer_bytes = int(transfer_bytes)
        if self.metrics is not None and transfer_bytes:
            self.metrics.delta_updown_counter(
                "app_tpu_kv_transfer_bytes_total", float(transfer_bytes),
                model=self.model_name)

        # claim the slot synchronously (no awaits between here and the
        # table write: admission and ticks must never see a half-claimed
        # slot). active stays False until the pages land on device.
        queue: asyncio.Queue = asyncio.Queue()
        future = asyncio.get_running_loop().create_future()
        slot_idx = self._free.pop()
        slot = self._slots[slot_idx]
        slot.future = future
        slot.submitted_at = (submitted_at if submitted_at is not None
                             else record.admitted_at)
        self._observe_phase("queue", record.admitted_at - slot.submitted_at)
        slot.deadline = current_deadline()
        slot.remaining = max_new_tokens
        slot.eos_id = eos_id
        slot.tokens = []
        slot.active = False
        slot.migrating = False
        slot.gen += 1
        gen = slot.gen
        # a prefill handoff ships one already-sampled token to publish;
        # a resumed session's last token was delivered by the exporter
        slot.inflight = 0 if resume else 1
        slot.queue = queue
        slot.temperature = sampling.temperature
        slot.cls = CLASS_MIGRATED
        slot.grammar = None        # migrated sessions decode unconstrained
        slot.spec_proposed = 0
        slot.spec_accepted = 0
        slot.fill = payload.tokens
        slot.nodes = []
        # kv_wire ships the one kind's pages (_kv_wire_refusal)
        slot.chains = {ONE_KIND: [0, list(ids)]}
        slot.record = record
        slot.req_span = span
        slot.phase_span = None     # decode span opens at the first push
        self._tables[ONE_KIND][slot_idx, :len(ids)] = ids
        self._table_version += 1

        fn = self._adopt_fn(need)

        def upload(jnp=self._jnp):
            # H2D of the migrated pages + the donating scatter, under the
            # pool lock like every other pool-aliasing dispatch. Always
            # off-loop: the host->device copy of n_pages*page_bytes is
            # too big to run inline even warm.
            idx = np.asarray(ids, np.int32)
            key = np.asarray(payload.sample_key, np.uint32)
            with self._pool.lock:
                pages = {name: self._h2d.upload(payload.leaves[name],
                                                jnp.asarray, path="kv")
                         for name in payload.leaves}
                (leaves, self.cache_len, self.last_token, self.temps,
                 self.top_ks, self.top_ps, self.sample_keys) = fn(
                    self._pool.leaves, pages, jnp.asarray(idx),
                    np.int32(slot_idx), np.int32(payload.tokens),
                    np.int32(payload.first_token),
                    self.cache_len, self.last_token, self.temps,
                    self.top_ks, self.top_ps, self.sample_keys,
                    np.float32(max(sampling.temperature, 0.0)),
                    np.int32(sampling.top_k),
                    np.float32(sampling.top_p), jnp.asarray(key))
                self._pool.leaves = leaves
            self._pool.note_writes(need)

        try:
            await asyncio.get_running_loop().run_in_executor(None, upload)
        except BaseException:
            slot.gen += 1
            slot.queue = None
            slot.future = None
            self._release_slot_kv(slot_idx, slot)
            self._finish_slot(slot, "error")
            self._free.append(slot_idx)
            if span is not None:
                span.set_status("ERROR")
                span.finish()
            raise
        slot.active = True
        self._kv_adoptions += 1
        if resume:
            self._session_adoptions += 1
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_tpu_kv_adoptions_total", model=self.model_name)
        self._wake.set()
        if not resume:
            # publish the shipped first token through the normal path:
            # TTFT, eos/budget bookkeeping, and immediate finish all
            # behave exactly as if a local prefill fetch had just landed.
            # A resumed session publishes nothing here — its next token
            # comes out of this engine's first decode tick, conditioned
            # on the shipped last_token/sample_key.
            self._push_tokens(slot_idx, gen, [payload.first_token])
        if span is not None:
            span.finish()
        stream = TokenStream(self, queue, future)
        if dedupe is not None:
            self._adopt_ledger[dedupe] = (time.monotonic(), stream)
        return stream

    async def adopt_session(self, payload, remaining: int,
                            eos_id: Optional[int] = None,
                            sampling: Optional[Sampling] = None,
                            submitted_at: Optional[float] = None,
                            traceparent: Optional[str] = None,
                            transfer_s: float = 0.0,
                            transfer_bytes: int = 0,
                            dedupe: Optional[str] = None) -> TokenStream:
        """Resume a live decode session exported by a peer's
        :meth:`export_session` (ISSUE 12). The payload's pages carry the
        session's whole committed KV (prompt + every token decoded so
        far), ``first_token`` is the last token the exporter committed,
        and ``sample_key`` its advanced PRNG state — decode continues
        token-identically with zero re-prefill, exactly like a prefill
        handoff but mid-stream. The returned stream yields only tokens
        generated *after* the hop; the fleet relay splices it onto the
        client's stream."""
        return await self.adopt_kv(
            payload, remaining, eos_id=eos_id, sampling=sampling,
            submitted_at=submitted_at, traceparent=traceparent,
            transfer_s=transfer_s, transfer_bytes=transfer_bytes,
            resume=True, dedupe=dedupe)

    async def export_session(self, stream,
                             timeout_s: float = 5.0):
        """Snapshot a live decode session for migration (ISSUE 12): the
        source half of ``migrate_session``. Quiesces the slot (it joins
        no further ticks; in-flight tokens drain through the normal
        publish path so the client sees them), then stages the slot's
        committed KV pages plus its decode state (cache length, last
        token, sampling params, PRNG key) to host and retires the slot —
        pages return to the free list, the stream ends cleanly, and the
        flight record closes with status ``migrated``.

        Returns ``(payload, state)``: a session-flagged
        :class:`~gofr_tpu.tpu.kv_wire.KVPayload` and a host-state dict
        (``remaining`` budget, ``eos_id``, sampling params, ``emitted``
        token count) for the adopting replica's
        :meth:`adopt_session`. Token identity holds across the hop: the
        target's first decode tick reads exactly the device state this
        snapshot froze. Raises ``KeyError`` when the stream is not bound
        to a slot (not yet admitted, or already finished), ``ValueError``
        for constrained sessions (the grammar walker is host state that
        does not ship), ``TimeoutError`` when in-flight ticks fail to
        drain in ``timeout_s``."""
        from gofr_tpu.tpu import kv_wire
        if self._kv_wire_refusal:
            raise ValueError(self._kv_wire_refusal)
        if not self.paged:
            raise ValueError("export_session needs paged_kv=True (the "
                             "session ships as page-pool rows)")
        queue = getattr(stream, "_queue", stream)
        slot_idx = next((i for i, s in enumerate(self._slots)
                         if s.queue is queue), None)
        if slot_idx is None:
            raise KeyError("stream is not bound to a live slot")
        slot = self._slots[slot_idx]
        if slot.grammar is not None:
            raise ValueError("constrained sessions hold host-side "
                             "grammar state and cannot migrate")
        gen0 = slot.gen
        slot.migrating = True

        def live() -> bool:
            return (slot.gen == gen0 and slot.queue is queue
                    and slot.active)

        try:
            deadline = time.monotonic() + timeout_s
            while slot.inflight > 0:
                if not live():
                    raise RuntimeError(
                        "session finished before it could be exported")
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        "in-flight decode ticks did not drain in "
                        f"{timeout_s}s")
                await asyncio.sleep(0.001)
            if not live():
                raise RuntimeError(
                    "session finished before it could be exported")

            fill = slot.fill
            page = self.kv_page
            n_pages = -(-fill // page)
            ids = [int(pid)
                   for pid in self._tables[ONE_KIND][slot_idx, :n_pages]]
            if any(pid == self._pool.sentinel for pid in ids):
                raise RuntimeError(
                    f"slot {slot_idx} table row holds a sentinel inside "
                    f"its {n_pages}-page fill span")
            codec = kv_wire.codec_for_cfg(self.cfg)
            names = kv_wire.leaf_names(codec)
            jnp = self._jnp

            def snapshot():
                # device→host staging on a worker thread (GT006), under
                # the pool lock so a concurrent donating dispatch cannot
                # alias the leaves mid-gather
                idx = np.asarray(ids, np.int32)
                with self._pool.lock:
                    host = {name: np.asarray(
                                self._pool.leaves[name][:, jnp.asarray(idx)])
                            for name in names}
                    last = int(np.asarray(self.last_token)[slot_idx])
                    key_row = np.asarray(self.sample_keys)[slot_idx]
                    temp = float(np.asarray(self.temps)[slot_idx])
                    top_k = int(np.asarray(self.top_ks)[slot_idx])
                    top_p = float(np.asarray(self.top_ps)[slot_idx])
                return (host, last, (int(key_row[0]), int(key_row[1])),
                        temp, top_k, top_p)

            loop = asyncio.get_running_loop()
            host, last, key, temp, top_k, top_p = \
                await loop.run_in_executor(None, snapshot)
            if not live():
                raise RuntimeError("session was cancelled during export")
        except BaseException:
            slot.migrating = False   # re-joins ticks if still live
            raise

        payload = kv_wire.KVPayload(
            codec=codec, dtype=host["k"].dtype.name, page=page,
            tokens=fill, n_layers=self.cfg.n_layers,
            n_kv_heads=self.cfg.n_kv_heads, head_dim=self.cfg.head_dim,
            n_pages=n_pages, first_token=last, sample_key=key,
            model=self.model_name, leaves=host,
            flags=kv_wire.FLAG_SESSION)
        state = {
            "remaining": slot.remaining,
            "eos_id": slot.eos_id,
            "temperature": temp,
            "top_k": top_k,
            "top_p": top_p,
            "emitted": len(slot.tokens),
            "cls": slot.cls,
        }

        # retire the source slot: stale in-flight state is impossible
        # (inflight drained above), so this is the normal teardown minus
        # the token publish — the remainder of the completion streams
        # from the adopting replica
        slot.active = False
        slot.migrating = False
        slot.gen += 1
        slot.inflight = 0
        q = slot.queue
        slot.queue = None
        self._release_slot_kv(slot_idx, slot)
        self._session_exports += 1
        self._finish_slot(slot, "migrated")
        if slot.future is not None and not slot.future.done():
            # non-streaming waiters get the tokens this replica produced;
            # the fleet relay ignores the future and splices streams
            slot.future.set_result(list(slot.tokens))
        self._free.append(slot_idx)
        if q is not None:
            q.put_nowait(_DONE)
        return payload, state

    def prefix_digest(self,
                      max_entries: int = 512) -> Optional[Dict[str, Any]]:
        """Compact digest of resident prefix-cache chains for fleet
        routing (tpu/fleet.py); None when no prefix cache is wired."""
        if self._prefix is None:
            return None
        return self._prefix.digest(max_entries=max_entries)

    def _cancel_stream(self, queue: asyncio.Queue) -> None:
        """Abandon the request bound to ``queue``: free its slot (in-flight
        tick tokens are dropped via the generation counter) or, if not yet
        admitted, mark it so admission skips it."""
        for slot_idx, slot in enumerate(self._slots):
            if slot.queue is queue:
                slot.active = False
                slot.gen += 1          # stale in-flight tokens are dropped
                slot.inflight = 0
                slot.queue = None
                self._release_slot_kv(slot_idx, slot)
                self._finish_slot(slot, "cancelled")
                if slot.future is not None and not slot.future.done():
                    slot.future.cancel()
                if slot_idx not in self._free:
                    self._free.append(slot_idx)
                return
        # not bound to a slot: either still in the admission queue, or
        # already completed (then it can never match again — admission
        # clears this set whenever the pending queue drains empty). The
        # queue OBJECT is kept (not its id) so a recycled address can
        # never cancel an unrelated request.
        self._cancelled_queues.add(queue)

    @property
    def active_slots(self) -> int:
        return sum(1 for slot in self._slots if slot.active)

    def admission_depth(self) -> int:
        """Host admission backlog (WFQ pending + page-deferred overflow)
        — the batch lane's primary backpressure signal, the live twin of
        ``app_tpu_admission_queue_depth`` summed over classes."""
        return self._pending.qsize() + len(self._overflow)

    def kv_free_headroom(self) -> Optional[int]:
        """Free pool pages above the reserve watermark (paged engines;
        None on dense). The batch lane pauses its consumer when this
        runs out rather than piling deferred requests into overflow."""
        if not self.paged:
            return None
        return min(self._pool.free_pages_of(name)
                   for name in self._pool.kinds) - self._kv_reserve

    def attach_telemetry(self, store, every: int = 64) -> None:
        """Wire the continuous telemetry plane (ISSUE 16): ``store`` gets
        a phase-anatomy dict for every ``every``-th decode tick via
        ``note_tick``, filled from the loop clock's stamps (``_LoopClock``).
        Called by the app when telemetry is enabled; never called →
        ``self.telemetry`` stays None and no dict is built."""
        self.telemetry = store
        self._tick_every = max(1, int(every))

    def attach_workload(self, recorder) -> None:
        """Wire the workload capture plane (ISSUE 17): admissions call
        ``recorder.admit`` and every terminal status reaches
        ``recorder.finish`` through the flight recorder's single finish
        funnel. Never called → zero-cost (``self.workload`` stays None)."""
        self.workload = recorder
        self.recorder.workload = recorder

    # -- operating-point plane (ISSUE 19) -----------------------------------
    def _note_compile(self, kind: str, key) -> None:
        """Charge one executable compile (a jit-cache miss). Compiles
        inside ``warmup()``/``prewarm_operating_point`` are warmup-class;
        everything else is serving-class — the signal the auto-tuner's
        compile guard and the SLO watchdog's recompile-storm check read
        on engines that have no executor CompileLedger."""
        cls = "warmup" if self._warming else "serving"
        self._compiles_by_class[cls] += 1
        self._compile_events.append(
            (time.monotonic(), cls, f"{kind}{key}"))
        del self._compile_events[:-256]
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_tpu_engine_compiles_total", cls=cls,
                model=self.model_name)

    def serving_compiles(self, window_s: float = 60.0,
                         now: Optional[float] = None) -> int:
        """Serve-time executable compiles inside the trailing window —
        CompileLedger-compatible, so the same recompile-storm guards
        (autoscaler, auto-tuner, watchdog) accept an engine directly."""
        now = time.monotonic() if now is None else now
        return sum(1 for at, cls, _ in self._compile_events
                   if cls == "serving" and now - at <= window_s)

    def operating_point(self) -> Dict[str, Any]:
        """The live operating point with provenance: every knob the
        auto-tuner may move, plus where the current values came from
        (``source`` is ``seed`` until the first guarded apply)."""
        return {
            "prompt_buckets": list(self.prompt_buckets),
            "steps_per_tick": self.steps_per_tick,
            "gamma_cap": self._gamma_cap,
            "kv_reserve": self._kv_reserve if self.paged else None,
            "class_weights": self._pending.weights(),
            "slots_cap": self.slots_cap,
            "staging_depth": self._h2d.depth,
            "max_slots": self.max_slots,
            "source": self._op_source,
            "generation": self._op_generation,
            "applied_at": self._op_applied_at,
        }

    def _op_shape_sig(self, point) -> Tuple[Tuple[int, ...], int]:
        """Normalized (prompt_buckets, steps_per_tick) signature of a
        candidate point — the shape-changing half of the knob set, the
        part that maps to compiled executables."""
        buckets = getattr(point, "prompt_buckets", None)
        buckets = (self.prompt_buckets if buckets is None
                   else tuple(sorted({int(b) for b in buckets})))
        k = getattr(point, "steps_per_tick", None)
        k = self.steps_per_tick if k is None else max(1, int(k))
        return buckets, k

    async def prewarm_operating_point(self, point) -> Dict[str, Any]:
        """Compile every executable a shape-changing operating-point
        move needs, off the hot path, charged as warmup-class.

        Unlike ``warmup()`` this is safe while serving: it never touches
        engine state — every donated input is a freshly allocated dummy
        of the right shape, so it runs in an executor thread while the
        loop keeps ticking. The cost is transient memory for one dummy
        cache (dense) or one dummy page-pool leaf set (paged) at a
        time; on a memory-tight replica, prewarm during a quiet
        window. New prompt buckets are warmed across the whole
        admission-count ladder and new decode rungs greedy and sampled,
        and in the constrained form where constrained traffic can reach
        them (every prefill, the k=1 ticks), so an applied move never
        compiles on the serving path (the zero-serve-time-compiles
        bar)."""
        buckets, k = self._op_shape_sig(point)
        bad = [b for b in buckets if b > self.max_len]
        if bad or not buckets:
            raise ValueError(
                f"prewarm: prompt buckets {bad or buckets} out of range "
                f"(max_len={self.max_len})")
        if self.paged:
            bad = [b for b in buckets if b % self.kv_page]
            if bad:
                raise ValueError(
                    f"prewarm: prompt buckets {bad} are not multiples of "
                    f"kv_page {self.kv_page}")
        rungs = [1]
        while rungs[-1] * 2 <= k:
            rungs.append(rungs[-1] * 2)
        jnp = self._jnp
        loop = asyncio.get_running_loop()

        def dummy_state():
            """Fresh slot state and a zeroed cache of the engine's shapes:
            what the donating executables consume in the engine's stead."""
            return SimpleNamespace(
                _kv=self._jax.tree.map(
                    lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
                    self._kv),
                cache_len=jnp.zeros((self.max_slots,), jnp.int32),
                last_token=jnp.zeros((self.max_slots,), jnp.int32),
                temps=jnp.zeros((self.max_slots,), jnp.float32),
                top_ks=jnp.zeros((self.max_slots,), jnp.int32),
                top_ps=jnp.ones((self.max_slots,), jnp.float32),
                sample_keys=jnp.zeros((self.max_slots, 2), jnp.uint32))

        def compile_new() -> int:
            compiled = 0
            for lb in buckets:
                for nb in self._n_ladder:
                    missing = [biased for biased in (False, True)
                               if (nb, lb, biased) not in self._prefill_fns]
                    need_insert = (
                        (nb, lb, 0) not in self._insert_paged_fns
                        if self.paged else
                        (nb, lb) not in self._insert_fns)
                    if not missing and not need_insert:
                        continue
                    dev = self._padding_group(nb, lb)
                    with_bias = dict(dev, bias=jnp.zeros(
                        (nb, self.cfg.vocab_size), jnp.float32))
                    outs = {biased: self._run_prefill(
                        nb, lb, with_bias if biased else dev)
                        for biased in missing}
                    compiled += len(missing)
                    if need_insert:
                        first, small, keys = (
                            outs.get(False)
                            or self._run_prefill(nb, lb, dev))
                        self._run_insert(nb, lb, 0, dev, first, small, keys,
                                         state=dummy_state())
                        compiled += 1
            active = jnp.zeros((self.max_slots,), bool)
            masks = {False: (active, None),
                     True: (active.astype(jnp.int32),
                            jnp.zeros((self.max_slots, self.cfg.vocab_size),
                                      jnp.float32))}
            state = None
            for rung in rungs:
                for sampled in (False, True):
                    # a constrained slot rides k=1 ticks only
                    for biased in (False, True) if rung == 1 else (False,):
                        if (rung, sampled, biased) in self._tick_fns:
                            continue
                        # one dummy serves every tick: each call writes
                        # its donated leaves back into it
                        state = state or dummy_state()
                        mask, bias = masks[biased]
                        self._run_tick(rung, sampled, mask, bias=bias,
                                       state=state)
                        compiled += 1
            return compiled

        def compile_warming() -> int:
            self._warming += 1
            try:
                return compile_new()
            finally:
                self._warming -= 1

        compiled = await loop.run_in_executor(None, compile_warming)
        self._op_prewarmed.add((buckets, k))
        if self.logger is not None and compiled:
            self.logger.info(
                "engine prewarm: compiled %d executables for operating "
                "point (buckets=%s k=%d)", compiled, list(buckets), k)
        return {"compiled": compiled, "prompt_buckets": list(buckets),
                "steps_per_tick": k}

    def apply_operating_point(self, point,
                              source: str = "autotune") -> Dict[str, Any]:
        """Atomically swap the engine's tunable operating point — the
        ONLY sanctioned mutation path for serving knobs (graftcheck
        GT014 flags direct writes from outside).

        ``point`` duck-types the knob set (any attribute may be None /
        absent to mean "keep the current value"): ``prompt_buckets``,
        ``steps_per_tick``, ``gamma_cap``, ``kv_reserve``,
        ``class_weights``, ``slots_cap``, ``staging_depth``.

        Refusals (raised, never partially applied):

        - a brownout is active — retuning a degraded replica fights the
          shedding ladder;
        - a shape-changing move (buckets / steps_per_tick) whose
          executables were not compiled by ``prewarm_operating_point``
          — applying it would push compiles onto the serving path;
        - any knob value out of range.

        Everything is validated first, then swapped with no awaits in
        between, so the engine loop observes either the old point or
        the new one. In-flight requests keep the buckets they were
        admitted under (their executables stay cached), which is what
        makes a non-shape knob move bit-identical for live decodes."""
        if self._brownout > 0:
            raise RuntimeError(
                f"apply_operating_point refused: brownout level "
                f"{self._brownout} active")
        buckets, k = self._op_shape_sig(point)
        current_sig = (self.prompt_buckets, self.steps_per_tick)
        if not buckets:
            raise ValueError("apply_operating_point: empty prompt buckets")
        bad = [b for b in buckets if b > self.max_len or b < 1]
        if bad:
            raise ValueError(
                f"apply_operating_point: buckets {bad} out of range "
                f"(max_len={self.max_len})")
        if self.paged:
            bad = [b for b in buckets if b % self.kv_page]
            if bad:
                raise ValueError(
                    f"apply_operating_point: buckets {bad} are not "
                    f"multiples of kv_page {self.kv_page}")
        if (buckets, k) != current_sig \
                and (buckets, k) not in self._op_prewarmed:
            raise RuntimeError(
                "apply_operating_point refused: shape-changing move "
                f"(buckets={list(buckets)} k={k}) was not prewarmed — "
                "call prewarm_operating_point first so compiles stay "
                "off the serving path")
        gamma = getattr(point, "gamma_cap", None)
        if gamma is not None and self.spec:
            gamma = max(1, min(int(gamma), self.spec_gamma))
        reserve = getattr(point, "kv_reserve", None)
        if reserve is not None and self.paged:
            reserve = int(reserve)
            if not 0 <= reserve < self._pool.num_pages:
                raise ValueError(
                    f"apply_operating_point: kv_reserve {reserve} out of "
                    f"range [0, {self._pool.num_pages})")
        weights = getattr(point, "class_weights", None)
        if weights:
            weights = {str(name): float(w) for name, w in weights.items()}
            bad_w = [name for name, w in weights.items() if w <= 0]
            if bad_w:
                raise ValueError(
                    f"apply_operating_point: non-positive class weights "
                    f"{bad_w}")
        cap = getattr(point, "slots_cap", None)
        if cap is not None:
            cap = int(cap)
            if not 1 <= cap <= self.max_slots:
                raise ValueError(
                    f"apply_operating_point: slots_cap {cap} out of "
                    f"range [1, {self.max_slots}]")
        depth = getattr(point, "staging_depth", None)
        if depth is not None:
            depth = max(1, int(depth))
        # validated — swap with no awaits (atomic wrt the engine loop).
        # The outgoing shape stays registered as prewarmed: its
        # executables remain in the jit caches, so a rollback re-apply
        # is always compile-free.
        self._op_prewarmed.add(current_sig)
        self.prompt_buckets = buckets
        self.steps_per_tick = k
        ladder = [1]
        while ladder[-1] * 2 <= k:
            ladder.append(ladder[-1] * 2)
        self._k_ladder = ladder
        if gamma is not None and self.spec:
            self._gamma_cap = gamma
        if reserve is not None and self.paged:
            self._kv_reserve = reserve
        if weights:
            self.class_weights = dict(weights)
            self._pending.set_weights(weights)
        self.slots_cap = cap if cap is not None else self.slots_cap
        if depth is not None:
            self._h2d.depth = depth
        self._op_source = str(source)
        self._op_generation += 1
        self._op_applied_at = time.monotonic()
        if self.logger is not None:
            self.logger.info(
                "engine operating point applied (gen %d, source=%s): "
                "buckets=%s k=%d", self._op_generation, self._op_source,
                list(buckets), k)
        return self.operating_point()

    def shadow_clone(self, point=None) -> "GenerationEngine":
        """A fresh engine over the SAME config and params (device
        arrays are shared, never copied) with a candidate operating
        point — the shadow-replay evaluation target (ISSUE 19). The
        clone carries no metrics/telemetry/recorder wiring, so scoring
        traffic never pollutes live observability. It allocates its own
        KV cache (dense) or page pool (paged), which is the memory cost
        of shadow evaluation; speculative decode and the prefix cache
        are not cloned (the replay cost model does not score them)."""
        buckets, k = self._op_shape_sig(point) if point is not None \
            else (self.prompt_buckets, self.steps_per_tick)
        weights = getattr(point, "class_weights", None) \
            if point is not None else None
        kwargs: Dict[str, Any] = dict(
            max_slots=self.max_slots, max_len=self.max_len,
            prompt_buckets=buckets, steps_per_tick=k,
            mesh=self.mesh,
            model_module=(None if self._llama.__name__.endswith("llama")
                          else self._llama),
            model_name=f"{self.model_name}@shadow",
            class_weights=dict(weights or self.class_weights),
            coalesce_uploads=self.coalesce_uploads,
            coalesce_stream=self.coalesce_stream)
        if self.paged:
            kwargs.update(paged_kv=True, kv_page=self.kv_page,
                          kv_pages=self._pool.num_pages,
                          ragged_attn=self.ragged_attn)
        return GenerationEngine(self.cfg, self.params, **kwargs)

    def _group_rows(self, bucket: int) -> int:
        """Most rows one admission group of ``bucket`` may hold: the
        largest count rung whose rows x bucket stays within
        ``max_group_tokens`` (one row always may), max_slots without."""
        if self.max_group_tokens is None:
            return self._n_ladder[-1]
        return max([n for n in self._n_ladder
                    if n * bucket <= self.max_group_tokens] or [1])

    def _admit_room(self, taken: int) -> bool:
        """True while admission may claim another slot this pass:
        free slots remain beyond the ``taken`` already claimed, and the
        operating point's ``slots_cap`` (when set) is not exceeded."""
        if len(self._free) - taken <= 0:
            return False
        cap = self.slots_cap
        if cap is not None and \
                (self.max_slots - len(self._free)) + taken >= cap:
            return False
        return True

    def stats(self) -> Dict[str, Any]:
        out = {"model": self.model_name,
               "active_slots": self.active_slots,
               "free_slots": len(self._free),
               "queue_depth": self._pending.qsize(),
               "decode_steps": self._steps,
               "prefill_batches": self._prefills,
               # prompt-FLOPs proxy: bucket tokens actually dispatched to
               # prefill executables vs the real (non-padding, non-reused)
               # prompt tokens inside them — prefix reuse shrinks the
               # former for the same admitted traffic
               "prefill_bucket_tokens": self._prefill_bucket_tokens,
               "prefill_real_tokens": self._prefill_real_tokens,
               # rows of those groups, padding rows included: bucket
               # tokens over rows is the mean bucket a prompt ran in
               "prefill_rows": self._prefill_rows,
               # disaggregated handoff accounting: exports are prompt
               # forwards shipped out, adoptions are migrated prompts
               # admitted with ZERO local prefill dispatches
               "kv_exports": self._kv_exports,
               "kv_adoptions": self._kv_adoptions,
               # live-migration accounting (ISSUE 12): both ride the
               # zero-re-prefill path, so these never move the prefill
               # counters above
               "session_exports": self._session_exports,
               "session_adoptions": self._session_adoptions,
               "max_len": self.max_len,
               # what benchmark/adapters/mla_moe.py still reads: one
               # window a tick, the whole of max_len
               "window_ladder": [self.max_len],
               "mesh": dict(self.mesh.shape) if self.mesh else None,
               "device_seconds": {
                   f"{model}/{cls}": round(seconds, 6)
                   for (model, cls), seconds
                   in sorted(self._device_seconds.items())},
               # who holds the serving thread, by phase (_LoopClock)
               "loop": self._clock.stats(),
               # what the device ran, landing to landing (_Timeline)
               "timeline": self._timeline.stats()}
        if self._prefix is not None:
            out["prefix_cache"] = self._prefix.stats()
            out["prefix_cache"]["page_ladder"] = list(self._p_ladder)
        if self.paged:
            pool = self._pool.stats()
            pool["reserve_pages"] = self._kv_reserve
            pool["pages_per_slot"] = self.pages_per_slot
            pool["page_stalls"] = self._page_stalls
            pool["deferred_requests"] = len(self._overflow)
            pool["attn_path"] = self.attn_path
            pool["ragged_attn"] = self.ragged_attn
            if self._walk:
                pool["ragged_walk"] = self._walk
            out["kv_pool"] = pool
        if self.spec:
            rate = (self._spec_accepted / self._spec_proposed
                    if self._spec_proposed else 0.0)
            out["speculative"] = {
                "gamma": self.spec_gamma,
                "gamma_cap": self._gamma_cap,
                "gamma_ladder": list(self._g_ladder),
                "spec_ticks": self._spec_ticks,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "acceptance_rate": round(rate, 6),
            }
        # what the module's decode steps counted, nested by the dotted
        # name: "moe.held_pairs" is stats()["moe"]["held_pairs"]
        for name, total in zip(self._step_counters, self._step_totals):
            group, _, key = name.rpartition(".")
            (out.setdefault(group, {}) if group else out)[key] = total
        out["classes"] = {
            "weights": self._pending.weights(),
            "depths": self._pending.depths(),
            "served": self._pending.served(),
            "shed": dict(self._shed_by_class),
        }
        # engine-side compile ledger (ISSUE 19): serving-class compiles
        # are the recompile-storm signal the auto-tuner guard reads
        out["compiles"] = dict(self._compiles_by_class)
        # what _lay_out_weights moved at start, and the steady tick's
        # temporaries
        out["weights"] = dict(self._weights)
        if self._constrained_requests or len(self.grammar_cache):
            out["constrained"] = {
                "requests": self._constrained_requests,
                "ticks": self._constrained_ticks,
                "grammar_cache": self.grammar_cache.stats(),
            }
        if (self._brownout or self._quarantined or self._adopt_dedup_hits
                or self._adopt_ledger):
            # chaos-plane resilience accounting (ISSUE 14); sparse so a
            # healthy replica's stats payload is unchanged
            out["resilience"] = {
                "brownout_level": self._brownout,
                "quarantined": dict(self._quarantined),
                "adopt_dedup_hits": self._adopt_dedup_hits,
                "adopt_ledger_entries": len(self._adopt_ledger),
            }
        return out

    def data_plane(self) -> Dict[str, Any]:
        """Zero-copy data-plane snapshot (ISSUE 9): engine-side H2D
        totals per path and transfer-coalescer amortization — the live
        twin of ``app_tpu_h2d_bytes_total`` / ``app_tpu_h2d_seconds``
        for the decode/admission path. Rendered by ``/debug/statusz``."""
        h2d = self._h2d.stats()
        return {
            "coalesce_uploads": self.coalesce_uploads,
            "coalesce_stream": self.coalesce_stream,
            "h2d_uploads": h2d["uploads"],
            "h2d_bytes": h2d["upload_bytes"],
            "h2d_mb_per_s": h2d["upload_mb_per_s"],
            "coalescer": self._coalescer.stats(),
        }

    def hbm_attribution(self) -> Dict[str, Any]:
        """Device-memory attribution for ``/debug/hbmz`` (ISSUE 10):
        reconcile what this engine KNOWS it placed on device — params,
        the KV page pool split by ownership class, staging slabs —
        against the backend's ``memory_stats()`` figure. The residual is
        what nobody claims (XLA temp buffers, executables, fragmentation)
        and is the honest "unattributed" line, not an error. Pure host
        bookkeeping — no device syncs."""
        from gofr_tpu.tpu.sched import CLASS_MIGRATED
        tree_leaves = self._jax.tree_util.tree_leaves
        if getattr(self, "_params_nbytes", None) is None:
            nbytes = sum(getattr(leaf, "nbytes", 0)
                         for leaf in tree_leaves(self.params))
            if self.draft_params is not None:
                nbytes += sum(getattr(leaf, "nbytes", 0)
                              for leaf in tree_leaves(self.draft_params))
            self._params_nbytes = int(nbytes)
        out: Dict[str, Any] = {
            "model": self.model_name,
            "params_bytes": self._params_nbytes,
        }
        pool_section: Dict[str, Any] = {}
        attributed = self._params_nbytes
        if self.paged and self._pool is not None:
            pool = self._pool
            page_bytes = pool.page_bytes
            decode_pages = migrated_pages = 0
            for slot in self._slots:
                if not slot.active:
                    continue
                held = slot.held_pages() - len(slot.nodes)
                if slot.cls == CLASS_MIGRATED:
                    migrated_pages += held
                else:
                    decode_pages += held
            used = pool.used_pages
            # pages in use but held by no slot are prefix-cache pins
            # (trie-owned); clip covers the race between a slot release
            # and the pool's counter catching up
            prefix_pages = max(0, used - decode_pages - migrated_pages)
            pool_section = {
                "pool_bytes": pool.pool_bytes,
                "page_bytes": page_bytes,
                "pages": {"total": pool.num_pages,
                          "free": pool.free_pages,
                          "decode": decode_pages,
                          "migrated": migrated_pages,
                          "prefix_pinned": prefix_pages},
                "bytes": {"free": pool.free_pages * page_bytes,
                          "decode": decode_pages * page_bytes,
                          "migrated": migrated_pages * page_bytes,
                          "prefix_pinned": prefix_pages * page_bytes},
            }
            attributed += pool.pool_bytes
        out["page_pool"] = pool_section or None
        staging_bytes = int(self._h2d.stats().get("slab_bytes", 0))
        out["staging_bytes"] = staging_bytes
        attributed += staging_bytes
        out["attributed_bytes"] = attributed
        out["device_bytes_in_use"] = self.device_bytes_in_use()
        if out["device_bytes_in_use"] is not None:
            out["unattributed_bytes"] = (
                out["device_bytes_in_use"] - attributed)
        else:
            out["unattributed_bytes"] = None
        out["device_seconds"] = {
            f"{model}/{cls}": round(seconds, 6)
            for (model, cls), seconds
            in sorted(self._device_seconds.items())}
        return out

    def device_bytes_in_use(self) -> Optional[int]:
        """Backend-reported bytes in use, summed over local devices.
        ``None`` when the backend exposes no ``memory_stats`` (some CPU
        builds) — callers render "unknown" rather than a fake zero."""
        total = 0
        seen = False
        for device in self._jax.local_devices():
            try:
                stats = device.memory_stats() or {}
            except Exception:
                continue
            if "bytes_in_use" in stats:
                total += int(stats["bytes_in_use"])
                seen = True
        return total if seen else None

    def statusz(self, recent: int = 32) -> Dict[str, Any]:
        """Live JSON snapshot for ``/debug/statusz``: admission queue depth,
        per-slot state, KV-cache occupancy, and the flight recorder's
        recent-request ring. Pure host bookkeeping — no device syncs."""
        slots = []
        for slot_idx, slot in enumerate(self._slots):
            slots.append({
                "slot": slot_idx,
                "state": "active" if slot.active else "free",
                "cls": slot.cls if slot.active else None,
                "fill": slot.fill if slot.active else 0,
                "remaining": slot.remaining if slot.active else 0,
                "inflight_tokens": slot.inflight,
                "spec_accepted": slot.spec_accepted if slot.active else 0,
                "spec_proposed": slot.spec_proposed if slot.active else 0,
                "streaming": slot.queue is not None,
                "pages_held": (slot.held_pages()
                               if slot.active else 0),
                "trace_id": (slot.record.trace_id
                             if slot.record is not None else None),
            })
        tokens_in_cache = sum(s.fill for s in self._slots if s.active)
        if self.paged:
            # occupancy against the POOL, not max_slots x max_len — paged
            # HBM is the pool, and live tokens ride actual pages
            capacity = self._pool.num_pages * self.kv_page
            pages_held = sum(s.held_pages()
                             for s in self._slots if s.active)
            kv_cache = {
                "paged": True,
                "attn_path": self.attn_path,
                "max_slots": self.max_slots,
                "max_len": self.max_len,
                "page_tokens": self.kv_page,
                "pool_pages": self._pool.num_pages,
                "pages_in_use": self._pool.used_pages,
                "slot_pages_held": pages_held,
                "tokens_in_cache": tokens_in_cache,
                "occupancy": round(tokens_in_cache / capacity, 6)
                if capacity else 0.0,
                "ragged_fill_ratio": round(
                    tokens_in_cache / (pages_held * self.kv_page), 6)
                if pages_held else 0.0,
            }
        else:
            capacity = self.max_slots * self.max_len
            kv_cache = {
                "max_slots": self.max_slots,
                "max_len": self.max_len,
                "tokens_in_cache": tokens_in_cache,
                "occupancy": round(tokens_in_cache / capacity, 6)
                if capacity else 0.0,
            }
        return {
            "queue_depth": self._pending.qsize(),
            "ticks_inflight": self._ticks_inflight,
            "slots": slots,
            "kv_cache": kv_cache,
            "data_plane": self.data_plane(),
            "stats": self.stats(),
            "requests": self.recorder.snapshot(limit=recent),
            # per-executable roofline attribution (ISSUE 17): the ranked
            # top-offenders view of the same device-seconds charged above
            "executables": self.exec_ledger.snapshot(limit=8),
        }

    def xlaz(self, recent: int = 64, max_rungs: int = 4) -> Dict[str, Any]:
        """Compile-plane view for ``/debug/xlaz``. The engine compiles
        lazily through ``jax.jit`` caches rather than an explicit
        ``.lower().compile()`` ledger, so the actionable signal here is
        shape fit: the observed prompt-length distribution against the
        configured prompt buckets, and the padding-optimal ladder those
        lengths would prefer. Same schema as ``Executor.xlaz`` so the
        endpoint renders either."""
        # ladder re-weighting (ISSUE 17): when a workload recorder is
        # attached, the suggested-ladder DP optimizes for the RECENT
        # traffic shape (the recorder's bounded ring) instead of lifetime
        # observed lengths — a workload shift moves the suggestion even
        # after months of stale history
        ladder_source = "observed_lengths"
        observed: Dict[int, int] = {}
        if self.workload is not None:
            observed = self.workload.prompt_length_distribution(
                self.model_name)
            if observed:
                ladder_source = "workload_trace"
        if not observed:
            observed = self.shapes.distribution("prompt")
        out = {
            "models": {
                "prompt": {
                    "ladder": list(self.prompt_buckets),
                    "observed_batch_sizes": {
                        str(k): v for k, v in sorted(observed.items())},
                    "bucket_hits": {
                        str(k): v for k, v in
                        sorted(self.shapes.bucket_hits("prompt").items())},
                    "suggested_ladder": suggest_ladder(
                        observed,
                        max_rungs=max(len(self.prompt_buckets), max_rungs)),
                    "ladder_source": ladder_source,
                },
            },
            "padding": self.shapes.snapshot(),
            # per-executable device time vs roofline (ISSUE 17): ranked
            # top offenders — "which compiled family burns the seconds"
            "executables": self.exec_ledger.snapshot(limit=max_rungs * 3),
            # the live operating point + provenance (ISSUE 19): the knobs
            # the auto-tuner moves, and whether they came from the seed
            # config or a guarded apply
            "operating_point": self.operating_point(),
            "compiles": dict(self._compiles_by_class),
        }
        if self._prefix is not None:
            # prefix reuse multiplies the prefill-executable family by the
            # page ladder — surface both the ladder and the realized
            # hit/save rates so an operator can judge whether the extra
            # compiles pay for themselves
            out["prefix_cache"] = {
                "page_ladder": list(self._p_ladder),
                "page_tokens": self._prefix.page,
                "store": self._prefix.stats(),
                "prefill_bucket_tokens": self._prefill_bucket_tokens,
                "prefill_real_tokens": self._prefill_real_tokens,
            }
        if self.paged:
            # one decode executable per (k, sampled, biased), each over
            # the whole table
            out["paged_kv"] = {
                "page_tokens": self.kv_page,
                "attn_path": self.attn_path,
                "ragged_attn": self.ragged_attn,
                "decode_executables": sorted(
                    str(key) for key in self._tick_fns),
                "pool": self._pool.stats(),
            }
        if self.spec:
            # the speculative executable family is the only NEW compile
            # surface this subsystem adds: one a γ rung, plus
            # draft prefill/insert riding the existing (nb, bucket) grid
            out["speculative"] = {
                "gamma_ladder": list(self._g_ladder),
                "gamma_cap": self._gamma_cap,
                "compiled_spec_fns": len(self._spec_fns),
                "compiled_draft_prefill_fns": len(self._draft_prefill_fns),
            }
        return out

    def health_check(self) -> Dict[str, Any]:
        """Container-health contract (container/health.go analog)."""
        details: Dict[str, Any] = dict(self.stats())
        try:
            for device in self._jax.devices():
                memory = device.memory_stats() or {}
                details.setdefault("devices", {})[str(device.id)] = {
                    "hbm_bytes_in_use": memory.get("bytes_in_use", 0)}
            status = "UP"
        except Exception as exc:
            details["error"] = repr(exc)
            status = "DOWN"
        return {"status": status, "details": details}

    # -- engine loop --------------------------------------------------------
    async def _loop(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            try:
                await self._loop_body(loop)
            except asyncio.CancelledError:
                raise
            except Exception as exc:     # noqa: BLE001 — engine must not
                # die silently: fail every outstanding caller and keep
                # serving (handler panic-isolation analog).
                if self.logger is not None:
                    self.logger.error("generation engine tick failed: %r",
                                      exc)
                self._fail_outstanding(exc)
                self._clock.enter("wait")    # the drain below yields
                # drain in-flight fetches BEFORE rebuilding device state:
                # their worker threads may still be reading the old buffers,
                # and an unawaited task would log "exception was never
                # retrieved" (ADVICE r3)
                for entry in self._publishq:
                    if entry.span is not None:
                        entry.span.set_status("ERROR")
                        entry.span.finish()
                    try:
                        await entry.task
                    except asyncio.CancelledError:
                        raise    # engine.stop() must still win
                    except Exception:  # noqa: BLE001 — swallow: the
                        pass           # caller was already failed above
                self._publishq.clear()
                self._ticks_inflight = 0
                # the failed executable may have consumed donated buffers
                # (cache/cache_len/last_token donate_argnums) — the old
                # handles are poisoned, so rebuild device state or every
                # later dispatch re-raises the same buffer error
                try:
                    self._reset_device_state()
                except Exception as reset_exc:  # noqa: BLE001
                    if self.logger is not None:
                        self.logger.error(
                            "engine device-state reset failed: %r",
                            reset_exc)

    def _reset_device_state(self) -> None:
        """Reinitialize cache/cache_len/last_token (fresh device buffers,
        original shardings). Loses in-progress KV state — callers were
        already failed by _fail_outstanding."""
        jnp, llama = self._jnp, self._llama
        if self.paged:
            # rebuild the pool leaves and drop every page mapping: slots
            # were already failed, so the table goes back to all-sentinel
            # (the shared prefix index resets below without re-touching
            # the pool it no longer owns). The guard keeps the reset
            # fan-out from re-entering THIS engine's _on_pool_reset —
            # co-resident engines still get notified.
            self._in_pool_reset = True
            try:
                with self._pool.lock:
                    self._pool.reset()
            finally:
                self._in_pool_reset = False
            self._fresh_tables()
            self._table_version += 1
            self._table_cache = None
            for slot in self._slots:
                slot.chains = {}
                slot.nodes = []
        elif self.mesh is not None:
            from gofr_tpu.parallel.sharding import (
                llama_cache_specs, prune_specs, shard_pytree)
            cache = llama.init_cache(self.cfg, self.max_slots, self.max_len)
            self.cache = shard_pytree(
                cache, self.mesh,
                prune_specs(llama_cache_specs(kv_int8=self.cfg.kv_int8),
                            self.mesh))
        else:
            self.cache = self._jax.device_put(
                llama.init_cache(self.cfg, self.max_slots, self.max_len))
        self.cache_len = jnp.zeros((self.max_slots,), jnp.int32)
        self.last_token = jnp.zeros((self.max_slots,), jnp.int32)
        self.temps = jnp.zeros((self.max_slots,), jnp.float32)
        self.top_ks = jnp.zeros((self.max_slots,), jnp.int32)
        self.top_ps = jnp.ones((self.max_slots,), jnp.float32)
        self.sample_keys = jnp.zeros((self.max_slots, 2), jnp.uint32)
        if self.spec:
            # the draft cache's donated handles are as poisoned as the
            # target's — same failure, same rebuild
            self._draft_cache = self._jax.device_put(
                llama.init_cache(self.draft_cfg, self.max_slots,
                                 self.max_len))
        self._mask_key = None
        # the prefix store's pages may be poisoned too (a failed publish
        # consumed nothing, but the index must not advertise pages whose
        # pool handle is being rebuilt) — drop the whole store
        if self._prefix is not None:
            self._prefix.reset()

    def _fail_outstanding(self, exc: BaseException) -> None:
        """Propagate a loop failure to every caller bound to an active slot
        and reset the slot table. Requests still sitting in the admission
        queue were never dispatched to the device, so they are left intact
        and retried against the rebuilt device state (ADVICE r3: one bad
        tick must not reject unrelated queued callers)."""
        for slot_idx, slot in enumerate(self._slots):
            if slot.active:
                slot.active = False
                slot.gen += 1
                slot.inflight = 0
                self._release_slot_kv(slot_idx, slot)
                self._finish_slot(slot, "error")
                if slot.future is not None and not slot.future.done():
                    slot.future.set_exception(exc)
                if slot.queue is not None:
                    slot.queue.put_nowait(exc)
                    slot.queue = None
                if slot_idx not in self._free:
                    self._free.append(slot_idx)

    async def _loop_body(self, loop) -> None:
        q, clock = self._publishq, self._clock
        clock.passes += 1
        clock.enter("admit")
        began = clock.stamp()
        # 1. batched admission of everything pending (up to free slots);
        #    each prefill's first-token fetch starts concurrently
        for entry in await self._admit_pending(loop):
            q.append(entry.start(loop, self._annotation))

        # 2. dispatch the next decode tick(s) up to the pipeline depth;
        #    its token fetch starts immediately in its own worker thread
        clock.enter("dispatch")
        admitted = clock.stamp()
        tick_entry = None
        if (self.active_slots > 0
                and self._ticks_inflight < self.max_inflight_ticks):
            tick_entry = await self._dispatch_tick(loop)
            if tick_entry is not None:
                self._ticks_inflight += 1
                q.append(tick_entry.start(loop, self._annotation))

        if not q:
            if (self.active_slots == 0 and self._pending.empty()
                    and not self._overflow):
                clock.enter("park")
                self._wake.clear()
                await self._wake.wait()
            else:
                # Active or queued work exists but this pass produced no
                # dispatch — e.g. every active slot is quiescing for a
                # migration export, or admission is page-deferred. The
                # admit/dispatch coroutines above return without ever
                # suspending in that state, so without a real sleep this
                # loop would monopolize the event loop and starve the
                # very coroutines (exporter quiesce poll, stream
                # consumers) that unblock it.
                clock.enter("wait")
                await asyncio.sleep(0.001)
            return

        # 3. publish in dispatch order (per-slot token order). Block on the
        #    oldest fetch only when the pipeline can't go deeper; then
        #    drain whatever else already completed.
        block = (tick_entry is None
                 or self._ticks_inflight >= self.max_inflight_ticks)
        clock.enter("wait" if block else "publish")
        if tick_entry is not None and self.telemetry is not None:
            # every Nth tick carries this pass's admit and dispatch
            # phases, their enqueue and upload laps with them, to
            # _publish, which completes them for the sampled tick ring
            # (/debug/timez)
            self._tick_seq += 1
            if self._tick_seq % self._tick_every == 0:
                dispatched = clock.stamp()
                tick_entry.anatomy = {
                    "admission_s": admitted[0] - began[0],
                    "admission_cpu_s": admitted[1] - began[1],
                    "host_dispatch_s": dispatched[0] - admitted[0],
                    "host_dispatch_cpu_s": dispatched[1] - admitted[1]}
        if block:
            entry = q.popleft()
            self._publish(entry, await entry.task)
        while q and q[0].task.done():
            entry = q.popleft()
            self._publish(entry, entry.task.result())

    def _attribute_device_time(self, entry: _Fetch, interval: float) -> None:
        """Charge the entry's interval on the device's timeline
        (``_Timeline``: landing to landing, so the charges over one
        device are disjoint and sum to at most wall time) to the
        participating requests' {model, slo class}, split evenly, AND to
        the dispatched executable family (ISSUE 17) — both through the
        shared :func:`charge_device_time` helper, so the per-family
        ledger and ``app_tpu_device_seconds_total`` see the exact same
        seconds (the totals agree by construction, no double count).
        Feeds the hbmz/clusterz rollups and the xlaz roofline table."""
        if entry.kind == "spec":
            participants = [s for s, _ in entry.payload[0]]
        elif entry.kind == "prefill":
            participants = [s for s, _, _ in entry.payload]
        else:
            participants = [s for s, _ in entry.payload]
        if not participants:
            return
        classes = [getattr(self._slots[s], "cls", None) or "standard"
                   for s in participants]
        charge_device_time(
            interval, self.model_name, classes=classes,
            family=entry.family or entry.kind,
            device_seconds=self._device_seconds, metrics=self.metrics,
            ledger=self.exec_ledger)

    def _stall(self, entry: _Fetch, interval: float) -> None:
        """What a prefill group cost the requests it stopped: every slot
        that is active, has its first token and is not of the group sat
        out the group's ``interval`` on the device. Booked on the
        request (``RequestRecord.stalled``) and, times the slots, on the
        timeline."""
        group = {slot_idx for slot_idx, _, _ in entry.payload}
        stopped = 0
        for slot_idx, slot in enumerate(self._slots):
            if slot.active and slot.tokens and slot_idx not in group:
                stopped += 1
                if slot.record is not None:
                    slot.record.stalled(interval)
        self._timeline.stalled_slot_s += interval * stopped

    def _publish(self, entry: _Fetch, host) -> None:
        clock = self._clock
        clock.enter("publish")       # ends the wait, or the last publish
        interval = self._timeline.land(entry)
        self._attribute_device_time(entry, interval)
        if entry.kind == "prefill":
            self._stall(entry, interval)
            for slot_idx, gen, row in entry.payload:
                self._push_tokens(slot_idx, gen, [int(host[row])])
        elif entry.kind == "spec":
            self._ticks_inflight -= 1
            toks, accepts = host
            snapshot, g = entry.payload
            proposed = accepted = 0
            for slot_idx, gen in snapshot:
                a = int(accepts[slot_idx])
                slot = self._slots[slot_idx]
                if slot.gen == gen:
                    # dispatch charged the g+1 worst case; refund the
                    # rejected tail so inflight/fill track the device
                    # advance of a+1 exactly
                    refund = g - a
                    slot.inflight -= refund
                    slot.fill -= refund
                    slot.spec_proposed += g
                    slot.spec_accepted += a
                    proposed += g
                    accepted += a
                self._push_tokens(slot_idx, gen,
                                  [int(t) for t in toks[:a + 1, slot_idx]])
            self._note_spec(proposed, accepted)
        else:
            self._ticks_inflight -= 1
            if isinstance(host, tuple):
                host, counts = host
                self._note_step_counters(counts)
            plan = faults.active()
            if plan.enabled and entry.payload \
                    and plan.should("nan_logits"):
                # chaos site (ISSUE 14): NaN/inf logits argmax to garbage
                # token ids on device; model it host-side by poisoning
                # one slot's fetched tokens out of vocab range so the
                # _push_tokens breaker quarantines exactly that slot
                # (host is already an ndarray — the fetch ran np.asarray
                # on a worker thread — so this copy is host-side)
                host = host.copy()
                host[:, entry.payload[0][0]] = -1
            self._timeline.tick_tokens += sum(
                self._push_tokens(slot_idx, gen,
                                  [int(t) for t in host[:, slot_idx]])
                for slot_idx, gen in entry.payload)
        if entry.span is not None:   # step span covers dispatch → publish
            entry.span.finish()
        if entry.anatomy is not None and self.telemetry is not None:
            # a sampled tick (see _loop_body): the device wait (dispatch →
            # the fetch's landing) and this publish, off the loop clock
            publish_lap = clock.enter("publish")
            entry.anatomy.update(
                device_wait_s=entry.landed_at - entry.dispatched_at,
                publish_s=publish_lap[0], publish_cpu_s=publish_lap[1],
                kind=entry.kind,
                batch=len(entry.payload[0] if entry.kind == "spec"
                          else entry.payload),
                step=self._steps, at=time.time())
            self.telemetry.note_tick(entry.anatomy)

    def _note_step_counters(self, counts) -> None:
        """Add one tick's STEP_COUNTERS sums to the running totals
        (``stats()``) and to ``app_tpu_step_counter_total``."""
        for i, name in enumerate(self._step_counters):
            value = int(counts[i])
            self._step_totals[i] += value
            if self.metrics is not None and value:
                self.metrics.delta_updown_counter(
                    "app_tpu_step_counter_total", float(value),
                    model=self.model_name, counter=name)

    def _note_spec(self, proposed: int, accepted: int) -> None:
        """Acceptance accounting plus the adaptive-γ controller: every
        ``_SPEC_WINDOW_TICKS`` speculative ticks the windowed acceptance
        rate halves the γ cap (draft diverging — wasted verify slots) or
        doubles it back toward the configured maximum (draft agreeing —
        leave tokens on the table no longer)."""
        if proposed <= 0:
            return
        self._spec_proposed += proposed
        self._spec_accepted += accepted
        self._spec_window_proposed += proposed
        self._spec_window_accepted += accepted
        self._spec_ticks += 1
        if self.metrics is not None:
            self.metrics.delta_updown_counter(
                "app_tpu_spec_proposed_total", float(proposed),
                model=self.model_name)
            self.metrics.delta_updown_counter(
                "app_tpu_spec_accepted_total", float(accepted),
                model=self.model_name)
        if self._spec_ticks % _SPEC_WINDOW_TICKS:
            return
        rate = self._spec_window_accepted / self._spec_window_proposed
        if self.metrics is not None:
            self.metrics.set_gauge("app_tpu_spec_acceptance_rate", rate,
                                   model=self.model_name)
        if rate < _SPEC_SHRINK_BELOW:
            self._gamma_cap = max(1, self._gamma_cap // 2)
        elif rate > _SPEC_GROW_ABOVE:
            self._gamma_cap = min(self.spec_gamma, self._gamma_cap * 2)
        self._spec_window_proposed = 0
        self._spec_window_accepted = 0

    def _prefix_plan(self, prompt: List[int], bucket: int):
        """Plan prefix reuse for one request: look up the longest cached
        page chain, round DOWN to a prefix-pages ladder rung (the
        remainder rides the suffix), and pick the suffix bucket. Returns
        (p_rung, suffix_bucket, page_ids, pinned_nodes) — p_rung 0 means
        full prefill. Pins the used nodes; the caller releases them at
        the end of the admission pass."""
        store = self._prefix
        chain = store.lookup(prompt)
        store.classify(len(chain), store.max_lookup_pages(len(prompt)))
        p = 0
        for rung in self._p_ladder:
            if rung <= len(chain):
                p = rung
        sb = bucket
        while p:
            plen = p * store.page
            suffix_len = len(prompt) - plen
            fit = next((b for b in self.prompt_buckets
                        if b >= suffix_len
                        and plen + b <= self.max_len), None)
            if fit is not None:
                sb = fit
                break
            # widened insert would overrun max_len: drop a rung
            smaller = [r for r in self._p_ladder if r < p]
            p = smaller[-1] if smaller else 0
        if p == 0:
            return 0, bucket, [], []
        nodes = chain[:p]
        store.acquire(nodes)
        store.record_saved(p * store.page)
        return p, sb, [n.page_id for n in nodes], nodes

    async def _admit_pending(self, loop):
        """Drain the queue into slots; one batched prefill dispatch per
        (prefix-pages, prompt-length-bucket) group — prefix_pages is 0
        (full prefill, publishing its pages back to the prefix store when
        one is configured) or a prefix-ladder rung (suffix-only prefill
        gathering cached pages). Returns a ``_Fetch`` a group, not yet
        started, for the first generated tokens."""
        requests: List[Tuple] = []
        # page-deferred requests re-enter FIRST (FIFO fairness: they were
        # admitted-in-order before the pool ran short)
        while self._overflow and self._admit_room(len(requests)):
            requests.append(self._overflow.popleft())
        while self._admit_room(len(requests)) and not self._pending.empty():
            requests.append(self._pending.get_nowait())
        if not requests:
            return []
        jnp = self._jnp
        fetches: List[_Fetch] = []
        by_group: Dict[Tuple[int, int, bool], List[Tuple]] = {}
        leases: List[Any] = []
        # pages promised to requests admitted this pass, a cache kind
        promised: Dict[str, int] = {}
        for ri, request in enumerate(requests):
            prompt, bucket, budget, eos_id, sampling, future, queue, \
                submitted_at, flight, cls, grammar = request
            if queue is not None and queue in self._cancelled_queues:
                # stream consumer vanished before admission: drop it
                self._cancelled_queues.discard(queue)
                if not future.done():
                    future.cancel()
                if flight.qspan is not None:
                    flight.qspan.set_status("CANCELLED")
                    flight.qspan.finish()
                self.recorder.finish(flight.record, "cancelled")
                continue
            if (flight.deadline is not None
                    and time.monotonic() > flight.deadline):
                # deadline ate the whole budget in the admission queue:
                # shed before prefill — a late answer is wasted HBM+flops
                exc = DeadlineExceeded()
                if not future.done():
                    future.set_exception(exc)
                if queue is not None:
                    queue.put_nowait(exc)
                if flight.qspan is not None:
                    flight.qspan.set_status("EXPIRED")
                    flight.qspan.finish()
                self.recorder.finish(flight.record, "expired")
                if self.slo is not None:
                    self.slo.record_outcome("expired", cls=cls,
                                            model=self.model_name)
                if self.logger is not None:
                    self.logger.warn(
                        "engine: shed expired request before prefill "
                        "(%.1fms past deadline)",
                        (time.monotonic() - flight.deadline) * 1000.0)
                continue
            if self.paged:
                # admission is page-gated a cache kind, BEFORE the prefix
                # lookup so a deferred request doesn't double-count
                # hit/save metrics when it retries. Worst case: every
                # kind needs fresh pages for the whole prompt (a window
                # kind: for its last window); the reserve keeps headroom
                # for decode growth of slots already running.
                need = {name: n for name, (_, n)
                        in self._kind_pages(len(prompt)).items()}
                never = [f"{n} {_kind_label(name)} pages but the pool "
                         f"holds {self._pool.kinds[name].num_pages}"
                         for name, n in need.items()
                         if n + self._kv_reserve
                         > self._pool.kinds[name].num_pages]
                if never:
                    exc = RuntimeError(
                        f"prompt needs {' and '.join(never)} (reserve "
                        f"{self._kv_reserve}); it can never be admitted")
                    if not future.done():
                        future.set_exception(exc)
                    if queue is not None:
                        queue.put_nowait(exc)
                    if flight.qspan is not None:
                        flight.qspan.set_status("ERROR")
                        flight.qspan.finish()
                    self.recorder.finish(flight.record, "error")
                    continue

                def short():
                    return any(self._pool.free_pages_of(name)
                               - promised.get(name, 0)
                               < n + self._kv_reserve
                               for name, n in need.items())

                while (short() and self._prefix is not None
                        and self._prefix.evict_one()):
                    pass
                if short():
                    # head-of-line FIFO: defer this and everything popped
                    # after it (admitting a shorter later request first
                    # would starve long prompts under pressure); past the
                    # deque cap the deepest class sheds its own newest
                    self._overflow.extend(requests[ri:])
                    self._shed_overflow()
                    break
                for name, n in need.items():
                    promised[name] = promised.get(name, 0) + n
            # constrained requests always run a FULL prefill (p_rung 0):
            # the biased executable family is keyed (nb, bucket) only, so
            # the suffix-prefill ladder never multiplies by grammar state
            p_rung, sb, page_ids, nodes = (
                self._prefix_plan(prompt, bucket)
                if self._prefix is not None and grammar is None
                else (0, bucket, [], []))
            if not self.paged:
                # dense: pins last only until this admission pass's
                # dispatches are ordered; paged slots keep their nodes
                # pinned for the slot's lifetime (pages ARE the cache)
                leases.extend(nodes)
            by_group.setdefault((p_rung, sb, grammar is not None),
                                []).append(
                (prompt, budget, eos_id, sampling, future, queue,
                 submitted_at, flight, page_ids, nodes, cls, grammar))
        if self._pending.empty() and not self._overflow:
            # no queued request can match a leftover entry any more —
            # bound the set (cancel-after-completion would otherwise leak)
            self._cancelled_queues.clear()
        # Phase 1: claim slots for EVERY group before dispatching any
        # prefill — if one group's dispatch raises, every admitted
        # request is bound to a slot and _fail_outstanding reaches it
        # (otherwise later groups' callers would hang unresolved).
        staged: List[Tuple[int, int, int, bool, Any,
                           List[Tuple[int, int, int]]]] = []
        # a group over the admission bound goes as several, in the order
        # its requests arrived
        bounded = []
        for key, whole in sorted(by_group.items()):
            rows = self._group_rows(key[1])
            bounded += [(key, whole[at:at + rows])
                        for at in range(0, len(whole), rows)]
        for (p_rung, bucket, biased), group in bounded:
            nb = next(x for x in self._n_ladder if x >= len(group))
            plen = p_rung * self._prefix.page if p_rung else 0
            padded = np.zeros((nb, bucket), np.int32)
            lengths = np.ones((nb,), np.int32)
            slots = np.full((nb,), self.max_slots, np.int32)  # OOB → drop
            temps = np.zeros((nb,), np.float32)
            top_ks = np.zeros((nb,), np.int32)
            top_ps = np.ones((nb,), np.float32)
            seeds = np.zeros((nb,), np.uint32)
            page_mat = np.zeros((nb, p_rung), np.int32)
            # constrained group: each row's start-state grammar mask
            # biases the first token sampled inside the prefill
            bias_rows = (np.zeros((nb, self.cfg.vocab_size), np.float32)
                         if biased else None)
            # paged path: fresh page ids per (row, suffix page), row-major,
            # sentinel where the row has no page (padding rows / short
            # suffixes) — the insert scatter drops those
            npg = bucket // self.kv_page if self.paged else 0
            flat_ids = {name: np.full((nb * npg,), kind.num_pages, np.int32)
                        for name, kind in self._pool.kinds.items()} \
                if self.paged else None
            db = 0
            draft_padded = draft_lengths = None
            if self.spec:
                # the draft always prefills the FULL prompt (it has no
                # prefix store), so its bucket covers the longest prompt in
                # the group — the original bucket of each request is ≥ its
                # prompt length, so a covering rung always exists
                db = next(b for b in self.prompt_buckets
                          if b >= max(len(entry[0]) for entry in group))
                draft_padded = np.zeros((nb, db), np.int32)
                draft_lengths = np.ones((nb,), np.int32)
            claimed: List[Tuple[int, int, int]] = []          # (slot,gen,row)
            for row, (prompt, budget, eos_id, sampling, future, queue,
                      submitted_at, flight, page_ids,
                      nodes, cls, grammar) in enumerate(group):
                slot_idx = self._free.pop()
                if self.paged:
                    self._pool.claim_slot(slot_idx)
                slot = self._slots[slot_idx]
                slot.future = future
                slot.submitted_at = submitted_at
                slot.deadline = flight.deadline
                slot.remaining = budget
                slot.eos_id = eos_id
                slot.tokens = []
                slot.migrating = False
                slot.active = True
                slot.gen += 1
                slot.inflight = 1          # the prefill's first token
                slot.queue = queue
                slot.temperature = sampling.temperature
                slot.cls = cls
                slot.grammar = None
                if grammar is not None:
                    # per-request cursor over the shared compiled grammar;
                    # the start-state bias row steers the prefill's token
                    slot.grammar = GrammarWalker(grammar)
                    bias_rows[row, :] = slot.grammar.bias_row()
                slot.spec_proposed = 0
                slot.spec_accepted = 0
                slot.fill = len(prompt)    # device cache_len after insert
                # queue.wait ends here; the prefill phase span opens, both
                # in the request's own trace
                if flight.qspan is not None:
                    flight.qspan.set_attribute("slot", slot_idx)
                    flight.qspan.finish()
                flight.record.admitted()
                self._observe_phase(
                    "queue", flight.record.admitted_at - submitted_at)
                flight.record.cached_prefix_len = plen
                slot.record = flight.record
                slot.req_span = flight.link_span
                slot.phase_span = (
                    self.tracer.start_span("prefill", parent=flight.link_span)
                    if self.tracer is not None else None)
                if slot.phase_span is not None:
                    slot.phase_span.set_attribute("slot", slot_idx)
                    slot.phase_span.set_attribute("prompt_len", len(prompt))
                    slot.phase_span.set_attribute("cached_prefix_len", plen)
                # only the suffix past the reused prefix is prefilled
                # (the whole prompt when p_rung == 0)
                suffix = prompt[plen:]
                padded[row, :len(suffix)] = suffix
                lengths[row] = len(suffix)
                self._prefill_real_tokens += len(suffix)
                if self.spec:
                    draft_padded[row, :len(prompt)] = prompt
                    draft_lengths[row] = len(prompt)
                if p_rung:
                    page_mat[row] = page_ids
                if self.paged:
                    # prefix hit = table entries, zero KV copies: the
                    # pinned trie nodes' pages map straight into columns
                    # [0, p_rung); fresh pages follow, of each kind for
                    # the columns its layers will read: all of the
                    # suffix's, or a window kind's last window's (the
                    # insert drops what lies before). The reserve gating
                    # above guarantees the alloc (reclaim backstop evicts
                    # cold prefixes if it somehow doesn't).
                    slot.nodes = list(nodes)
                    for j, node in enumerate(nodes):
                        self._tables[ONE_KIND][slot_idx, j] = node.page_id
                    for name, (first, n) in self._kind_pages(
                            len(prompt), p_rung).items():
                        ids = self._pool.alloc(
                            n, kind=name,
                            reclaim=(self._prefix.evict_one
                                     if self._prefix is not None else None))
                        if ids is None:
                            raise RuntimeError(
                                f"kv page pool exhausted at admission: {n} "
                                f"{_kind_label(name)} pages wanted, "
                                f"{self._pool.free_pages_of(name)} free")
                        slot.chains[name] = [first, list(ids)]
                        self._tables[name][slot_idx, first:first + n] = ids
                        at = row * npg + first - p_rung
                        flat_ids[name][at:at + n] = ids
                    self._table_version += 1
                    flight.record.pages_held = slot.held_pages()
                    if p_rung == 0 and self._prefix is not None:
                        # zero-copy publish: fully-valid prompt pages are
                        # adopted by the trie (one retain per new page);
                        # the page decode writes into stays slot-private
                        want = min(len(prompt) // self.kv_page,
                                   self._prefix.max_pages)
                        if want > 0:
                            self._prefix.register(
                                prompt, slot.chains[ONE_KIND][1][:want])
                slots[row] = slot_idx
                temps[row] = max(sampling.temperature, 0.0)
                top_ks[row] = sampling.top_k
                top_ps[row] = sampling.top_p
                seeds[row] = np.uint32(sampling.seed & 0xFFFFFFFF)
                claimed.append((slot_idx, slot.gen, row))

            # a full prefill publishes its page-aligned prefix back into
            # the store (dedup'd: already-cached pages keep the num_pages
            # sentinel and the scatter drops them)
            publish_ids = None
            if p_rung == 0 and self._prefix is not None and not self.paged:
                store = self._prefix
                np_max = min(bucket // store.page, store.max_pages)
                if np_max > 0:
                    flat = np.full((nb * np_max,), store.num_pages,
                                   np.int32)
                    new_any = False
                    for row, entry in enumerate(group):
                        want = min(len(entry[0]) // store.page, np_max)
                        if want <= 0:
                            continue
                        pages = store.insert(entry[0], want)
                        for j, (pid, is_new) in enumerate(pages):
                            if is_new:
                                flat[row * np_max + j] = pid
                                new_any = True
                    if new_any:
                        publish_ids = flat

            if self.paged:
                def dispatch(p=p_rung, bucket=bucket, nb=nb, padded=padded,
                             lengths=lengths, slots=slots, temps=temps,
                             top_ks=top_ks, top_ps=top_ps, seeds=seeds,
                             page_mat=page_mat, flat_ids=flat_ids,
                             plen=plen, bias_rows=bias_rows):
                    # the group's small arrays ship BEFORE the lock (they
                    # never alias the pool) — one coalesced transfer when
                    # GENERATE_COALESCE_UPLOADS is on; the grammar bias
                    # rows (float32) ride the same frame
                    group = dict(padded=padded, lengths=lengths,
                                 slots=slots, temps=temps, top_ks=top_ks,
                                 top_ps=top_ps, seeds=seeds)
                    # a kind's ids under a name of their own: the upload
                    # takes a flat group of arrays
                    group.update((f"flat_ids.{name}", ids)
                                 for name, ids in flat_ids.items())
                    if p:
                        group["page_mat"] = page_mat
                    if bias_rows is not None:
                        group["bias"] = bias_rows
                    dev = self._upload_group(group)
                    dev["flat_ids"] = self._pool.as_leaves(
                        {name: dev.pop(f"flat_ids.{name}")
                         for name in flat_ids})
                    # pool lock: a co-resident engine's donating dispatch
                    # must not interleave between our read of the leaves
                    # handle and the write-back below (tenancy safety)
                    with self._pool.lock:
                        if p == 0:
                            first, small, keys = self._run_prefill(
                                nb, bucket, dev)
                        else:
                            # suffix prefill reads the SAME pool leaves the
                            # insert below donates — PjRt usage events order
                            # the read before the aliased write
                            first, small, keys = self._suffix_prefill_fn(
                                nb, p, bucket)(
                                self.params, self._pool.leaves,
                                dev["page_mat"], dev["padded"],
                                dev["lengths"], dev["temps"],
                                dev["top_ks"], dev["top_ps"],
                                dev["seeds"])
                        self._run_insert(nb, bucket, plen, dev, first, small,
                                         keys)
                    self._pool.note_writes(sum(
                        int((ids != self._pool.sentinel_of(name)).sum())
                        for name, ids in flat_ids.items()))
                    return first

                warm = ((nb, bucket, plen) in self._insert_paged_fns
                        and ((nb, bucket, biased) in self._prefill_fns
                             if p_rung == 0 else
                             (nb, p_rung, bucket)
                             in self._suffix_prefill_fns))
            elif p_rung == 0:
                def dispatch(bucket=bucket, nb=nb, padded=padded,
                             lengths=lengths, slots=slots, temps=temps,
                             top_ks=top_ks, top_ps=top_ps, seeds=seeds,
                             publish_ids=publish_ids, bias_rows=bias_rows):
                    group = dict(
                        padded=padded, lengths=lengths, slots=slots,
                        temps=temps, top_ks=top_ks, top_ps=top_ps,
                        seeds=seeds)
                    if bias_rows is not None:
                        group["bias"] = bias_rows
                    dev = self._upload_group(group)
                    first, small, keys = self._run_prefill(nb, bucket, dev)
                    self._run_insert(nb, bucket, 0, dev, first, small, keys)
                    if publish_ids is not None:
                        # insert does not donate `small`, so the publish
                        # scatter can read it after the insert dispatch
                        self._prefix.publish(small, publish_ids, nb, bucket)
                    return first

                warm = ((nb, bucket, biased) in self._prefill_fns
                        and (nb, bucket) in self._insert_fns
                        and (publish_ids is None
                             or self._prefix.publish_ready(nb, bucket)))
            else:
                def dispatch(p=p_rung, bucket=bucket, nb=nb, padded=padded,
                             lengths=lengths, slots=slots, temps=temps,
                             top_ks=top_ks, top_ps=top_ps, seeds=seeds,
                             page_mat=page_mat):
                    dev = self._upload_group(dict(
                        padded=padded, lengths=lengths, slots=slots,
                        temps=temps, top_ks=top_ks, top_ps=top_ps,
                        seeds=seeds, page_mat=page_mat))
                    first, small, keys = self._suffix_prefill_fn(
                        nb, p, bucket)(
                        self.params, self._prefix.pool,
                        dev["page_mat"], dev["padded"],
                        dev["lengths"], dev["temps"],
                        dev["top_ks"], dev["top_ps"],
                        dev["seeds"])
                    (self.cache, self.cache_len, self.last_token, self.temps,
                     self.top_ks, self.top_ps, self.sample_keys) = \
                        self._suffix_insert_fn(nb, p, bucket)(
                            self.cache, self._prefix.pool,
                            dev["page_mat"], small,
                            dev["slots"], dev["lengths"], first,
                            self.cache_len, self.last_token, self.temps,
                            self.top_ks, self.top_ps, self.sample_keys,
                            dev["temps"], dev["top_ks"],
                            dev["top_ps"], keys)
                    return first

                warm = ((nb, p_rung, bucket) in self._suffix_prefill_fns
                        and (nb, p_rung, bucket) in self._suffix_insert_fns)

            draft_dispatch = None
            if self.spec:
                def draft_dispatch(nb=nb, db=db, draft_padded=draft_padded,
                                   draft_lengths=draft_lengths, slots=slots):
                    dev = self._upload_group(dict(
                        draft_padded=draft_padded,
                        draft_lengths=draft_lengths, slots=slots))
                    small = self._draft_prefill_fn(nb, db)(
                        self.draft_params, dev["draft_padded"],
                        dev["draft_lengths"])
                    self._draft_cache = self._draft_insert_fn(nb, db)(
                        self._draft_cache, small, dev["slots"])

                warm = (warm and (nb, db) in self._draft_prefill_fns
                        and (nb, db) in self._draft_insert_fns)

            staged.append((nb, bucket, p_rung, warm, dispatch,
                           draft_dispatch, claimed))

        # Phase 2: dispatch per group (first-time compiles run off-loop;
        # warm dispatch is ~free). Leases release after every dispatch:
        # pinned pages must survive until the suffix gathers that read
        # them are ordered behind any publish that could recycle a page.
        try:
            for (nb, bucket, p_rung, warm, dispatch, draft_dispatch,
                 claimed) in staged:
                step_span = self._step_span("tpu.engine.prefill", claimed,
                                            bucket=bucket, padded_batch=nb,
                                            prefix_pages=p_rung)
                dispatched_at = time.monotonic()
                if warm:
                    # the lap opens first: of two annotations that cover
                    # a device gap, the earlier one names it
                    with self._clock.lap("enqueue"), \
                            self._profile_step("tpu.engine.prefill"):
                        first_dev = dispatch()
                        if draft_dispatch is not None:
                            draft_dispatch()
                else:
                    def cold(dispatch=dispatch,
                             draft_dispatch=draft_dispatch):
                        first = dispatch()
                        if draft_dispatch is not None:
                            draft_dispatch()
                        return first

                    first_dev = await self._off_loop(loop, cold)
                self._prefills += 1
                self._prefill_bucket_tokens += nb * bucket
                self._prefill_rows += nb
                family = (f"suffix_prefill[nb={nb},p={p_rung},b={bucket}]"
                          if p_rung else f"prefill[nb={nb},b={bucket}]")
                fetches.append(_Fetch(
                    "prefill", claimed, partial(np.asarray, first_dev),
                    dispatched_at, span=step_span, family=family))
        finally:
            if self._prefix is not None and leases:
                self._prefix.release(leases)
        self._set_queue_gauges()
        return fetches

    async def _off_loop(self, loop, compile_and_dispatch):
        """A first-time compile runs in a worker thread; the engine yields
        the event loop meanwhile, so the stretch is the clock's ``wait``."""
        phase = self._clock.phase
        self._clock.enter("wait")
        try:
            return await loop.run_in_executor(None, compile_and_dispatch)
        finally:
            self._clock.enter(phase)

    def _profile_step(self, name: str):
        """``StepTraceAnnotation`` for the on-demand profiler (ISSUE 10):
        when a ``/debug/profiler`` capture is live, each dispatched step
        shows up named and numbered in the XProf timeline; with no
        capture active it is a nanosecond-cheap TraceMe no-op."""
        return self._jax.profiler.StepTraceAnnotation(
            name, step_num=self._steps)

    def _step_span(self, name: str, participants,
                   **attributes) -> Optional[Span]:
        """Open an engine-step span (root of its own trace — the engine loop
        must not inherit whatever request context first started it) with
        span links to every request it serves: the many-to-one edge of the
        flight recorder. ``participants`` is a list of tuples whose first
        element is a slot index. Finished by ``_publish`` when the step's
        token fetch lands, so the span covers dispatch → device compute →
        D2H fetch."""
        if self.tracer is None:
            return None
        span = Span(self.tracer, name)
        span.set_attribute("batch_size", len(participants))
        for key, value in attributes.items():
            span.set_attribute(key, value)
        for entry in participants:
            slot = self._slots[entry[0]]
            if slot.req_span is not None:
                span.add_link(slot.req_span)
        return span

    def _upload_group(self, arrays: Dict[str, Any]) -> Dict[str, Any]:
        """Ship one admission/tick group of small host arrays host→device.

        With ``coalesce_uploads`` the whole group (every engine control
        array is a 4-byte dtype) rides ONE packed transfer and is split
        back on device with a bit-exact jitted bitcast — greedy decode is
        token-identical with coalescing on or off. Off, each array is its
        own metered ``jnp.asarray``. Either way the caller indexes the
        returned dict by name, so the two paths share all dispatch code.
        On the loop's thread the transfer is the clock's ``upload`` lap."""
        live = {k: v for k, v in arrays.items() if v is not None}
        with self._clock.lap("upload"):
            if self.coalesce_uploads and len(live) > 1:
                out = self._coalescer.upload(live)
            else:
                jnp = self._jnp
                out = {k: self._h2d.upload(v, jnp.asarray, path="dispatch")
                       for k, v in live.items()}
        for k in arrays:
            out.setdefault(k, None)
        return out

    def _active_mask(self, active):
        """The tick's active mask on the device, kept resident: it is
        uploaded again only when the active set changed (every H2D pays a
        fixed per-transfer cost; most ticks are stable)."""
        key = active.tobytes()
        if getattr(self, "_mask_key", None) != key:
            self._mask_dev = self._h2d.upload(active, self._jnp.asarray,
                                              path="mask")
            self._mask_key = key
        return self._mask_dev

    async def _dispatch_tick(self, loop):
        """Choose K adaptively, dispatch one decode executable, return
        its ``_Fetch`` (not yet started) without syncing.

        Slots whose budget is already covered by in-flight tokens are
        excluded from this tick (frozen in the mask) rather than stalling
        everyone: one nearly-finished slot must not serialize the rest.
        Returns None only when *no* slot wants more tokens. K drops to 1
        only when a pending request could actually be admitted next
        iteration (pending non-empty AND a free slot exists) — under
        saturation there is nothing to admit, so fused-K ticks continue."""
        # chaos site (ISSUE 14): a tick_exception fault surfaces exactly
        # where a poisoned executable would — inside the loop body, where
        # _loop's catch-all fails outstanding work and rebuilds device
        # state
        faults.active().raise_if("tick_exception")
        # constrained slots only join a tick when no token of theirs is in
        # flight: their grammar mask is valid for exactly the next
        # position, so pipelined ticks must not run ahead of the walker
        eligible = [(slot_idx, slot)
                    for slot_idx, slot in enumerate(self._slots)
                    if slot.active and slot.remaining > slot.inflight
                    and not slot.migrating
                    and (slot.grammar is None or slot.inflight == 0)]
        if not eligible:
            return None
        biased = any(slot.grammar is not None for _, slot in eligible)
        min_wanted = min(slot.remaining - slot.inflight
                         for _, slot in eligible)
        k = 1
        # a constrained participant pins the tick to k=1 (one mask per
        # token) and suppresses speculative dispatch (the draft cannot
        # propose through a grammar)
        if not biased and (self._pending.empty() or not self._free):
            for rung in self._k_ladder:
                if rung <= min_wanted:
                    k = rung
            if self.spec and min_wanted >= 2 and self._brownout < 3:
                # speculative rung g commits UP TO g+1 tokens per slot, so
                # it needs g+1 ≤ min_wanted — the same never-overshoot
                # invariant as fused-K (device advance is accepts+1 ≤ g+1).
                # Brownout (ISSUE 14): level 2 pins γ to the cheapest
                # rung, level 3 (checked above) drops speculation outright
                g = 0
                cap = 1 if self._brownout >= 2 else self._gamma_cap
                for rung in self._g_ladder:
                    if rung + 1 <= min_wanted and rung <= cap:
                        g = rung
                if g > 0:
                    return await self._dispatch_spec(loop, eligible, g)
        if self.paged:
            covered = self._cover_pages(eligible, k)
            if not covered:
                # every eligible slot is short of pages and nothing can be
                # reclaimed. In-flight ticks will free pages when their
                # slots complete; with NONE in flight the pool is
                # wedged — shed the newest request to unwedge (its pages
                # restart the oldest slots).
                if self._ticks_inflight == 0:
                    self._shed_newest(eligible)
                return None
            eligible = covered
        active = np.zeros((self.max_slots,), bool)
        snapshot = []
        sampled = False
        fills = []
        for slot_idx, slot in eligible:
            active[slot_idx] = True
            slot.inflight += k
            fills.append(slot.fill)
            slot.fill += k       # device cache_len advances by exactly k
            snapshot.append((slot_idx, slot.gen))
            if slot.temperature > 0.0:
                sampled = True
            if slot.record is not None:
                slot.record.rode_batch(len(eligible))
        if biased:
            # per-tick grammar masks: every constrained participant's
            # current-state bias row lands in a fresh (max_slots, vocab)
            # slab (rows default to 0 — unconstrained participants decode
            # unbiased; inactive rows are frozen by the mask). Mask +
            # bias ship as ONE coalesced H2D frame (both 4-byte dtypes),
            # through the same _upload_group entry point as every other
            # dispatch — no new per-step device_put path.
            slab = np.zeros((self.max_slots, self.cfg.vocab_size),
                            np.float32)
            active_i32 = np.zeros((self.max_slots,), np.int32)
            active_i32[active] = 1
            for slot_idx, slot in eligible:
                if slot.grammar is not None:
                    slab[slot_idx, :] = slot.grammar.bias_row()
            dev = self._upload_group(dict(active=active_i32, bias=slab))
            mask, bias = dev["active"], dev["bias"]
            self._constrained_ticks += 1
        else:
            mask, bias = self._active_mask(active), None

        def dispatch():
            return self._run_tick(k, sampled, mask, bias=bias)

        step_span = self._step_span("tpu.engine.step", snapshot,
                                    k=k, sampled=sampled, step=self._steps)
        dispatched_at = time.monotonic()
        if (k, sampled, biased) in self._tick_fns:
            with self._clock.lap("enqueue"), \
                    self._profile_step("tpu.engine.step"):
                tokens_dev, counts_dev = dispatch()
        else:
            tokens_dev, counts_dev = await self._off_loop(loop, dispatch)
        self._steps += 1
        if self.metrics is not None:
            exemplar = next(
                ({"trace_id": slot.record.trace_id}
                 for _, slot in eligible
                 if slot.record is not None and slot.record.trace_id),
                None)
            self.metrics.record_histogram(
                "app_tpu_batch_size", float(len(snapshot)),
                exemplar=exemplar, model=self.model_name)
            self.metrics.increment_counter(
                "app_tpu_attn_kernel_total", model=self.model_name,
                path=self.attn_path)

        if counts_dev:
            def fetch(dev=(tokens_dev, counts_dev[0])):
                # tokens and the steps' counters land in the one fetch
                return self._jax.device_get(dev)
        else:
            def fetch(dev=tokens_dev):
                return np.asarray(dev)

        # what the timeline books when the tick lands: its steps, and on
        # the paged gather path the rows under the participants' lengths
        # (step j of K reads fill + j + 1 a slot) over the rows its
        # gathers bring in for every slot: the whole table
        live = gathered = 0
        if self.attn_path == "gather":
            live = k * sum(fills) + len(fills) * k * (k + 1) // 2
            gathered = k * self.max_slots * self.pages_per_slot \
                * self.kv_page
        work = (k, live, gathered)
        # executable-family name for the roofline ledger (ISSUE 17)
        family = self._tick_names(k, biased)[1]
        return _Fetch("tick", snapshot, fetch, dispatched_at,
                      span=step_span, family=family, work=work)

    async def _dispatch_spec(self, loop, eligible, g: int):
        """Dispatch one speculative tick at rung ``g``: charge every
        participating slot ``g + 1`` in-flight tokens (the conservative
        worst case — ``_publish`` refunds the rejected remainder), run the
        fused draft+verify executable, and hand back a fetch that lands
        both the (g+1, B) token matrix and the per-slot accept counts
        (a ``_Fetch``, not yet started)."""
        if self.paged:
            covered = self._cover_pages(eligible, g + 1)
            if not covered:
                if self._ticks_inflight == 0:
                    self._shed_newest(eligible)
                return None
            eligible = covered
        active = np.zeros((self.max_slots,), bool)
        snapshot = []
        for slot_idx, slot in eligible:
            active[slot_idx] = True
            slot.inflight += g + 1
            # conservative fill mirror: assume full acceptance until the
            # accepts land; the refund keeps page covers safe under
            # pipelining (an overestimate can only widen the cover)
            slot.fill += g + 1
            snapshot.append((slot_idx, slot.gen))
            if slot.record is not None:
                slot.record.rode_batch(len(eligible))
        mask = self._active_mask(active)

        def dispatch():
            return self._run_spec(g, mask)

        step_span = self._step_span("tpu.engine.spec", snapshot,
                                    gamma=g, step=self._steps)
        dispatched_at = time.monotonic()
        if g in self._spec_fns:
            with self._clock.lap("enqueue"):
                pair = dispatch()
        else:
            pair = await self._off_loop(loop, dispatch)
        self._steps += 1
        if self.metrics is not None:
            self.metrics.record_histogram(
                "app_tpu_batch_size", float(len(snapshot)),
                model=self.model_name)
            self.metrics.set_gauge("app_tpu_spec_gamma", float(g),
                                   model=self.model_name)
            self.metrics.increment_counter(
                "app_tpu_attn_kernel_total", model=self.model_name,
                path=self.attn_path)

        def fetch(pair=pair):
            return np.asarray(pair[0]), np.asarray(pair[1])

        family = f"spec_paged[g={g}]" if self.paged else f"spec[g={g}]"
        return _Fetch("spec", (snapshot, g), fetch, dispatched_at,
                      span=step_span, family=family)

    def _kind_pages(self, tokens: int, pinned: int = 0
                    ) -> Dict[str, Tuple[int, int]]:
        """A kind's table columns a context of ``tokens`` holds pages of
        the slot's own for, as (first column, how many): all of them
        after the ``pinned`` columns of a reused prefix, or from the page
        a window kind's first attended position (that of the next
        token's query) lies in."""
        out = {}
        for name, kind in self._pool.kinds.items():
            first = (pinned if kind.window is None
                     else self._window_column(tokens, kind.window))
            out[name] = (first, -(-tokens // self.kv_page) - first)
        return out

    def _window_column(self, tokens: int, window: int) -> int:
        """The table column of the first position a query after
        ``tokens`` tokens attends in a layer that looks ``window`` back."""
        return max(tokens - window + 1, 0) // self.kv_page

    def _cover_pages(self, eligible, k: int):
        """Give back a window kind's pages that lie wholly behind the
        first position this tick attends, then grow each participating
        slot's page chains, a kind, to cover its fill + k tokens,
        reclaiming cold prefix pages when the free list runs short.
        Slots that cannot be covered sit this tick out (admission
        backpressure, not an error): their pages come back when other
        slots complete. Ticks already dispatched read the pages given
        back before any later program writes them: the device runs the
        programs in order, each with the table it was dispatched with."""
        reclaim = (self._prefix.evict_one
                   if self._prefix is not None else None)
        windows = [(name, kind.window)
                   for name, kind in self._pool.kinds.items()
                   if kind.window is not None]
        covered = []
        for slot_idx, slot in eligible:
            for name, window in windows:
                chain = slot.chains[name]
                keep = self._window_column(slot.fill, window)
                behind = keep - chain[0]
                if behind > 0:
                    self._pool.release_behind(chain[1][:behind], name)
                    self._tables[name][slot_idx, chain[0]:keep] = \
                        self._pool.sentinel_of(name)
                    chain[0], chain[1] = keep, chain[1][behind:]
                    self._table_version += 1
            columns = -(-(slot.fill + k) // self.kv_page)
            grown = short = False
            for name, (first, pages) in slot.chains.items():
                want = columns - first - len(pages)
                if want <= 0:
                    continue
                ids = self._pool.alloc(want, kind=name, reclaim=reclaim)
                if ids is None:
                    short = True
                    break
                at = first + len(pages)
                self._tables[name][slot_idx, at:at + want] = ids
                pages.extend(ids)
                grown = True
            if grown:
                self._table_version += 1
            if short:
                self._page_stalls += 1
                continue
            if grown and slot.record is not None:
                slot.record.pages_held = slot.held_pages()
            covered.append((slot_idx, slot))
        return covered

    def _shed_newest(self, eligible) -> None:
        """Pool-wedge breaker: every decodable slot is short of pages,
        nothing is reclaimable, and no tick is in flight to free any —
        fail the NEWEST request (LIFO shed preserves the most sunk work)
        so its pages unwedge the rest."""
        slot_idx, slot = max(eligible, key=lambda e: e[1].submitted_at)
        exc = RuntimeError(
            "kv page pool wedged: no slot can grow and nothing is "
            "reclaimable; shedding the newest request")
        if self.logger is not None:
            self.logger.error(
                "engine: %s (slot %d, %d pages back to the pool)",
                exc, slot_idx, slot.held_pages())
        slot.active = False
        slot.gen += 1
        slot.inflight = 0
        self._release_slot_kv(slot_idx, slot)
        self._finish_slot(slot, "error")
        if slot.future is not None and not slot.future.done():
            slot.future.set_exception(exc)
        if slot.queue is not None:
            slot.queue.put_nowait(exc)
            slot.queue = None
        if slot_idx not in self._free:
            self._free.append(slot_idx)

    def _shed_overflow(self) -> None:
        """Bound the page-deferred deque: past the cap, the class with the
        deepest backlog sheds its own NEWEST entry — strictly within class
        before any cross-class impact, and LIFO within the class (the
        newest arrival has the least sunk queue time)."""
        while len(self._overflow) > self._overflow_cap:
            depths: Dict[str, int] = {}
            for entry in self._overflow:
                depths[entry[9]] = depths.get(entry[9], 0) + 1
            victim_cls = max(depths.items(), key=lambda kv: kv[1])[0]
            request = None
            for i in range(len(self._overflow) - 1, -1, -1):
                if self._overflow[i][9] == victim_cls:
                    request = self._overflow[i]
                    del self._overflow[i]
                    break
            if request is None:      # unreachable: victim_cls came from
                return               # the deque itself
            prompt, bucket, budget, eos_id, sampling, future, queue, \
                submitted_at, flight, cls, grammar = request
            exc = RuntimeError(
                f"admission overflow: more than {self._overflow_cap} "
                f"page-deferred requests; shedding the newest {cls!r} "
                f"entry (deepest class)")
            if not future.done():
                future.set_exception(exc)
            if queue is not None:
                queue.put_nowait(exc)
            if flight.qspan is not None:
                flight.qspan.set_status("ERROR")
                flight.qspan.finish()
            self.recorder.finish(flight.record, "expired")
            self._shed_by_class[cls] = self._shed_by_class.get(cls, 0) + 1
            if self.slo is not None:
                self.slo.record_outcome("expired", cls=cls,
                                        model=self.model_name)
            if self.metrics is not None:
                self.metrics.increment_counter(
                    "app_tpu_sched_shed_total", model=self.model_name,
                    cls=cls)
            if self.logger is not None:
                self.logger.warn(
                    "engine %s: shed overflowed %s request "
                    "(backlog %d > cap %d)", self.model_name, cls,
                    len(self._overflow) + 1, self._overflow_cap)

    def _set_queue_gauges(self) -> None:
        """Per-class admission backlog gauge (WFQ pending + page-deferred
        overflow). A zero row stays published — a vanishing gauge is
        indistinguishable from a scrape gap."""
        if self.metrics is None:
            return
        depths = self._pending.depths()
        for entry in self._overflow:
            depths[entry[9]] = depths.get(entry[9], 0) + 1
        for cls, depth in depths.items():
            self.metrics.set_gauge(
                "app_tpu_admission_queue_depth", float(depth),
                model=self.model_name, cls=cls)

    def _on_pool_reset(self) -> None:
        """Shared-pool reset observer (multi-model tenancy): a co-resident
        engine rebuilt the pool every page table of THIS engine points
        into. All page ids and device handles dangle — fail outstanding
        work and re-sentinel the table. Own resets set ``_in_pool_reset``
        and skip (the reset path already rebuilds everything)."""
        if self._in_pool_reset:
            return
        self._fail_outstanding(RuntimeError(
            "shared kv page pool was reset by a co-resident engine"))
        self._fresh_tables()
        self._table_version += 1
        self._table_cache = None
        if self._prefix is not None:
            self._prefix.reset()

    def _observe_phase(self, phase: str, seconds: float) -> None:
        """``app_tpu_request_phase_seconds{model, phase}``: a request's
        ``queue`` phase (submit → slot claimed) and ``first_token`` phase
        (slot claimed → first token published: the ticks in flight ahead
        of its prefill, the prefill, the fetch), on the stamps
        ``app_tpu_ttft`` uses, so per request queue + first_token = ttft.
        A request cancelled, expired or refused before it got a slot has
        neither, like ``app_tpu_ttft``; one that got a slot and ended
        before its first token has ``queue`` alone. ``stall`` is observed
        once a request that had a first token, when it ends: the seconds
        of its decode phase (first token → end) in which the device ran
        other requests' prefill groups (``RequestRecord.stall_s``). It is
        part of the decode phase, not of TTFT."""
        if self.metrics is not None:
            self.metrics.record_histogram(
                "app_tpu_request_phase_seconds", seconds,
                model=self.model_name, phase=phase)

    def _push_tokens(self, slot_idx: int, gen: int,
                     tokens: List[int]) -> int:
        """Append generated tokens to a slot, handling eos/budget; stale
        generations (slot reclaimed since dispatch) are dropped. Returns
        how many tokens the request took."""
        slot = self._slots[slot_idx]
        if slot.gen != gen:
            return 0
        slot.inflight -= len(tokens)
        if not slot.active:
            return 0
        if not slot.tokens:
            # first published token for this request: submit → now is the
            # operator-facing TTFT — admission wait + prefill dispatch +
            # fetch (the first token is sampled in the prefill executable,
            # so no decode tick is included)
            now = time.monotonic()
            ttft = now - slot.submitted_at
            if slot.record is not None:
                slot.record.first_token()
                self._observe_phase("first_token",
                                    now - slot.record.admitted_at)
            if self.metrics is not None:
                self.metrics.record_histogram(
                    "app_tpu_ttft", ttft,
                    exemplar=({"trace_id": slot.record.trace_id}
                              if slot.record is not None
                              and slot.record.trace_id else None),
                    model=self.model_name)
            if self.slo is not None:
                self.slo.record_ttft(ttft)
            # prefill phase ends at the first token; decode begins
            if slot.phase_span is not None:
                slot.phase_span.finish()
                slot.phase_span = None
            if self.tracer is not None:
                slot.phase_span = self.tracer.start_span(
                    "decode", parent=slot.req_span)
                slot.phase_span.set_attribute("slot", slot_idx)
        pushed = 0
        # batched token shipping (ISSUE 9): under coalesce_stream the
        # whole tick's delta for this slot goes onto the queue as ONE
        # list — one wakeup, one frame — instead of a put per token.
        # TokenStream drains it token-by-token, so consumers see the
        # identical sequence either way.
        chunk: Optional[List[int]] = [] if self.coalesce_stream else None
        for token in tokens:
            if token < 0 or token >= self.cfg.vocab_size:
                # NaN/inf logits argmax to implementation-defined ids; an
                # out-of-range token is the host-visible symptom. Fail
                # THIS request, not the tick (ISSUE 14 quarantine).
                if chunk and slot.queue is not None:
                    slot.queue.put_nowait(chunk)
                self._quarantine_slot(
                    slot_idx, slot, "nan_logits", RuntimeError(
                        f"slot {slot_idx} produced out-of-range token "
                        f"{token} (vocab {self.cfg.vocab_size}); "
                        "NaN/inf logits upstream — request quarantined"))
                return pushed
            slot.tokens.append(token)
            slot.remaining -= 1
            pushed += 1
            if slot.record is not None:
                slot.record.tokens += 1
            if self.slo is not None:
                self.slo.record_tokens(1)   # raw throughput, as produced
            if slot.queue is not None:
                if chunk is not None:
                    chunk.append(token)
                else:
                    slot.queue.put_nowait(token)
            done = (slot.remaining <= 0
                    or (slot.eos_id is not None and token == slot.eos_id))
            if slot.grammar is not None and not done:
                # advance the walker past the emitted token; a completed
                # match — no grammar-valid continuation left — finishes
                # the slot exactly like eos (so does a violation, which
                # only sampling pathologies can produce under the bias).
                # A walker that RAISES (malformed state, bias/advance
                # disagreement) poisons only this request — quarantine it
                # rather than letting the loop catch-all fail the tick's
                # every other slot (ISSUE 14)
                try:
                    slot.grammar.advance(token)
                    done = slot.grammar.must_stop
                except Exception as exc:  # noqa: BLE001 — any walker
                    if chunk and slot.queue is not None:  # failure is
                        slot.queue.put_nowait(chunk)      # this request's
                    self._quarantine_slot(slot_idx, slot, "grammar", exc)
                    return pushed
            if done:
                slot.active = False    # rest of the chunk is discarded
                self._release_slot_kv(slot_idx, slot)
                self._free.append(slot_idx)
                if self.slo is not None:
                    # terminal classification: within deadline (or no
                    # deadline) → ok and its tokens count as goodput;
                    # late → violated (work done, value lost). A late
                    # finish carries how late plus the trace id so the
                    # violation histogram gains an exemplar pointing at
                    # a /debug/whyz-able request (ISSUE 18).
                    finished_at = time.monotonic()
                    outcome = self.slo.classify(slot.deadline, finished_at)
                    late_by_s = (finished_at - slot.deadline
                                 if slot.deadline is not None
                                 and finished_at > slot.deadline else None)
                    self.slo.record_outcome(
                        outcome,
                        tokens=float(len(slot.tokens)), cls=slot.cls,
                        model=self.model_name,
                        trace_id=(slot.record.trace_id
                                  if slot.record is not None else None),
                        late_by_s=late_by_s)
                self._finish_slot(slot, "done")
                if slot.future is not None and not slot.future.done():
                    slot.future.set_result(list(slot.tokens))
                if slot.queue is not None:
                    if chunk:
                        slot.queue.put_nowait(chunk)
                        chunk = None
                    slot.queue.put_nowait(_DONE)
                    slot.queue = None
                break
        if chunk and slot.queue is not None:
            slot.queue.put_nowait(chunk)
        if pushed and self.metrics is not None:
            # per-class tick share actually delivered — the observable
            # output of WFQ admission (weights shape THIS distribution)
            self.metrics.delta_updown_counter(
                "app_tpu_sched_tokens_total", float(pushed),
                model=self.model_name, cls=slot.cls)
        return pushed

    def _quarantine_slot(self, slot_idx: int, slot: _Slot, reason: str,
                         exc: BaseException) -> None:
        """Poison-request quarantine (ISSUE 14): one slot whose step
        output is unusable — the grammar walker blew up, or NaN/inf
        logits surfaced as an out-of-range token — is excised and failed
        individually while the tick's other slots keep their tokens and
        the loop keeps serving. Without this, the only containment is
        ``_loop``'s catch-all, which fails EVERY outstanding request and
        rebuilds device state for one poisoned request."""
        self._quarantined[reason] = self._quarantined.get(reason, 0) + 1
        if self.logger is not None:
            self.logger.error(
                "engine %s: quarantined slot %d (%s): %r",
                self.model_name, slot_idx, reason, exc)
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_tpu_slot_quarantine_total", model=self.model_name,
                reason=reason)
        slot.active = False
        slot.gen += 1
        slot.inflight = 0
        self._release_slot_kv(slot_idx, slot)
        if self.slo is not None:
            # a quarantined request is a terminal bad outcome: it must
            # burn the error budget like any other failure (ISSUE 18)
            self.slo.record_outcome("error", cls=slot.cls,
                                    model=self.model_name)
        self._finish_slot(slot, "error")
        if slot.future is not None and not slot.future.done():
            slot.future.set_exception(exc)
        if slot.queue is not None:
            slot.queue.put_nowait(exc)
            slot.queue = None
        if slot_idx not in self._free:
            self._free.append(slot_idx)

    def _release_slot_kv(self, slot_idx: int, slot: _Slot) -> None:
        """Return a finished slot's KV footprint to the shared pool
        (paged path only): its own pages drop to the free list when their
        refcount hits zero — pages adopted by the prefix trie survive
        with the trie's reference — and its pinned prefix nodes unpin
        (refcounted reclaim; eviction frees the underlying pages later).
        The table row goes back to all-sentinel so a recycled slot can
        never gather a stale page."""
        if not self.paged:
            return
        self._pool.release_slot(slot_idx)
        if slot.nodes:
            if self._prefix is not None:
                self._prefix.release(slot.nodes)
            slot.nodes = []
        for kind, (_, pages) in slot.chains.items():
            self._pool.release(pages, kind)
        slot.chains = {}
        for kind, table in self._tables.items():
            row, sentinel = table[slot_idx], self._pool.sentinel_of(kind)
            if (row != sentinel).any():
                row.fill(sentinel)
                self._table_version += 1

    def _finish_slot(self, slot: _Slot, status: str) -> None:
        """Close a slot's observability state: finish the open phase span
        (tagging non-success statuses) and retire the flight record."""
        if slot.phase_span is not None:
            if status != "done":
                slot.phase_span.set_status(
                    "ERROR" if status == "error" else "CANCELLED")
                slot.phase_span.set_attribute("outcome", status)
            slot.phase_span.finish()
            slot.phase_span = None
        if slot.record is not None:
            if slot.record.tokens:
                slot.record.first_token()   # idempotent backstop
                self._observe_phase("stall", slot.record.stall_s)
            self.recorder.finish(slot.record, status)
            slot.record = None
        slot.req_span = None
