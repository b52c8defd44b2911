"""The sparse-attention member of the latent-attention / sparse-expert
family (``models/dsa_mla_moe.py``) behind the generate harness, built on
``adapters/hc_mla_moe.py``: its served check (the deep requests first,
then the others through the socket, judged against the reference
teacher-forced), its verdict and its controls' plumbing are used as
they are, with this family's set-up, probe and reference below.
``systems.build`` finds this file by the configuration's ``module``.

- Weights: the module's own seeded ``init`` in one jitted call with the
  seed as an argument (bf16; the router's bias float32).
- The pool: a kind's pages from ``pool_pages_max``, scaled to what the
  chip has left less ``pool_headroom_bytes``.
- Set-up checks that decide ``correct`` (limits and their reasons:
  ``reference_dsa_mla_moe.LIMITS``):
  **the probe**, before the pool takes the chip's rest: ``prefill`` of
  ``probe.prompt`` tokens and ``probe.steps`` paged decode steps through
  a ``PagePool`` of both kinds (the index kernel, the top-k, the
  gathered rows), against the reference's logits *given the program's
  selection and routing*; the program's selection against the
  reference's own (a share of overlap, over the prompt's rows and over
  the decode steps' rows alone); the routing against the reference's
  own; **the router alone**, bias and all; **the index kernel alone**
  against its gathered oracle on the probe's pool.
  **The served check**: ``adapters/hc_mla_moe.py``'s.
- The reference is one jitted program for every comparison of set-up
  and of ``main()``: the tokens padded to the longest sequence a check
  reads, the routing and the selection given or left to the reference,
  and the controls' switches operands (``reference.SWITCHES``), so it
  compiles once (each of its forms was ~55 s of compiling at the cell's
  widths, described-chip compile). ``notes["setup_s_parts"]`` says where
  set-up's time went, ``notes["warm_compiles_at"]`` when the engine's
  warm-up began each program.
- ``main()`` runs the same set-up and verdict without a window, on the
  program and then on each *control* in its place (``--control``):
  the reference with the selection ignored, with the indexer's ReLU
  dropped, with its keys not normed, with the window ignored, with the
  gates dropped, with float8 weights; and the program's own probe with
  the index keys of the slot's last tenant in the pool. Each has to come
  out not correct.
- ``bytes_dsa_mla_moe``'s functions are made reachable to
  ``layers.read_roofline`` as ``decode_step_bytes_dsa_mla_moe`` and
  ``index_call_bytes_dsa_mla_moe``.
"""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _path in (BENCH, os.path.dirname(BENCH)):       # as run.py does
    if _path not in sys.path:
        sys.path.insert(0, _path)

import bytes as bytes_mod
import bytes_dsa_mla_moe
import reference_dsa_mla_moe as reference
import systems
import traffic as traffic_mod
import weights

_spec = importlib.util.spec_from_file_location(
    "adapter_hc_mla_moe", os.path.join(BENCH, "adapters", "hc_mla_moe.py"))
hc_adapter = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(hc_adapter)

bytes_mod.decode_step_bytes_dsa_mla_moe = bytes_dsa_mla_moe.decode_step_bytes
bytes_mod.index_call_bytes_dsa_mla_moe = bytes_dsa_mla_moe.index_call_bytes

# a control's switches: of reference.forward_logits (types as names), or
# of the program's own probe (PROGRAM_SWITCHES)
CONTROLS = {"dense": {"dense": True},
            "no-relu": {"no_relu": True},
            "no-ik-norm": {"no_ik_norm": True},
            "no-window": {"no_window": True},
            "no-gates": {"no_gates": True},
            "stale-index-keys": {"stale_index_keys": True},
            "float8-weights": {"float8_weights": True},
            None: {}}
PROGRAM_SWITCHES = {"stale_index_keys"}


def _overlap(picked, own, first: int) -> np.ndarray:
    """Rows ``first..`` of two selections (..., S, S) bool: the keys in
    common over the larger of the two, a row at a time."""
    a, b = picked[..., first:, :], own[..., first:, :]
    common = (a & b).sum(-1)
    return common / np.maximum(np.maximum(a.sum(-1), b.sum(-1)), 1)


class Adapter(hc_adapter.Adapter):

    def lap(self, part: str) -> None:
        """Charge the time since the last lap to ``part`` of
        ``notes["setup_s_parts"]``."""
        now = time.monotonic()
        parts = self.notes.setdefault("setup_s_parts", {})
        parts[part] = round(now - getattr(self, "_lapped", now), 3)
        self._lapped = now

    async def start(self) -> None:
        import jax
        import jax.numpy as jnp

        from gofr_tpu.app import App
        from gofr_tpu.container import new_mock_container
        from gofr_tpu.http.response import Stream
        from gofr_tpu.tpu.generate import GenerationEngine
        from gofr_tpu.tpu.page_pool import PagePool, cache_kinds

        config = self.config
        self._lapped = time.monotonic()
        module, cfg = systems.model_config(config)
        self.cfg = cfg
        self.params = jax.jit(
            lambda s: module.init(cfg, jax.random.key(s)))(
            jnp.uint32(self.seed % (2 ** 31 - 1)))
        jax.block_until_ready(self.params)
        self.notes["weight_bytes"] = weights.tree_bytes(self.params)
        self._reference: Dict[int, Tuple[Any, Any]] = {}   # by length
        self.lap("weights")
        self.probe(module, cfg, self.params)

        settings = dict(config["engine"])
        settings["prompt_buckets"] = tuple(settings["prompt_buckets"])
        pages = {kind: int(n) for kind, n in config["pool_pages_max"].items()}
        memory = jax.devices()[0].memory_stats()
        if memory:                               # none on the CPU
            left = memory["bytes_limit"] - memory["bytes_in_use"]
            pool_bytes = left - int(config["pool_headroom_bytes"])
            if pool_bytes <= 0:
                raise systems.CheckFailed(
                    f"no room for a page pool: {left} bytes left beside "
                    f"the weights")
            wanted = sum(
                pages[kind.name] * PagePool._kind_page_bytes(
                    kind, settings["kv_page"])
                for kind in cache_kinds(cfg, module.cache_leaves(cfg)))
            share = min(1.0, pool_bytes / wanted)
            pages = {name: int(n * share) for name, n in pages.items()}
        container = new_mock_container()
        container.logger = self.logger
        self.metrics = container.metrics
        engine = GenerationEngine(cfg, self.params, kv_pages=pages,
                                  model_module=module, logger=self.logger,
                                  metrics=container.metrics, **settings)
        self.engine = engine
        # one tree on the device: the engine's, whose re-laid leaves
        # would otherwise lie beside ours
        self.params = engine.params
        pool = engine.stats()["kv_pool"]
        self.lap("engine")
        self.notes.update(kv_pool_bytes=pool["pool_bytes"],
                          kv_pages={name: kind["num_pages"] for name, kind
                                    in pool["kinds"].items()},
                          attn_path=engine.attn_path,
                          attn_why=engine.attn_reason,
                          attention_paths=engine.attention_paths(),
                          weights=engine.stats().get("weights"))
        began = time.monotonic()
        await engine.warmup(prompt_counts=tuple(engine._n_ladder),
                            windows="all")
        self.notes["warm_compiles"] = engine.stats()["compiles"]["warmup"]
        self.notes["warm_compiles_at"] = [
            [round(at - began, 1), name]
            for at, kind, name in engine._compile_events if kind == "warmup"]
        self.lap("warmup")

        app = App(config=container.config, container=container)
        app.http_port = app.metrics_port = 0

        async def generate_stream(ctx):
            data = ctx.bind()
            stream = await engine.generate_stream(
                data["prompt_ids"],
                max_new_tokens=int(data["max_new_tokens"]))

            async def frames():
                try:
                    async for token in stream:
                        yield json.dumps({"token": token})
                    yield "[DONE]"
                finally:
                    await stream.aclose()

            return Stream(frames(), sse=True, on_close=stream.cancel)

        app.post(config["route"], generate_stream)
        self.app = app
        await engine.start()
        await app.start()
        self.port = app._http_server.bound_port

        # the same greedy prompt, alone, twice: the same tokens
        prompt = traffic_mod.prompt_ids(self.seed, (1 << 30) + 1, 48,
                                        cfg.vocab_size)
        twice = [await self.ask(prompt, 12) for _ in range(2)]
        self.checks["greedy_repeatable"] = twice[0] == twice[1]
        self.checks["greedy_frames"] = len(twice[0])
        self.lap("greedy")
        await self.served_check()

    async def served_check(self) -> None:
        await self.serve_check_requests()
        self.lap("served_requests")
        self.judge_served()
        self.lap("served_reference")

    def reference_logits(self, tokens: Sequence[int],
                         positions: Sequence[int], routes=None,
                         selections=None, **switches):
        """The reference over ``tokens``: its logits at ``positions``,
        and (the experts it chose at every token (expert layers, tokens,
        top_k), each full layer's own selection (full layers, tokens,
        tokens) bool); with ``routes`` and ``selections`` (both or
        neither) the experts and keys it is *given*. One program: the
        tokens padded to the longest sequence a check reads (what
        follows a position does not move it), the routing and selection
        operands (-1 and empty rows where not given), the switches of
        ``reference.SWITCHES`` an operand of flags; a program's switches
        are not the reference's and are left out."""
        import jax
        import jax.numpy as jnp

        config, cfg = self.config, self.cfg
        probe, served = config["probe"], config["served_check"]
        deep = served["deep"]
        most = max(probe["steps"] + 1, max(served["new_tokens"]))
        longest = max(probe["prompt"] + probe["steps"],
                      deep["prompt"] + deep["new_tokens"])
        if len(tokens) > longest:
            raise systems.CheckFailed(
                f"a reference sequence of {len(tokens)} tokens is past the "
                f"longest a check reads, {longest}")
        unknown = set(switches) - set(reference.SWITCHES) - PROGRAM_SWITCHES
        if unknown:
            raise ValueError(f"unknown switches {sorted(unknown)}")
        flags = np.array([bool(switches.get(name))
                          for name in reference.SWITCHES])
        full = cfg.kinds.count("full")
        if longest not in self._reference:
            self._reference[longest] = (
                jax.jit(lambda p, t, at, r, s, on: reference.forward_logits(
                    p, config, t, positions=at, routes=r, selections=s,
                    **dict(zip(reference.SWITCHES, on)))),
                jnp.zeros((full, longest, longest), bool))
        forward, none_picked = self._reference[longest]
        padded = np.zeros((longest,), np.int32)
        padded[:len(tokens)] = tokens
        at = np.full((most,), positions[-1], np.int32)
        at[:len(positions)] = positions
        n = len(tokens)
        chosen = np.full((cfg.n_layers - cfg.n_dense_layers, longest,
                          cfg.top_k), -1, np.int32)
        picked = none_picked
        if routes is not None:
            chosen[:, :n] = routes
            picked = np.zeros((full, longest, longest), bool)
            picked[:, :n, :n] = selections
        logits, own_routes, own_picked = forward(
            self.params, padded, at, chosen, picked, flags)
        return (np.asarray(logits)[:len(positions)],
                (np.asarray(own_routes)[:, :n],
                 np.asarray(own_picked[:, :n, :n])))

    def probe(self, module, cfg, params) -> None:
        """Prefill, then teacher-forced paged decode steps, against the
        reference's one forward over all the tokens *given the system's
        selection and routing*; the selection and the routing against
        the reference's own beside it; the router alone; the index
        kernel alone."""
        config = self.config
        prompt, steps = config["probe"]["prompt"], config["probe"]["steps"]
        page = config["engine"]["kv_page"]
        if prompt % page:
            raise systems.CheckFailed("probe.prompt must fill whole pages")
        tokens = traffic_mod.prompt_ids(self.seed, 1 << 30, prompt + steps,
                                        cfg.vocab_size)
        positions = range(prompt - 1, prompt + steps)
        self.router_check(module, cfg, params)
        self.lap("probe_router")
        if self.control and not set(self.control) & PROGRAM_SWITCHES:
            got, (routes, picked) = self.reference_logits(
                tokens, positions, **self.control)
        else:
            got, routes, picked = self.program_logits(
                module, cfg, params, tokens, prompt, steps, page,
                stale=bool(self.control.get("stale_index_keys")))
        self.lap("probe_system")
        want, (own_routes, own_picked) = self.reference_logits(
            tokens, positions, routes=routes, selections=picked)
        self.lap("probe_reference")
        each = [reference.rel_l2(g, w) for g, w in zip(got, want)]
        differs = (np.sort(routes, -1) != np.sort(own_routes, -1)).any(-1)
        first = cfg.index_topk                 # rows that select
        overlap = _overlap(picked, own_picked, first)      # (layers, rows)
        self.checks.update(
            logits_rel_l2_each=each,
            logits_rel_l2=float(np.percentile(each, 25)),
            logits_tol=reference.LIMITS["logits_rel_l2_q1"],
            logits_rel_l2_max=max(each),
            route_swap_share=float(differs.mean()),
            select_overlap_each=overlap.mean(-1).tolist(),
            select_overlap_min=float(overlap.mean(-1).min()),
            decode_overlap_min=float(
                overlap[:, prompt - first:].mean(-1).min()),
            limits=reference.LIMITS)

    def program_logits(self, module, cfg, params, tokens, prompt, steps,
                       page, stale: bool = False):
        """The program over the probe's tokens: the prefill's last
        logits and those of ``steps`` paged decode steps, the experts it
        chose at every token (expert layers, tokens, top_k), and the
        keys each full layer's rows attended (full layers, tokens,
        tokens) bool. ``stale``: the pool's index keys of the prompt are
        another prompt's, as if the slot's last tenant had left them
        (a control). Then the index kernel alone on the pool."""
        import jax
        import jax.numpy as jnp

        from gofr_tpu.tpu.page_pool import PagePool

        total = prompt + steps
        tokens = jnp.asarray(tokens, jnp.int32)

        def prefill(p, t):
            logits, small, cache_len, routes, picked = module.prefill(
                p, cfg, t[None], module.init_cache(cfg, 1, t.shape[0]),
                routes=True, selections=True)
            return logits[0], small, cache_len, routes[:, 0], picked[:, 0]

        first, small, cache_len, routes, picked = jax.jit(prefill)(
            params, tokens[:prompt])
        got, chosen = [np.asarray(first)], [np.asarray(routes)]
        selected = np.zeros((picked.shape[0], total, total), bool)
        selected[:, :prompt, :prompt] = np.asarray(picked)
        del picked
        if stale:
            other = jnp.asarray(traffic_mod.prompt_ids(
                self.seed, (1 << 30) - 1, prompt, cfg.vocab_size), jnp.int32)
            _, tenant, _, _, _ = jax.jit(prefill)(params, other)
            small = dict(small, full=dict(small["full"],
                                          ik=tenant["full"]["ik"]))
        columns = -(-total // page)
        pool = PagePool(cfg, page=page,
                        num_pages={"full": columns + 3,
                                   "window": columns + 3},
                        leaf_specs=module.cache_leaves(cfg))
        # the prompt's rows into pages of a pool, in another order than
        # the sequence's, through the pool's own tables
        tables = {kind: jnp.asarray([pool.alloc(columns, kind=kind)[::-1]],
                                    jnp.int32) for kind in pool.kinds}

        def insert(leaves, small):
            return {kind: {name: leaf.at[:, tables[kind][0, :prompt // page]]
                           .set(small[kind][name][:, 0].reshape(
                               leaf.shape[0], prompt // page, page,
                               *leaf.shape[3:]))
                           for name, leaf in leaves[kind].items()}
                    for kind in leaves}

        leaves = jax.jit(insert, donate_argnums=0)(pool.leaves, small)
        del small
        step = jax.jit(lambda p, token, leaves, cache_len:
                       module.decode_step_paged(
                           p, cfg, token, leaves, tables, cache_len,
                           jnp.ones((1,), bool), routes=True,
                           selections=True), donate_argnums=2)
        for i in range(steps):
            logits, leaves, cache_len, routes, spots = step(
                params, tokens[prompt + i][None], leaves, cache_len)
            got.append(np.asarray(logits[0]))
            chosen.append(np.asarray(routes))       # (layers, 1, top_k)
            for layer, row in enumerate(np.asarray(spots)[:, 0]):
                selected[layer, prompt + i, row[row >= 0]] = True
        self.index_kernel_check(leaves["full"]["ik"], tables["full"],
                                prompt + steps)
        return got, np.concatenate(chosen, axis=1), selected

    def index_kernel_check(self, keys, table, length: int) -> None:
        """The index kernel alone on the probe's pool, every full layer:
        four slots of the probe's table at four lengths (the whole
        context, a page short of it, part of a block, none), seeded
        queries and weights, against the gathered oracle: the largest
        difference over the largest score."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from gofr_tpu.ops.attention import index_scores as oracle
        from gofr_tpu.ops.pallas import index_scores, index_tileable

        cfg = self.cfg
        key = jax.random.key(self.seed % (2 ** 31 - 1) + 2)
        q = jax.random.normal(key, (4, cfg.index_n_heads,
                                    cfg.index_head_dim)).astype(cfg.dtype)
        w = jax.random.normal(jax.random.fold_in(key, 1),
                              (4, cfg.index_n_heads), jnp.float32)
        lengths = jnp.array([length, length - 33, 200, 0], jnp.int32)
        tables = jnp.broadcast_to(table, (4, table.shape[1]))
        kernel = index_tileable(cfg.index_head_dim, cfg.index_n_heads,
                                keys.shape[2])
        worst = 0.0
        ours = jax.jit(lambda *a: index_scores(*a))
        theirs = jax.jit(lambda keys, layer: oracle(
            q, w, lax.dynamic_index_in_dim(keys, layer, 0, False), tables,
            lengths))
        for layer in range(keys.shape[0]):
            want = np.asarray(theirs(keys, layer))
            got = np.asarray(ours(q, w, keys, tables, lengths,
                                  layer)) if kernel else want
            live = np.isfinite(want)
            if (np.isfinite(got) != live).any():
                worst = float("inf")
                continue
            if live.any():
                gap = np.abs(got[live] - want[live]).max()
                worst = max(worst, float(gap / max(np.abs(want[live]).max(),
                                                   1e-30)))
        self.checks.update(index_kernel_err=worst, index_kernel_ran=kernel)

    def router_check(self, module, cfg, params) -> None:
        """The router alone, its correction bias with it: the program's
        ``route`` and the reference's on one input, every expert layer's
        router over ``probe.prompt`` rows of the activation type."""
        import jax
        import jax.numpy as jnp

        rows = self.config["probe"]["prompt"]
        h = jax.random.normal(jax.random.key(self.seed % (2 ** 31 - 1)),
                              (rows, cfg.dim), jnp.float32).astype(cfg.dtype)
        layers = params["layers"][cfg.n_dense_layers:]
        routers = (jnp.stack([layer["router"] for layer in layers]),
                   jnp.stack([layer["router_bias"] for layer in layers]))

        def theirs(router, bias):
            with jax.default_matmul_precision("highest"):
                return reference._hc.route(router, h.astype(jnp.float32),
                                           self.config, None, bias)[0]

        ours = jax.jit(jax.vmap(lambda router, bias: module.route(
            cfg, router, h, bias)[0]))(*routers)
        differs = (np.sort(np.asarray(ours), -1)
                   != np.sort(np.asarray(jax.jit(jax.vmap(theirs))(
                       *routers)), -1)).any(-1)
        self.checks["router_swap_share"] = float(differs.mean())

    def probe_limits(self):
        """(check, its limit, what it says) for the probe, the router
        and the index kernel: each reads higher when worse; the overlaps
        are held from below in ``verdict``."""
        limits = reference.LIMITS
        return [
            ("logits_rel_l2", limits["logits_rel_l2_q1"],
             "logits differ from the reference given the same selection "
             "and routing, the lower quartile of the probed positions: "
             "relative L2"),
            ("logits_rel_l2_max", limits["logits_rel_l2_max"],
             "logits of one of the probed positions differ from the "
             "reference given the same selection and routing: relative L2"),
            ("router_swap_share", limits["router_swap_share"],
             "share of rows the router alone routes otherwise than the "
             "reference's on the same input"),
            ("index_kernel_err", limits["index_kernel_err"],
             "the index kernel differs from its gathered oracle on the "
             "probe's pool, over the largest score, by"),
            ("route_swap_share", limits["route_swap_share"],
             "share of (token, expert layer) pairs of the probe routed "
             "otherwise than by the reference")]

    def overlap_faults(self) -> List[str]:
        checks, limits = self.checks, reference.LIMITS
        return [f"{what}: {checks[name]:.4g} < {limits[name]}"
                for name, what in (
                    ("select_overlap_min", "a full layer's selection of "
                     "the probe shares too few keys with the reference's "
                     "own"),
                    ("decode_overlap_min", "a full layer's selection at "
                     "the decode steps shares too few keys with the "
                     "reference's own"))
                if checks[name] < limits[name]]

    def verdict(self) -> List[str]:
        return super().verdict() + self.overlap_faults()

    def judge_probe(self, switches: Dict[str, Any]) -> List[str]:
        return super().judge_probe(switches) + self.overlap_faults()


def main() -> None:
    """The cell's set-up and its verdict without a window: the readings
    the limits are set between. First the program; then each control of
    ``--control`` (names of ``CONTROLS``, comma-separated, or ``all``)
    in its place, over the same served sequences: each has to come out
    not correct. One JSON line a system.

        python3 benchmark/adapters/dsa_mla_moe.py --seed 7 \\
            [--control all|dense,no-relu,...] [--probe-only]
    """
    import argparse

    parser = argparse.ArgumentParser(description=main.__doc__)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--control", default="")
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    parser.add_argument("--size", choices=("full", "tiny"), default="full")
    parser.add_argument("--workload", default="dots3-note-ep32.longctx")
    parser.add_argument("--probe-only", action="store_true",
                        help="no engine and no served check: the probe, "
                             "the router and the index kernel alone")
    args = parser.parse_args()
    names = [name for name in CONTROLS if name] if args.control == "all" \
        else [name for name in args.control.split(",") if name]
    unknown = [name for name in names if name not in CONTROLS]
    if unknown:
        parser.error(f"unknown control(s) {unknown}; have "
                     f"{[name for name in CONTROLS if name]}")
    import run

    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from gofr_tpu.tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as handle:
        cells = json.load(handle)["workloads"]
    cell = next(w for w in cells if w["name"] == args.workload)
    config = run.load_json("configs", f"{cell['config']}.json")
    traffic = run.load_json("traffic", f"{cell['traffic']}.json")
    if args.size == "tiny":
        traffic = run.rehearsal_traffic(traffic, config["tiny"])
    adapter = Adapter(systems.published(config, args.size), traffic,
                      args.seed)
    device = jax.devices()[0].device_kind

    def report(control, faults) -> None:
        print(json.dumps({
            "control": control, "correct": not faults, "faults": faults,
            "device": device, "checks": adapter.checks}, default=str),
            flush=True)

    async def checked() -> None:
        await adapter.start()
        try:
            report(None, adapter.verdict())
            for name in names:
                report(name, adapter.judge_control(CONTROLS[name]))
        finally:
            await adapter.stop()

    if args.probe_only:
        for name in [None] + names:
            report(name, adapter.judge_probe(CONTROLS[name]))
    else:
        asyncio.run(checked())


if __name__ == "__main__":
    main()
