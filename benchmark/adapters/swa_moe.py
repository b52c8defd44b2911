"""The window/global attention + sparse-expert family
(``models/swa_moe.py``) behind the generate harness, as
``adapters/mla_moe.py`` is for the latent family. ``systems.build``
finds this file by the configuration's ``module``.

- Weights: the module's own seeded ``init`` in one jitted call with the
  seed as an argument (one program for every seed; bf16).
- Set-up checks that decide ``correct``, at the configuration's widths
  (limits and their reasons: ``reference_swa_moe.LIMITS``):
  **the served check**, on the timed path: ``served_check.requests``
  concurrent requests through the socket, more than there are slots, of
  the cell's own prompt lengths, so that every slot is live, every
  bucket's prefill runs, admission groups are split by
  ``max_group_tokens`` and slots are freed and claimed again. They are
  sent once a few *deep* requests have decoded past the window by two
  pages and the pool has taken window pages back from them
  (``kv_pool.kinds.window.freed_behind``), so that the ticks that
  decide ``correct`` run with window pages released and the kernel's
  lower bound above zero. Then the reference, teacher-forced over
  prompt plus served tokens of ``served_check.sample`` of them and the
  last tokens of one deep request, says at every served token which
  token float32 would have chosen: the share of served tokens that are
  the reference's choice, and how far below it the others lie, have a
  limit each.
  **The probe**, before the pool takes the chip's rest: ``prefill`` of a
  prompt longer than the window, its rows put into pages of a pool of
  both kinds through the pool's own tables (the window kind given no
  page for the columns behind the window), then ``probe.steps``
  teacher-forced ``decode_step_paged`` steps (the ragged kernel on a
  TPU, the gather formulation elsewhere) against the reference over all
  the tokens at once; every probed position lies past the window. How
  often the program's routing differs from the reference's is measured
  beside it and has a limit too. Then, as for every generate
  configuration: the same greedy prompt twice through the socket, no
  serve-time compile, the expected attention path.
- ``main()`` runs the same set-up and verdict without a window, on the
  program or on a *control* in its place that has to come out not
  correct: the reference with the window ignored on the sliding layers,
  with rotary on the full layers too, or rounded through float8.
  Without the first two ``correct`` would not notice the mechanism
  missing.
- ``bytes_swa_moe``'s functions are made reachable to
  ``layers.read_roofline`` as ``decode_step_bytes_swa_moe``,
  ``attn_call_bytes_swa_moe`` and ``prefill_attn_flops_swa_moe``.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _path in (BENCH, os.path.dirname(BENCH)):       # as run.py does
    if _path not in sys.path:
        sys.path.insert(0, _path)

import bytes as bytes_mod
import bytes_swa_moe
import reference_swa_moe as reference
import systems
import traffic as traffic_mod
import weights

bytes_mod.decode_step_bytes_swa_moe = bytes_swa_moe.decode_step_bytes
bytes_mod.attn_call_bytes_swa_moe = bytes_swa_moe.attn_call_bytes
bytes_mod.prefill_attn_flops_swa_moe = bytes_swa_moe.prefill_attn_flops

CONTROLS = {"window": {"ignore_window": True},
            "rope-full": {"rope_full": True},
            "float8_e4m3fn": {"round_to": "float8_e4m3fn"},
            "bf16-router": {"router_round_to": "bfloat16"},
            None: {}}


class Adapter(systems.GenerateSystem):

    # main()'s control: the switches of reference.forward_logits (types
    # as names) that make another system of the reference, which then
    # stands in the program's place at every comparison with it
    control: Dict[str, Any] = {}

    async def start(self) -> None:
        import jax
        import jax.numpy as jnp

        from gofr_tpu.app import App
        from gofr_tpu.container import new_mock_container
        from gofr_tpu.http.response import Stream
        from gofr_tpu.tpu.generate import GenerationEngine
        from gofr_tpu.tpu.page_pool import PagePool, cache_kinds

        config = self.config
        module, cfg = systems.model_config(config)
        self.cfg = cfg
        self.params = jax.jit(
            lambda s: module.init(cfg, jax.random.key(s)))(
            jnp.uint32(self.seed % (2 ** 31 - 1)))
        jax.block_until_ready(self.params)
        self.notes["weight_bytes"] = weights.tree_bytes(self.params)
        self._reference: Dict[Tuple, Any] = {}      # jitted, by switches
        self.probe(module, cfg)

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
        self.notes.update(kv_pool_bytes=pool["pool_bytes"],
                          kv_pages={name: kind["num_pages"] for name, kind
                                    in pool["kinds"].items()},
                          attn_path=engine.attn_path,
                          attn_why=engine.attn_reason,
                          weights=engine.stats().get("weights"))
        await engine.warmup(prompt_counts=tuple(engine._n_ladder),
                            windows="all")
        self.notes["warm_compiles"] = engine.stats()["compiles"]["warmup"]

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
        await self.served_check()

    async def ask(self, prompt: Sequence[int], new_tokens: int) -> List[int]:
        """One request through the socket: the tokens of its stream."""
        body = json.dumps({"prompt_ids": list(prompt),
                           "max_new_tokens": new_tokens}).encode()
        status, payload = await systems.http(self.port, "POST",
                                             self.config["route"], body)
        events = [line[6:].decode() for line in payload.split(b"\n")
                  if line.startswith(b"data: ")]
        if status != 200 or not events or events[-1] != "[DONE]":
            raise systems.CheckFailed(f"probe request: status {status}, "
                                      f"tail {events[-2:]}")
        return [json.loads(e)["token"] for e in events[:-1]]

    def reference_logits(self, tokens: Sequence[int],
                         positions: Sequence[int], **switches
                         ) -> Tuple[np.ndarray, np.ndarray]:
        """The reference over ``tokens``: its logits at ``positions``
        and the experts it chose at every token. One executable a set
        of switches: the tokens are padded to the longest sequence any
        check sends (what follows a position does not move it)."""
        import jax
        import jax.numpy as jnp

        config = self.config
        probe, served = config["probe"], config["served_check"]
        deep = served["deep"]
        longest = max(
            probe["prompt"] + probe["steps"],
            max(config["engine"]["prompt_buckets"])
            + max(served["new_tokens"]) - 1,
            deep["prompt"] + deep["new_tokens"] - 1)
        most = max(probe["steps"] + 1, max(served["new_tokens"]))
        key = tuple(sorted(switches.items()))
        if key not in self._reference:
            typed = {name: getattr(jnp, value) if isinstance(value, str)
                     else value for name, value in switches.items()}
            self._reference[key] = jax.jit(
                lambda p, t, at: reference.forward_logits(
                    p, config, t, positions=at, **typed))
        padded = np.zeros((longest,), np.int32)
        padded[:len(tokens)] = tokens
        at = np.full((most,), positions[-1], np.int32)
        at[:len(positions)] = positions
        logits, routes = self._reference[key](self.params, padded, at)
        return (np.asarray(logits)[:len(positions)],
                np.asarray(routes)[:, :len(tokens)])

    async def served_check(self) -> None:
        """The timed path against the reference: see the module's
        docstring. Fills ``checks["served_*"]``."""
        config, cfg, seed = self.config, self.cfg, self.seed
        spec, engine = config["served_check"], self.engine
        count, deep = spec["requests"], spec["deep"]
        window, page = cfg.sliding_window, engine.kv_page
        rng = np.random.default_rng([int(seed), 0x5E4D])
        lengths = [int(length) for length in rng.permutation(
            traffic_mod.quantile_set(self.traffic["lengths"]["prompt"],
                                     count))]
        budgets = [spec["new_tokens"][i % len(spec["new_tokens"])]
                   for i in range(count)]
        lengths += deep["requests"] * [deep["prompt"]]
        budgets += deep["requests"] * [deep["new_tokens"]]
        prompts = [traffic_mod.prompt_ids(seed, (1 << 30) + 2 + i, length,
                                          cfg.vocab_size)
                   for i, length in enumerate(lengths)]
        asks = [self.ask(prompt, budget)
                for prompt, budget in zip(prompts, budgets)]

        def freed() -> int:
            return engine.stats()["kv_pool"]["kinds"]["window"][
                "freed_behind"]

        before, freed_before = engine.stats(), freed()
        first = [asyncio.ensure_future(ask) for ask in asks[count:]]
        # past the window by two pages: each deep request has given back
        # the pages behind it at least twice
        wanted = freed_before + deep["requests"] * max(
            3, (deep["prompt"] - window) // page + 3)
        waited = 0.0
        while freed() < wanted:
            if waited > systems.READ_TIMEOUT_S or all(
                    task.done() for task in first):
                raise systems.CheckFailed(
                    f"served check: the deep requests never gave back "
                    f"{wanted - freed_before} window pages (got "
                    f"{freed() - freed_before})")
            await asyncio.sleep(0.02)
            waited += 0.02
        deep_context = min(
            slot.fill for slot in engine._slots if slot.active)
        replies = list(await asyncio.gather(*asks[:count]))
        overlapped = sum(not task.done() for task in first)
        replies += await asyncio.gather(*first)
        after = engine.stats()
        short = [i for i, (reply, budget) in enumerate(zip(replies, budgets))
                 if len(reply) != budget]
        # a sample spread over the prompt lengths, shortest and longest
        # among them, and one of the deep requests: its last tokens
        by_length = np.argsort(lengths[:count], kind="stable")
        sample = by_length[np.linspace(0, count - 1, spec["sample"])
                           .round().astype(int)].tolist() + [count]
        most = max(spec["new_tokens"])
        agree, margins, each = [], [], []
        for i in sample:
            prompt, served = prompts[i], replies[i]
            if not served:
                continue
            sequence = prompt + served[:-1]
            positions = range(len(prompt) - 1, len(sequence))[-most:]
            served = served[-most:]
            want, _ = self.reference_logits(sequence, positions)
            if self.control:
                served = self.reference_logits(
                    sequence, positions, **self.control
                )[0].argmax(-1).tolist()
            rows = np.arange(len(served))
            chosen = want.argmax(-1)
            same = chosen == np.asarray(served)
            # how far below the reference's choice the served token
            # lies, in standard deviations of that position's logits
            below = (want[rows, chosen] - want[rows, served]) / want.std(-1)
            margins += below.tolist()
            agree += same.tolist()
            each.append({"request": int(i), "prompt": len(prompt),
                         "tokens": len(served), "share": float(same.mean()),
                         "margin_max": float(below.max()),
                         "margin_max_at": int(below.argmax())})
        self.checks.update(
            served_requests=len(asks),
            served_short=short[:8],
            served_beside_deep=overlapped,
            served_deep_context=int(deep_context),
            served_deep_context_min=window + 2 * page,
            served_window_pages_freed=freed() - freed_before,
            served_prefill_batches=(after["prefill_batches"]
                                    - before["prefill_batches"]),
            served_ticks=after["decode_steps"] - before["decode_steps"],
            served_tokens_checked=len(agree),
            served_argmax_share=float(np.mean(agree)) if agree else 0.0,
            served_margin_p99=(float(np.percentile(margins, 99))
                               if margins else float("inf")),
            served_margin_top=sorted(margins)[-8:],
            served_each=each)

    def probe(self, module, cfg) -> None:
        """Prefill of a prompt longer than the window, then teacher-forced
        paged decode steps through both kinds of layer, against the
        reference's one forward over all the tokens."""
        import jax
        import jax.numpy as jnp

        from gofr_tpu.tpu.page_pool import PagePool

        config, params = self.config, self.params
        prompt, steps = config["probe"]["prompt"], config["probe"]["steps"]
        page = config["engine"]["kv_page"]
        if prompt % page or prompt <= cfg.sliding_window:
            raise systems.CheckFailed(
                "probe.prompt must fill whole pages and pass the window")
        tokens = traffic_mod.prompt_ids(self.seed, 1 << 30, prompt + steps,
                                        cfg.vocab_size)
        positions = range(prompt - 1, prompt + steps)
        want, want_routes = self.reference_logits(tokens, positions)
        self.router_check(module, cfg, params)
        if self.control:
            got, routes = self.reference_logits(tokens, positions,
                                                **self.control)
            self.compare(cfg, got, routes[:, :prompt], want,
                         want_routes[:, :prompt])
            return
        tokens = jnp.asarray(tokens, jnp.int32)

        def prefill(p, t):
            logits, small, cache_len, routes = module.prefill(
                p, cfg, t[None], module.init_cache(cfg, 1, t.shape[0]),
                routes=True)
            return logits[0], small, cache_len, routes[:, 0]

        first, small, cache_len, routes = jax.jit(prefill)(
            params, tokens[:prompt])
        got = [np.asarray(first)]
        # the prompt's rows into pages of a pool, in another order than
        # the sequence's, through the pool's own tables; the window kind
        # gets no page for the columns behind the window
        columns = -(-(prompt + steps) // page)
        kinds = module.cache_leaves(cfg)
        firsts = {name: 0 if kind["window"] is None
                  else (prompt - kind["window"] + 1) // page
                  for name, kind in kinds.items()}
        pool = PagePool(cfg, page=page, leaf_specs=kinds, num_pages={
            name: columns - firsts[name] + 3 for name in kinds})
        tables = {}
        for name in kinds:
            row = np.full((1, columns), pool.sentinel_of(name), np.int32)
            row[0, firsts[name]:] = pool.alloc(
                columns - firsts[name], kind=name)[::-1]
            tables[name] = jnp.asarray(row)

        def insert(leaves, small):
            out = {}
            for name, kind in leaves.items():
                cols = tables[name][0, firsts[name]:prompt // page]
                out[name] = {
                    leaf: kind[leaf].at[:, cols].set(
                        small[name][leaf][:, 0, firsts[name] * page:]
                        .reshape(kind[leaf].shape[0], -1, page,
                                 *kind[leaf].shape[3:]))
                    for leaf in kind}
            return out

        leaves = jax.jit(insert, donate_argnums=0)(pool.leaves, small)
        ragged = jax.default_backend() == "tpu"
        step = jax.jit(lambda p, token, leaves, cache_len:
                       module.decode_step_paged(
                           p, cfg, token, leaves, tables, cache_len,
                           jnp.ones((1,), bool), ragged=ragged),
                       donate_argnums=2)
        for i in range(steps):
            logits, leaves, cache_len = step(
                params, tokens[prompt + i][None], leaves, cache_len)
            got.append(np.asarray(logits[0]))
        self.checks["probe_path"] = "ragged" if ragged else "gather"
        self.compare(cfg, got, np.asarray(routes), want,
                     want_routes[:, :prompt])

    def router_check(self, module, cfg, params) -> None:
        """The router alone: the program's ``route`` and the reference's
        on one input, every layer's router over 512 rows of the
        activation type: holds the router itself to the float32 the
        configuration states."""
        import jax
        import jax.numpy as jnp

        h = jax.random.normal(jax.random.key(self.seed % (2 ** 31 - 1)),
                              (512, cfg.dim), jnp.float32).astype(cfg.dtype)
        routers = jnp.concatenate(
            [layer["router"] for layer in params["layers"]])

        def theirs(round_to=None):
            with jax.default_matmul_precision("highest"):
                return jax.jit(jax.vmap(lambda router: reference.route(
                    router, h.astype(jnp.float32), self.config,
                    round_to)[0]))(routers)

        if self.control:
            rounded = self.control.get("router_round_to")
            ours = theirs(rounded and getattr(jnp, rounded))
        else:
            ours = jax.jit(jax.vmap(
                lambda router: module.route(cfg, router, h)[0]))(routers)
        theirs = theirs()
        differs = (np.sort(np.asarray(ours), -1)
                   != np.sort(np.asarray(theirs), -1)).any(-1)
        self.checks["router_swap_share"] = float(differs.mean())

    def compare(self, cfg, got, routes, want, want_routes) -> None:
        """Fills ``checks`` with the probe's readings: the logits'
        distance at each position, and the routing over the prompt:
        (token, layer) pairs whose chosen set differs from the
        reference's, and those where the difference touches an expert
        held here."""
        each = [reference.rel_l2(g, w) for g, w in zip(got, want)]
        ours, theirs = np.sort(routes, -1), np.sort(want_routes, -1)
        first_held = cfg.expert_rank * cfg.n_held_experts
        differs = (ours != theirs).any(-1)
        held = np.zeros_like(differs)
        for layer, token in zip(*np.nonzero(differs)):
            swapped = set(ours[layer, token]) ^ set(theirs[layer, token])
            held[layer, token] = any(
                first_held <= e < first_held + cfg.n_held_experts
                for e in swapped)
        self.checks.update(
            logits_rel_l2_each=each,
            logits_rel_l2=float(np.percentile(each, 25)),
            logits_tol=reference.LIMITS["logits_rel_l2_q1"],
            logits_rel_l2_max=max(each),
            route_swap_share=float(differs.mean()),
            route_held_swap_share=float(held.mean()),
            limits=reference.LIMITS)

    def verdict(self) -> List[str]:
        faults = super().verdict()       # the lower quartile, and the rest
        checks = self.checks
        for name, what in (
                ("logits_rel_l2_max", "logits of one of the probed "
                 "positions differ from the reference: relative L2"),
                ("router_swap_share", "share of rows the router alone "
                 "routes otherwise than the reference's on the same input"),
                ("route_swap_share", "share of (token, layer) pairs "
                 "of the probe routed otherwise than by the reference"),
                ("route_held_swap_share", "share of the probe's pairs "
                 "routed otherwise where a held expert is touched"),
                ("served_margin_p99", "one served token in a hundred lies "
                 "below the reference's choice, in deviations of its "
                 "logits, by")):
            if checks[name] > reference.LIMITS[name]:
                faults.append(f"{what} {checks[name]:.4g} > "
                              f"{reference.LIMITS[name]}")
        if checks["served_argmax_share"] \
                < reference.LIMITS["served_argmax_share_min"]:
            faults.append(
                f"only {checks['served_argmax_share']:.4g} of "
                f"{checks['served_tokens_checked']} served tokens are the "
                f"reference's choice, under "
                f"{reference.LIMITS['served_argmax_share_min']}")
        if not checks["served_beside_deep"]:
            faults.append("the served check's requests outlasted the deep "
                          "ones: not all were served beside a slot past "
                          "the window")
        if checks["served_deep_context"] < checks["served_deep_context_min"]:
            faults.append(
                f"the deep requests were at {checks['served_deep_context']} "
                f"tokens when the others were sent, not past the window "
                f"by two pages ({checks['served_deep_context_min']})")
        if checks["served_short"]:
            faults.append(f"served-check requests {checks['served_short']} "
                          f"did not stream their whole budget")
        return faults


def main() -> None:
    """The cell's set-up and its verdict without a window: the readings
    the limits are set between. ``--control`` puts a system that has to
    come out not correct in the program's place at every comparison: the
    reference with the window ignored on the sliding layers
    (``window``), with rotary on the full layers too (``rope-full``),
    with its activations rounded through ``float8_e4m3fn`` (the nearest
    precision below the configuration's bfloat16), or with its router's
    scores in ``bfloat16``.

        python3 benchmark/adapters/swa_moe.py --seed 7 \\
            [--control window|rope-full|float8_e4m3fn|bf16-router]
    """
    import argparse

    parser = argparse.ArgumentParser(description=main.__doc__)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--control", default=None,
                        choices=[c for c in CONTROLS if c])
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    parser.add_argument("--size", choices=("full", "tiny"), default="full")
    parser.add_argument("--workload", default="command-a-plus-ep8.mixed")
    args = parser.parse_args()
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
        cell = next(w for w in json.load(handle)["workloads"]
                    if w["name"] == args.workload)
    config = run.load_json("configs", f"{cell['config']}.json")
    traffic = run.load_json("traffic", f"{cell['traffic']}.json")
    if args.size == "tiny":
        traffic = run.rehearsal_traffic(traffic, config["tiny"])
    adapter = Adapter(systems.published(config, args.size), traffic,
                      args.seed)
    adapter.control = CONTROLS[args.control]

    async def checked() -> List[str]:
        await adapter.start()
        try:
            return adapter.verdict()
        finally:
            await adapter.stop()

    faults = asyncio.run(checked())
    print(json.dumps({
        "control": args.control, "correct": not faults, "faults": faults,
        "device": jax.devices()[0].device_kind,
        "checks": adapter.checks}, default=str), flush=True)


if __name__ == "__main__":
    main()
