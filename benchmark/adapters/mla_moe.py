"""The latent-attention / sparse-expert family (``models/mla_moe.py``)
behind the generate harness: what ``systems.GenerateSystem`` does for
llama's tree, for this one. ``systems.build`` finds this file by the
configuration's ``module``.

- Weights: the module's own seeded ``init`` in one jitted call with the
  seed as an argument (one program for every seed; bf16, each draw fused
  into its leaf: 0 bytes of temporaries in the described-chip compile).
- Set-up checks that decide ``correct``, at the configuration's widths
  (limits and their reasons: ``reference_mla_moe.LIMITS``):
  **the served check**, on the timed path: ``served_check.requests``
  concurrent requests through the socket, more than there are slots, of
  the cell's own prompt lengths, so that every slot is live, admission
  groups are split by ``max_group_tokens``, every bucket's grouped
  prefill runs, and slots are freed and claimed again; they are sent
  once a few *deep* requests are past the widest narrower window rung,
  so that the K-step ticks serving them gather the full page table, as
  most of the window's ticks do. Then the reference, teacher-forced
  over prompt plus served tokens of ``served_check.sample`` of them and
  the last tokens of one deep request, says at every served token which
  token float32 would have chosen: the share of served tokens that are
  the reference's choice, and how far below it the others lie, have a
  limit each.
  **The probe**, a per-layer diagnosis beside it, before the pool takes
  the chip's rest: ``prefill`` of a ``probe.prompt``-token prompt, then
  ``probe.steps`` teacher-forced ``decode_step_paged`` steps through a
  latent ``PagePool`` and a page table, against the reference over all
  the tokens at once; how often the program's routing differs from the
  reference's is measured beside it, never handed to it, and has a
  limit too. Then, as for every generate configuration: the same greedy
  prompt twice through the socket, no serve-time compile, the expected
  attention path.
- ``main()`` runs the same set-up and verdict without a window, on the
  program or on a *control* in its place that has to come out not
  correct (the reference rounded through float8; the reference with a
  bfloat16 router).
- ``bytes_mla_moe.decode_step_bytes`` is made reachable to
  ``layers.read_roofline`` as ``bytes.decode_step_bytes_mla_moe``.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _path in (BENCH, os.path.dirname(BENCH)):       # as run.py does
    if _path not in sys.path:
        sys.path.insert(0, _path)

import bytes as bytes_mod
import bytes_mla_moe
import reference_mla_moe as reference
import systems
import traffic as traffic_mod
import weights

bytes_mod.decode_step_bytes_mla_moe = bytes_mla_moe.decode_step_bytes


class Adapter(systems.GenerateSystem):

    # main()'s control: the roundings (``round_to``, ``router_round_to``
    # of reference.forward_logits, as type names) that make a
    # lower-precision system of the reference, which then stands in the
    # program's place at every comparison with the float32 reference
    control: Dict[str, str] = {}

    async def start(self) -> None:
        import jax
        import jax.numpy as jnp

        from gofr_tpu.app import App
        from gofr_tpu.container import new_mock_container
        from gofr_tpu.http.response import Stream
        from gofr_tpu.tpu.generate import GenerationEngine
        from gofr_tpu.tpu.page_pool import PagePool

        config = self.config
        module, cfg = systems.model_config(config)
        self.cfg = cfg
        params = jax.jit(lambda s: module.init(cfg, jax.random.key(s)))(
            jnp.uint32(self.seed % (2 ** 31 - 1)))
        jax.block_until_ready(params)
        self.notes["weight_bytes"] = weights.tree_bytes(params)
        self.params = params
        self._reference: Dict[Tuple, Any] = {}      # jitted, by roundings
        self.probe(module, cfg, params)

        settings = dict(config["engine"])
        settings["prompt_buckets"] = tuple(settings["prompt_buckets"])
        pages = int(config["pool_pages_max"])
        memory = jax.devices()[0].memory_stats()
        if memory:                               # none on the CPU
            left = memory["bytes_limit"] - memory["bytes_in_use"]
            pool_bytes = left - int(config["pool_headroom_bytes"])
            if pool_bytes <= 0:
                raise systems.CheckFailed(
                    f"no room for a page pool: {left} bytes left beside "
                    f"the weights")
            pages = min(pages, pool_bytes // PagePool._page_bytes(
                cfg, settings["kv_page"], module.cache_leaves(cfg)))
        container = new_mock_container()
        container.logger = self.logger
        self.metrics = container.metrics
        engine = GenerationEngine(cfg, params, kv_pages=pages,
                                  model_module=module, logger=self.logger,
                                  metrics=container.metrics, **settings)
        self.engine = engine
        pool = engine.stats()["kv_pool"]
        self.notes.update(kv_pool_bytes=pool["pool_bytes"],
                          kv_pages=pool["num_pages"],
                          attn_path=engine.attn_path,
                          attn_why=engine.attn_reason)
        # every admission-count rung x bucket the bound leaves reachable,
        # every k rung, and every page-gather width of the window ladder:
        # a rung first met in the window would compile there
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
                         positions: Sequence[int], **roundings: str
                         ) -> Tuple[np.ndarray, np.ndarray]:
        """The reference over ``tokens``: its logits at ``positions``
        and the experts it chose at every token. One executable whatever
        the lengths of the short sequences and one for the deep ones:
        the tokens are padded to the longest sequence of their kind
        (what follows a position does not move it)."""
        import jax
        import jax.numpy as jnp

        config = self.config
        probe, served = config["probe"], config["served_check"]
        bucket = max(config["engine"]["prompt_buckets"])
        longest = max(probe["prompt"] + probe["steps"],
                      bucket + max(served["new_tokens"]) - 1)
        if len(tokens) > longest:
            longest = bucket + served["deep"]["new_tokens"] - 1
        most = max(probe["steps"] + 1, max(served["new_tokens"]))
        key = tuple(sorted(roundings.items()))
        if key not in self._reference:
            types = {name: getattr(jnp, kind)
                     for name, kind in roundings.items()}
            self._reference[key] = jax.jit(
                lambda p, t, at: reference.forward_logits(
                    p, config, t, positions=at, **types))
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
        rng = np.random.default_rng([int(seed), 0x5E4D])
        lengths = [int(length) for length in rng.permutation(
            traffic_mod.quantile_set(self.traffic["lengths"]["prompt"],
                                     count))]
        budgets = [spec["new_tokens"][i % len(spec["new_tokens"])]
                   for i in range(count)]
        # a few requests of the longest prompt go first and run deep, so
        # that the others are served by the tick's widest page-gather
        # rung, the one most of the window's ticks take, over a table
        # that holds real pages beyond the narrower rungs' columns
        lengths += deep["requests"] * [max(config["engine"]["prompt_buckets"])]
        budgets += deep["requests"] * [deep["new_tokens"]]
        prompts = [traffic_mod.prompt_ids(seed, (1 << 30) + 2 + i, length,
                                          cfg.vocab_size)
                   for i, length in enumerate(lengths)]
        asks = [self.ask(prompt, budget)
                for prompt, budget in zip(prompts, budgets)]
        before = engine.stats()
        first = [asyncio.ensure_future(ask) for ask in asks[count:]]
        rungs = before["window_ladder"]
        pages = deep["requests"] * (
            (rungs[-2] if len(rungs) > 1 else 0) // engine.kv_page + 1)
        waited = 0.0
        while engine.stats()["kv_pool"]["used_pages"] < pages:
            if waited > systems.READ_TIMEOUT_S or all(
                    task.done() for task in first):
                raise systems.CheckFailed(
                    f"served check: the deep requests never held {pages} "
                    f"pages (past the window rung {rungs[-2:]})")
            await asyncio.sleep(0.02)
            waited += 0.02
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
            served_prefill_batches=(after["prefill_batches"]
                                    - before["prefill_batches"]),
            served_ticks=after["decode_steps"] - before["decode_steps"],
            served_tokens_checked=len(agree),
            served_argmax_share=float(np.mean(agree)) if agree else 0.0,
            served_margin_p99=(float(np.percentile(margins, 99))
                               if margins else float("inf")),
            served_margin_top=sorted(margins)[-8:],
            served_each=each)

    def probe(self, module, cfg, params) -> None:
        """Prefill, then teacher-forced paged decode steps, against the
        reference's one forward over all the tokens."""
        import jax
        import jax.numpy as jnp

        from gofr_tpu.tpu.page_pool import PagePool

        config = self.config
        prompt, steps = config["probe"]["prompt"], config["probe"]["steps"]
        page = config["engine"]["kv_page"]
        if prompt % page:
            raise systems.CheckFailed("probe.prompt must fill whole pages")
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
        # the sequence's, through the pool's own tables
        columns = -(-(prompt + steps) // page)
        pool = PagePool(cfg, page=page, num_pages=columns + 3,
                        leaf_specs=module.cache_leaves(cfg))
        table = jnp.asarray([pool.alloc(columns)[::-1]], jnp.int32)

        def insert(leaves, small):
            return {name: leaves[name].at[:, table[0, :prompt // page]].set(
                small[name][:, 0].reshape(
                    small[name].shape[0], prompt // page, page,
                    *small[name].shape[3:])) for name in leaves}

        leaves = jax.jit(insert, donate_argnums=0)(pool.leaves, small)
        step = jax.jit(lambda p, token, leaves, cache_len:
                       module.decode_step_paged(
                           p, cfg, token, leaves, table, cache_len,
                           jnp.ones((1,), bool)), donate_argnums=2)
        for i in range(steps):
            logits, leaves, cache_len = step(
                params, tokens[prompt + i][None], leaves, cache_len)
            got.append(np.asarray(logits[0]))
        self.compare(cfg, got, np.asarray(routes), want,
                     want_routes[:, :prompt])

    def router_check(self, module, cfg, params) -> None:
        """The router alone: the program's ``route`` and the reference's
        on one input, every expert layer's router over ``probe.prompt``
        rows of the activation type. The probe's routing differs from
        the reference's mostly because its hidden state is bfloat16, as
        the configuration states; this holds the router itself to the
        float32 it states."""
        import jax
        import jax.numpy as jnp

        rows = self.config["probe"]["prompt"]
        h = jax.random.normal(jax.random.key(self.seed % (2 ** 31 - 1)),
                              (rows, cfg.dim), jnp.float32).astype(cfg.dtype)
        routers = params["moe"]["router"]

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
        (token, expert layer) pairs whose chosen set differs from the
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
                ("route_swap_share", "share of (token, expert layer) pairs "
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
                          "ones: not all were served by the widest rung")
        if checks["served_short"]:
            faults.append(f"served-check requests {checks['served_short']} "
                          f"did not stream their whole budget")
        return faults


def main() -> None:
    """The cell's set-up and its verdict without a window: the readings
    the limits are set between. ``--control`` puts a system that has to
    come out not correct in the program's place at every comparison: the
    reference with its activations rounded through ``float8_e4m3fn``
    (the nearest precision below the configuration's bfloat16), or the
    reference with its router's scores in ``bfloat16`` where the
    configuration states float32.

        python3 benchmark/adapters/mla_moe.py --seed 7 \\
            [--control float8_e4m3fn|bf16-router]
    """
    import argparse

    parser = argparse.ArgumentParser(description=main.__doc__)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--control", default=None,
                        choices=("float8_e4m3fn", "bf16-router"))
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    parser.add_argument("--size", choices=("full", "tiny"), default="full")
    parser.add_argument("--workload", default="pangu-ultra-moe-ep16.reason")
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
    adapter.control = {"float8_e4m3fn": {"round_to": "float8_e4m3fn"},
                       "bf16-router": {"router_round_to": "bfloat16"},
                       None: {}}[args.control]

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
