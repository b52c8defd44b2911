"""The short-convolution / expert family (``models/conv_moe.py``) behind
the generate harness, as ``adapters/jamba.py`` is for the state-space
family, with the routing checks of ``adapters/hc_mla_moe.py``.
``systems.build`` finds this file by the configuration's ``module``.

- Weights: the module's own seeded ``init`` in one jitted call with the
  seed as an argument (one program for every seed; bf16, the router's
  bias float32).
- Set-up checks that decide ``correct``, at the configuration's widths
  (limits and their reasons: ``reference_conv_moe.LIMITS``):
  **the probe**, before the pool takes the chip's rest: one prefill
  group of ``probe.prompts`` rows (1536 and 700 tokens) in the
  ``probe.bucket`` (2048) bucket, so that 512 and 1348 positions of
  padding lie behind the prompts; the rows' K/V put into pages of a
  pool through its own tables and their windows into its per-slot
  rows, in another order than the group's; then ``probe.steps``
  teacher-forced ``decode_step_paged`` steps of both rows at once,
  through the ragged kernel where the configuration expects it. The
  logits at the prefill's last position and at every step, a row,
  against the reference's full forward pass over that row's tokens
  alone *given the program's routing* (all 32 experts are held, so a
  fourth expert flipped by a last bit moves a token in any sound
  system); the routing against the reference's own beside it.
  **The router alone**, bias and all: the program's ``route`` and the
  reference's on one bfloat16 input, every expert layer's.
  **The served check**, on the timed path: ``served_check.requests``
  concurrent requests through the socket, more than there are slots, of
  the cell's own prompt lengths, so that every slot is live, every
  bucket's prefill runs, admission groups are split by
  ``max_group_tokens`` and slots are freed and claimed again (their
  windows overwritten). Then the reference, teacher-forced over prompt
  plus served tokens of ``served_check.sample`` of them, routing for
  itself, says at every served token which token float32 would have
  chosen.
  Then, as for every generate configuration: the same greedy prompt
  twice through the socket, no serve-time compile, the expected
  attention path; and no page stall.
- ``main()`` runs the same set-up and verdict without a window, on the
  program, then on each *control* of ``--control`` in its place; each has
  to come out not correct. Three are the program's own probe with the
  window done wrong (``STATE_CONTROLS``: the probe alone decides them):
  ``pad-unmasked`` (the window at the bucket's end), ``window-dropped``
  (not carried from prefill into decode), ``stale-window`` (a claimed
  slot keeping its last tenant's: the rows' windows swapped). Two are
  the engine's own insert writing a wrong window into the claimed rows
  on the timed path (``SERVED_CONTROLS``: the served check is run again
  and alone decides them): ``served-window-dropped`` (zeros) and
  ``served-stale-window`` (the rows left as they were, the last
  tenant's). Six are the reference under a switch in the program's
  place at every comparison (``CONTROLS``).
- ``bytes_conv_moe``'s functions are made reachable to
  ``layers.read_roofline`` as ``decode_step_bytes_conv_moe``,
  ``attn_call_bytes_conv_moe`` and ``prefill_attn_flops_conv_moe``.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
from typing import Any, List, Sequence

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _path in (BENCH, os.path.dirname(BENCH)):       # as run.py does
    if _path not in sys.path:
        sys.path.insert(0, _path)

import bytes as bytes_mod
import bytes_conv_moe
import reference_conv_moe as reference
import systems
import traffic as traffic_mod
import weights

bytes_mod.decode_step_bytes_conv_moe = bytes_conv_moe.decode_step_bytes
bytes_mod.attn_call_bytes_conv_moe = bytes_conv_moe.attn_call_bytes
bytes_mod.prefill_attn_flops_conv_moe = bytes_conv_moe.prefill_attn_flops

# a control's switches of reference.forward_logits
CONTROLS = {"no-qk-norm": {"qk_norm": 0.0},
            "no-router-bias": {"bias": 0.0},
            "no-b-gate": {"b_gate": 0.0},
            "no-c-gate": {"c_gate": 0.0},
            "bf16-router": {"router_bf16": 1.0},
            "float8_e4m3fn": {"float8": 1.0}}
# what the program's own probe does wrong with the window
STATE_CONTROLS = ("pad-unmasked", "window-dropped", "stale-window")
# what the engine's own insert does wrong with the window, served
SERVED_CONTROLS = ("served-window-dropped", "served-stale-window")


class Adapter(systems.GenerateSystem):

    # main()'s control: a name of STATE_CONTROLS, or the switches that
    # make another system of the reference, which then stands in the
    # program's place at every comparison with it
    control: Any = None

    async def start(self) -> None:
        import jax
        import jax.numpy as jnp

        from gofr_tpu.app import App
        from gofr_tpu.container import new_mock_container
        from gofr_tpu.http.response import Stream
        from gofr_tpu.tpu.generate import GenerationEngine

        config = self.config
        module, cfg = systems.model_config(config)
        self.module, self.cfg = module, cfg
        self.params = jax.jit(
            lambda s: module.init(cfg, jax.random.key(s)))(
            jnp.uint32(self.seed % (2 ** 31 - 1)))
        jax.block_until_ready(self.params)
        self.notes["weight_bytes"] = weights.tree_bytes(self.params)
        self._reference = None               # jitted, switches operands
        self.probe()
        self.router_check()

        settings = dict(config["engine"])
        settings["prompt_buckets"] = tuple(settings["prompt_buckets"])
        # every slot at max_len: the pages are 2.4 GB beside 9.3 GB of
        # weights, so nothing is scaled to what memory_stats() leaves
        pages = settings["max_slots"] * settings["max_len"] \
            // settings["kv_page"]
        container = new_mock_container()
        container.logger = self.logger
        self.metrics = container.metrics
        engine = GenerationEngine(cfg, self.params, kv_pages={"attn": pages},
                                  model_module=module, logger=self.logger,
                                  metrics=container.metrics, **settings)
        self.engine = engine
        # one tree on the device: the engine's, whose re-laid leaves
        # would otherwise lie beside ours
        self.params = engine.params
        pool = engine.stats()["kv_pool"]
        self.notes.update(kv_pool_bytes=pool["pool_bytes"],
                          kv_pages=pool["kinds"]["attn"]["num_pages"],
                          state_bytes=pool["kinds"]["conv"]["bytes"],
                          attn_path=engine.attn_path,
                          attn_why=engine.attn_reason,
                          attention_paths=engine.attention_paths(),
                          weights=engine.stats().get("weights"))
        await engine.warmup(prompt_counts=tuple(engine._n_ladder))
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
        await self.serve_check_requests()
        self.judge_served()

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
                         positions: Sequence[int], given=None,
                         switches=None):
        """The reference over ``tokens``: (its logits at ``positions``,
        the experts it chose (expert layers, tokens, top_k)), given the
        experts of ``given`` (the same shape) where it is not None. The
        tokens are padded to the shorter of two lengths that holds them
        (what follows a position does not move it): a quarter of the
        largest bucket, or that bucket, each plus the longest answer; the
        switches are operands, so the reference is two executables."""
        import jax

        config = self.config
        probe, served = config["probe"], config["served_check"]
        most = max(probe["steps"] + 1, max(served["new_tokens"]))
        bucket = max(config["engine"]["prompt_buckets"])
        longest = next(size + most for size in (bucket // 4, bucket)
                       if size + most >= len(tokens))
        if self._reference is None:
            self._reference = jax.jit(
                lambda p, t, at, given, switches: reference.forward_logits(
                    p, config, t, at, given, switches))
        padded = np.zeros((longest,), np.int32)
        padded[:len(tokens)] = tokens
        at = np.full((most,), positions[-1], np.int32)
        at[:len(positions)] = positions
        named = np.full((self.cfg.count("moe"), longest, self.cfg.top_k), -1,
                        np.int32)
        if given is not None:
            named[:, :given.shape[1]] = given
        operands = {name: np.float32(value) for name, value in
                    dict(reference.SOUND, **(switches or {})).items()}
        logits, chosen = self._reference(self.params, padded, at, named,
                                         operands)
        return (np.asarray(logits)[:len(positions)],
                np.asarray(chosen)[:, :len(tokens)])

    # -- the probe ----------------------------------------------------------------------

    def probe(self) -> None:
        """One prefill group of rows shorter than their bucket, the
        pool's insert, then teacher-forced paged decode steps of all the
        rows at once, a row against the reference's full forward over
        its tokens alone given the routing of the system under test.
        Fills ``checks["logits_*"]`` and ``checks["route_swap_share"]``."""
        config, cfg = self.config, self.cfg
        spec = config["probe"]
        steps, lengths = spec["steps"], list(spec["prompts"])
        rows = [traffic_mod.prompt_ids(self.seed, (1 << 30) + 7 * i,
                                       length + steps, cfg.vocab_size)
                for i, length in enumerate(lengths)]
        if isinstance(self.control, dict):
            runs = [self.reference_logits(row[:length + steps],
                                          range(length - 1, length + steps),
                                          switches=self.control)
                    for row, length in zip(rows, lengths)]
            gots = [got for got, _ in runs]
            routes = [chosen for _, chosen in runs]
        else:
            gots, routes = self.program_probe(rows, lengths)
        wants, swaps = [], []
        for row, length, route in zip(rows, lengths, routes):
            tokens = row[:length + steps]
            positions = range(length - 1, length + steps)
            wants.append(self.reference_logits(tokens, positions,
                                               given=route)[0])
            own = self.reference_logits(tokens, positions)[1]
            swaps.append((np.sort(route, -1) != np.sort(own, -1)).any(-1))
        each = [[reference.rel_l2(g, w) for g, w in zip(got, want)]
                for got, want in zip(gots, wants)]
        flat = [value for row in each for value in row]
        self.checks.update(
            logits_rel_l2_each=each,
            logits_rel_l2=float(np.percentile(flat, 25)),
            logits_tol=reference.LIMITS["logits_rel_l2_q1"],
            logits_rel_l2_max=max(flat),
            route_swap_share=float(np.mean(np.concatenate(
                [swap.ravel() for swap in swaps]))),
            limits=reference.LIMITS)

    def program_probe(self, rows, lengths):
        """The program (with a state control's fault, where one is set)
        over the probe's rows: (a row's logits at its prompt's last
        position and at each step, a row's experts chosen at each of its
        tokens (expert layers, tokens, top_k))."""
        import jax
        import jax.numpy as jnp

        from gofr_tpu.tpu.page_pool import PagePool

        config, module, cfg = self.config, self.module, self.cfg
        spec = config["probe"]
        bucket, steps = spec["bucket"], spec["steps"]
        page = config["engine"]["kv_page"]
        ragged = config.get("expect_attn_path") == "ragged"
        padded = np.zeros((len(rows), bucket), np.int32)
        for i, (row, length) in enumerate(zip(rows, lengths)):
            padded[i, :length] = row[:length]

        def prefill(p, tokens, lens):
            return module.prefill(
                p, cfg, tokens, module.init_cache(cfg, *tokens.shape),
                lengths=lens, routes=True)

        first, small, cache_len, chosen = jax.jit(prefill)(
            self.params, jnp.asarray(padded),
            jnp.asarray(lengths, jnp.int32))
        window = small["conv"]["window"]
        if self.control == "pad-unmasked":
            # the window at the bucket's end: what a prefill that takes
            # no notice of its rows' lengths leaves
            window = jax.jit(lambda p, t: module.prefill(
                p, cfg, t, module.init_cache(cfg, *t.shape))[1]["conv"][
                "window"])(self.params, jnp.asarray(padded))
        elif self.control == "window-dropped":
            window = jnp.zeros_like(window)
        elif self.control == "stale-window":
            window = window[:, ::-1]
        # slots in another order than the group's rows, pages in another
        # order than the sequences'
        n = len(rows)
        slots = list(range(n))[::-1]
        columns = -(-(bucket + steps) // page)
        pool = PagePool(cfg, page=page, leaf_specs=module.cache_leaves(cfg),
                        slots=n, num_pages={"attn": n * columns + 3})
        table = np.full((n, columns), pool.sentinel_of("attn"), np.int32)
        for i, slot in enumerate(slots):
            held = -(-(lengths[i] + steps) // page)
            table[slot, :held] = pool.alloc(held, kind="attn")[::-1]
        table = jnp.asarray(table)
        filled = bucket // page

        def insert(leaves, small, window):
            ids = table[jnp.asarray(slots), :filled].reshape(-1)
            attn = {name: leaf.at[:, ids].set(
                small["attn"][name].reshape(leaf.shape[0], -1, page,
                                            *leaf.shape[3:]), mode="drop")
                for name, leaf in leaves["attn"].items()}
            rows_ = leaves["conv"]["window"]
            return {"attn": attn, "conv": {"window": rows_.at[
                :, jnp.asarray(slots)].set(window.astype(rows_.dtype))}}

        leaves = jax.jit(insert, donate_argnums=0)(pool.leaves, small,
                                                   window)
        step = jax.jit(lambda p, token, leaves, cache_len:
                       module.decode_step_paged(
                           p, cfg, token, leaves, {"attn": table},
                           cache_len, jnp.ones((n,), bool), ragged=ragged,
                           routes=True),
                       donate_argnums=2)
        cache_len = jnp.zeros((n,), jnp.int32).at[
            jnp.asarray(slots)].set(cache_len)
        chosen = np.asarray(chosen)               # (layers, n, bucket, k)
        gots = [[np.asarray(first[i])] for i in range(n)]
        routes = [[chosen[:, i, :length]] for i, length in enumerate(lengths)]
        for t in range(steps):
            token = np.zeros((n,), np.int32)
            for i, slot in enumerate(slots):
                token[slot] = rows[i][lengths[i] + t]
            logits, leaves, cache_len, picked = step(
                self.params, jnp.asarray(token), leaves, cache_len)
            logits, picked = np.asarray(logits), np.asarray(picked)
            for i, slot in enumerate(slots):
                gots[i].append(logits[slot])
                routes[i].append(picked[:, slot:slot + 1])
        return gots, [np.concatenate(route, axis=1) for route in routes]

    def router_check(self) -> None:
        """The router alone, its correction bias with it: the program's
        ``route`` and the reference's on one input of the activation
        type, every expert layer's router over ``router_check.rows``
        rows: the share of rows whose chosen set differs."""
        import jax
        import jax.numpy as jnp

        from gofr_tpu.models import experts

        cfg = self.cfg
        rows = self.config["router_check"]["rows"]
        h = jax.random.normal(jax.random.key(self.seed % (2 ** 31 - 1)),
                              (rows, cfg.dim), jnp.float32).astype(cfg.dtype)
        stack = self.params["moe"]
        routers = (stack["router"], stack["router_bias"])

        def theirs(switches):
            with jax.default_matmul_precision("highest"):
                return jax.jit(jax.vmap(lambda router, bias: reference.route(
                    router, h.astype(jnp.float32), self.config,
                    switches["bias"] * bias, None,
                    switches["router_bf16"])[0]))(*routers)

        if isinstance(self.control, dict):
            ours = theirs(dict(reference.SOUND, **self.control))
        else:
            ours = jax.jit(jax.vmap(lambda router, bias: experts.route(
                cfg, router, h, bias)[0]))(*routers)
        differs = (np.sort(np.asarray(ours), -1)
                   != np.sort(np.asarray(theirs(reference.SOUND)), -1)
                   ).any(-1)
        self.checks["router_swap_share"] = float(differs.mean())

    # -- the served check ---------------------------------------------------------------

    async def serve_check_requests(self) -> None:
        """Send the served check's requests through the socket and keep
        what was served (``self.served``)."""
        config, cfg, seed = self.config, self.cfg, self.seed
        spec, engine = config["served_check"], self.engine
        count = spec["requests"]
        rng = np.random.default_rng([int(seed), 0x5E4D])
        lengths = [int(length) for length in rng.permutation(
            traffic_mod.quantile_set(self.traffic["lengths"]["prompt"],
                                     count))]
        budgets = [spec["new_tokens"][i % len(spec["new_tokens"])]
                   for i in range(count)]
        prompts = [traffic_mod.prompt_ids(seed, (1 << 30) + 2 + i, length,
                                          cfg.vocab_size)
                   for i, length in enumerate(lengths)]
        before = engine.stats()
        replies = list(await asyncio.gather(*[
            self.ask(prompt, budget)
            for prompt, budget in zip(prompts, budgets)]))
        after = engine.stats()
        self.served = {"prompts": prompts, "replies": replies,
                       "lengths": lengths}
        state = after["kv_pool"]["kinds"]["conv"]
        self.checks.update(
            served_requests=count,
            served_short=[i for i, (reply, budget)
                          in enumerate(zip(replies, budgets))
                          if len(reply) != budget][:8],
            served_prefill_batches=(after["prefill_batches"]
                                    - before["prefill_batches"]),
            served_ticks=after["decode_steps"] - before["decode_steps"],
            served_slots_claimed_peak=state["claimed_peak"],
            served_slots=state["slots"])

    def judge_served(self) -> None:
        """The reference, routing for itself (the engine streams tokens,
        not routes), teacher-forced over prompt plus served tokens of a
        sample spread over the prompt lengths (shortest and longest
        among them). Fills ``checks["served_*"]``."""
        spec, served = self.config["served_check"], self.served
        count = len(served["prompts"])
        by_length = np.argsort(served["lengths"], kind="stable")
        sample = by_length[np.linspace(0, count - 1, spec["sample"])
                           .round().astype(int)].tolist()
        switches = self.control if isinstance(self.control, dict) else None
        agree, margins, each = [], [], []
        for i in sample:
            prompt, reply = served["prompts"][i], served["replies"][i]
            if not reply:
                continue
            sequence = prompt + reply[:-1]
            positions = range(len(prompt) - 1, len(sequence))
            want = self.reference_logits(sequence, positions)[0]
            if switches:
                reply = self.reference_logits(
                    sequence, positions,
                    switches=switches)[0].argmax(-1).tolist()
            rows = np.arange(len(reply))
            chosen = want.argmax(-1)
            same = chosen == np.asarray(reply)
            # how far below the reference's choice the served token
            # lies, in standard deviations of that position's logits
            below = (want[rows, chosen] - want[rows, reply]) / want.std(-1)
            margins += below.tolist()
            agree += same.tolist()
            each.append({"request": int(i), "prompt": len(prompt),
                         "tokens": len(reply), "share": float(same.mean()),
                         "margin_max": float(below.max())})
        self.checks.update(
            served_tokens_checked=len(agree),
            served_argmax_share=float(np.mean(agree)) if agree else 0.0,
            served_margin_p99=(float(np.percentile(margins, 99))
                               if margins else float("inf")),
            served_margin_top=sorted(margins)[-8:],
            served_each=each)

    # -- the verdict --------------------------------------------------------------------

    def probe_faults(self) -> List[str]:
        checks, limits = self.checks, reference.LIMITS
        return [f"{what} {checks[name]:.4g} > {limits[name]}"
                for name, what in (
                    ("logits_rel_l2_max", "logits of one of the probed "
                     "positions differ from the reference given the same "
                     "routing: relative L2"),
                    ("route_swap_share", "share of (token, expert layer) "
                     "pairs of the probe routed otherwise than by the "
                     "reference"),
                    ("router_swap_share", "share of rows the router alone "
                     "routes otherwise than the reference's on the same "
                     "input"))
                if checks[name] > limits[name]]

    def verdict(self) -> List[str]:
        faults = super().verdict()       # the lower quartile, and the rest
        checks, limits = self.checks, reference.LIMITS
        faults += self.probe_faults()
        if checks["served_margin_p99"] > limits["served_margin_p99"]:
            faults.append(
                f"one served token in a hundred lies below the reference's "
                f"choice, in deviations of its logits, by "
                f"{checks['served_margin_p99']:.4g} > "
                f"{limits['served_margin_p99']}")
        if checks["served_argmax_share"] \
                < limits["served_argmax_share_min"]:
            faults.append(
                f"only {checks['served_argmax_share']:.4g} of "
                f"{checks['served_tokens_checked']} served tokens are the "
                f"reference's choice, under "
                f"{limits['served_argmax_share_min']}")
        if checks["served_short"]:
            faults.append(f"served-check requests {checks['served_short']} "
                          f"did not stream their whole budget")
        if checks["served_slots_claimed_peak"] < checks["served_slots"]:
            faults.append(
                f"the served check never had every slot claimed: "
                f"{checks['served_slots_claimed_peak']} of "
                f"{checks['served_slots']}")
        stalls = self.engine.stats()["kv_pool"]["page_stalls"]
        if stalls:
            faults.append(f"{stalls} page stall(s): the pool holds every "
                          f"slot at max_len")
        return faults

    async def serve_faulted(self, control: str) -> None:
        """The served check's requests again, through the socket, with
        the engine's insert writing a wrong window into the claimed
        rows: zeros (``served-window-dropped``) or what the rows held,
        the last tenant's (``served-stale-window``; every slot has had
        a tenant by now). Everything else is the engine's own."""
        import jax.numpy as jnp

        engine = self.engine
        insert = engine._run_insert

        def faulted(nb, lb, plen, dev, first, small, keys, state=None):
            held = (engine if state is None else state)._kv["conv"]
            window = small["conv"]["window"]
            if control == "served-window-dropped":
                window = jnp.zeros_like(window)
            else:
                window = jnp.take(held["window"], dev["slots"], axis=1,
                                  mode="clip").astype(window.dtype)
            return insert(nb, lb, plen, dev, first,
                          dict(small, conv={"window": window}), keys, state)

        engine._run_insert = faulted
        try:
            await self.serve_check_requests()
        finally:
            del engine._run_insert

    async def judge_control(self, control) -> List[str]:
        """The verdict with a control in the program's place: a state
        control is the program's probe with the window done wrong (the
        probe alone decides it); a served control is the served check
        with the engine's insert doing it wrong (the served check alone
        decides it); a switch control is the reference under the switch
        at every comparison, over the sequences the program served."""
        if not hasattr(self, "_sound"):
            # the program's readings and what it served
            self._sound, self._sound_served = dict(self.checks), self.served
        self.checks, self.served = dict(self._sound), self._sound_served
        self.control = control
        if control in SERVED_CONTROLS:
            await self.serve_faulted(control)
            self.judge_served()
            return self.verdict()
        self.probe()
        if isinstance(control, dict):
            self.router_check()
            self.judge_served()
        return self.verdict()


def main() -> None:
    """The cell's set-up and its verdict without a window: the readings
    the limits are set between. First the program; then each control of
    ``--control`` (names of ``STATE_CONTROLS``, ``SERVED_CONTROLS`` and
    ``CONTROLS``, comma-separated, or ``all``) in its place: each has to
    come out not correct. One JSON line a system.

        python3 benchmark/adapters/conv_moe.py --seed 7 \\
            [--control all|pad-unmasked,no-qk-norm,...]
    """
    import argparse

    known = list(STATE_CONTROLS) + list(SERVED_CONTROLS) + list(CONTROLS)
    parser = argparse.ArgumentParser(description=main.__doc__)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--control", default="")
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    parser.add_argument("--size", choices=("full", "tiny"), default="full")
    parser.add_argument("--workload", default="lfm2-8b-a1b-pp2.chat")
    args = parser.parse_args()
    names = known if args.control == "all" \
        else [name for name in args.control.split(",") if name]
    unknown = [name for name in names if name not in known]
    if unknown:
        parser.error(f"unknown control(s) {unknown}; have {known}")
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
                report(name, await adapter.judge_control(
                    CONTROLS.get(name, name)))
        finally:
            await adapter.stop()

    asyncio.run(checked())


if __name__ == "__main__":
    main()
