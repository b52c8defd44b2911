#!/usr/bin/env python3
"""The benchmark's command: one cell, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds the cell in ``BENCHMARK.json``, loads ``configs/<config>.json``,
``traffic/<traffic>.json`` and the ``layer_metrics/<name>.json`` of every
per-layer metric that lists the cell, builds the system by the
configuration's ``kind`` (``systems.py``), refuses any platform but a TPU
and any device that ``peaks.json`` does not know, warms the cell's
reachable shapes, starts the load generator's child process(es)
(``loadgen.py``), measures the window, checks the outputs, and prints one
JSON object as the last line of its standard output. The whole tree of
what it saw goes to ``benchmark/out/<cell>.<seed>.json``.

``--platform cpu --size tiny`` rehearses the *harness* on the CPU at toy
widths (the ``tiny`` block of the configuration file): it prints
``"correct": false`` and names the device, so that a CPU number can never
be taken for a chip number.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()        # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import layers  # noqa: E402
import reduce  # noqa: E402
import stats as stats_mod  # noqa: E402

TRACE_S = 3.0             # the traced part of the window: its last seconds
SPAWN_S = 1.5             # for the children to start and build their plan


def load_json(*parts: str) -> Any:
    with open(os.path.join(HERE, *parts)) as handle:
        return json.load(handle)


def parse_args() -> argparse.Namespace:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                        help="cpu: rehearse the harness; never a result")
    parser.add_argument("--size", choices=("full", "tiny"), default="full")
    parser.add_argument("--rate", type=float, default=None,
                        help="sweep only: override the traffic's rate_rps; "
                             "the run then prints correct=false")
    parser.add_argument("--keep-trace-ms", type=float, default=0.0,
                        help="also save the first N ms of the trace's "
                             "intermediate form beside the out file")
    return parser.parse_args()


def cell_metrics(bench: Dict[str, Any], group: str,
                 cell: str) -> List[Dict[str, Any]]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def rehearsal_traffic(traffic: Dict[str, Any],
                      tiny: Dict[str, Any]) -> Dict[str, Any]:
    """The mix at toy widths: lengths and rates scaled by the ``tiny``
    block, so the same file drives the CPU rehearsal."""
    traffic = json.loads(json.dumps(traffic))
    scale = tiny.get("length_scale")
    if scale and "lengths" in traffic:
        for dist in traffic["lengths"].values():
            for key in ("median", "min", "max", "value"):
                if key in dist:
                    dist[key] = max(2, int(dist[key] * scale))
    if "rate_scale" in tiny and "rate_rps" in traffic:
        traffic["rate_rps"] *= tiny["rate_scale"]
    return traffic


# -- end-to-end metrics, from the generator's records ---------------------------

def end_to_end(names: List[str], records: List[Dict[str, Any]],
               frames_in_window: int, seconds: float,
               worst_ms: float) -> Dict[str, float]:
    """The quantities ``end_to_end.json`` defines, over *all* measured
    requests; a failed request is the worst latency."""
    table = load_json("end_to_end.json")
    out: Dict[str, float] = {}
    for name in names:
        how = table[name]
        if how["quantity"] == "frames_per_s":
            out[name] = frames_in_window / seconds
            continue
        samples = []
        for record in records:
            value = None
            if not record["ok"]:
                value = worst_ms
            elif how["quantity"] == "ttft_ms":
                value = (record["first"] - record["due"]) * 1e3
            elif how["quantity"] == "latency_ms":
                value = (record["end"] - record["due"]) * 1e3
            elif how["quantity"] == "tpot_ms":
                value = stats_mod.tpot_ms(record["first"], record["last"],
                                          record["frames"])
            if value is not None:
                samples.append(value)
        value = stats_mod.percentile(samples, how["percentile"])
        if value is not None:
            out[name] = value
    return out


def generator_summary(results: List[Dict[str, Any]]) -> Dict[str, Any]:
    records = [r for result in results for r in result["records"]]
    late = [(r["sent"] - r["due"]) * 1e3 for r in records if "sent" in r]
    return {"records": records,
            "frames_in_window": sum(r["frames_in_window"] for r in results),
            "ramp_requests": sum(r["ramp_requests"] for r in results),
            "errors": [e for r in results for e in r["errors"]][:8],
            "late_p99_ms": stats_mod.percentile(late, 99),
            "late_p50_ms": stats_mod.percentile(late, 50),
            "late_max_ms": max(late) if late else None}


# -- one run ---------------------------------------------------------------------

async def sleep_until(when: float) -> None:
    delay = when - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)


async def spawn_generators(count: int, spec: Dict[str, Any]):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    children = []
    for index in range(count):
        child = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(HERE, "loadgen.py"),
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            env=env)
        child.stdin.write((json.dumps(dict(spec, index=index, of=count))
                           + "\n").encode())
        await child.stdin.drain()
        child.stdin.close()
        children.append(child)
    return children


async def measure(args, bench, cell, config, traffic, system, jax,
                  peaks) -> Dict[str, Any]:
    from gofr_tpu.metrics.exposition import render_prometheus

    loop = asyncio.get_running_loop()
    await system.start()

    def snapshot():
        return (layers.parse_prometheus(render_prometheus(system.metrics)),
                system.stats())

    seconds = float(args.seconds)
    t0 = time.monotonic() + float(traffic["ramp_s"]) + SPAWN_S
    t_end = t0 + seconds
    spec = dict(system.spec(), port=system.port, seed=args.seed,
                seconds=seconds, t0=t0, traffic=traffic)
    children = await spawn_generators(int(traffic.get("generators", 1)),
                                      spec)
    try:
        samples: List[Dict[str, Any]] = []

        async def sampler():
            tick = t0
            while tick < t_end:
                await sleep_until(tick)
                samples.append(dict(system.stats(), at=tick - t0))
                tick += 1.0

        sampling = asyncio.ensure_future(sampler())
        await sleep_until(t0)
        setup_s = t0 - T_PROCESS
        prom0, stats0 = snapshot()
        trace_dir = trace_window = None
        if args.trace:
            trace_s = min(TRACE_S, seconds / 2)
            await sleep_until(t_end - trace_s)
            trace_dir = os.path.join(HERE, "out",
                                     f"trace.{cell['name']}.{args.seed}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # annotations, not frames
            options.host_tracer_level = 2
            await loop.run_in_executor(
                None, lambda: jax.profiler.start_trace(
                    trace_dir, profiler_options=options))
            trace_started = time.monotonic()
        await sleep_until(t_end)
        prom1, stats1 = snapshot()
        if args.trace:
            trace_window = time.monotonic() - trace_started
            await loop.run_in_executor(None, jax.profiler.stop_trace)
        await sampling
        limit = float(traffic["drain_s"]) + 30.0
        outputs = await asyncio.wait_for(asyncio.gather(
            *[child.communicate() for child in children]), limit)
    finally:
        for child in children:
            if child.returncode is None:
                child.kill()
                await child.wait()
    bad = [c.returncode for c in children if c.returncode != 0]
    if bad:
        raise RuntimeError(f"load generator exited with {bad}")
    generator = generator_summary(
        [json.loads(out.decode().strip().splitlines()[-1])
         for out, _ in outputs])
    faults = system.verdict()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices()[:cell["chips"]])
    await system.stop()

    records = generator["records"]
    failed = [r for r in records if not r["ok"]]
    worst_ms = (seconds + float(traffic["drain_s"])) * 1e3
    trace_form = reduced = None
    if trace_dir is not None:
        trace_form = reduce.load_xplane(
            reduce.find_xplane(trace_dir),
            load_json("trace_names.json")["host_annotations"], trace_window)
        reduced = reduce.reduce_form(trace_form)
        if args.keep_trace_ms:
            with open(os.path.join(HERE, "out", f"{cell['name']}."
                                   f"{args.seed}.trace.json"), "w") as f:
                json.dump(reduce.crop(trace_form, args.keep_trace_ms), f)
        shutil.rmtree(trace_dir, ignore_errors=True)

    evidence = layers.Evidence(
        prom0=prom0, prom1=prom1, stats0=stats0, stats1=stats1,
        samples=samples, generator=generator, trace=trace_form,
        config=config, peaks=peaks, seconds=seconds)
    units = {}
    if args.trace:
        values: Dict[str, float] = {}
        for metric in cell_metrics(bench, "per_layer", cell["name"]):
            source = load_json("layer_metrics",
                               f"{metric['name']}.json")["source"]
            value = layers.read(source, evidence)
            if value is not None:
                values[metric["name"]] = evidence.values[metric["name"]] \
                    = value
                units[metric["name"]] = metric["unit"]
    else:
        wanted = cell_metrics(bench, "end_to_end", cell["name"])
        units = {m["name"]: m["unit"] for m in wanted}
        values = end_to_end([m["name"] for m in wanted
                             if m["name"] != "setup_s"], records,
                            generator["frames_in_window"], seconds, worst_ms)
        values["setup_s"] = setup_s

    if not records:
        faults.append("the generator measured no request")
    if failed:
        faults.append(f"{len(failed)} of {len(records)} requests failed: "
                      f"{generator['errors'][:3]}")
    if args.platform != "tpu" or args.size != "full":
        faults.append("rehearsal of the harness, not a measurement")
    if args.rate is not None:
        faults.append("rate overridden for a sweep")
    device = jax.devices()[0]
    line = {"correct": not faults, "attempted": len(records),
            "failed": len(failed),
            "metrics": {name: {"value": value, "unit": units[name]}
                        for name, value in values.items()},
            "device": {"platform": device.platform,
                       "kind": device.device_kind,
                       "count": cell["chips"] if device.platform == "tpu"
                       else len(jax.devices()),
                       "memory_peak_bytes": peak}}
    if reduced is not None:
        line["device"].update(busy_s=reduced["busy_s"],
                              window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    tree = {"line": line, "faults": faults, "setup_s": setup_s,
            "checks": system.checks, "notes": system.notes,
            "generator": {k: v for k, v in generator.items()
                          if k != "records"},
            "requests": summarize_requests(records),
            "stats_start": stats0, "stats_end": stats1, "samples": samples,
            "reduced": reduced, "args": vars(args)}
    os.makedirs(os.path.join(HERE, "out"), exist_ok=True)
    with open(os.path.join(HERE, "out",
                           f"{cell['name']}.{args.seed}.json"), "w") as f:
        json.dump(tree, f, indent=1, default=str)
    return line


def summarize_requests(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Histograms of the sizes drawn and the medians a reader wants when
    a tail looks odd."""
    out: Dict[str, Any] = {"count": len(records)}
    ok = [r for r in records if r["ok"]]
    if ok and "frames" in ok[0]:
        out["frames_total"] = sum(r["frames"] for r in ok)
        for name, values in (
                ("ttft_ms", [(r["first"] - r["due"]) * 1e3 for r in ok]),
                ("tpot_ms", stats_mod.finite(
                    stats_mod.tpot_ms(r["first"], r["last"], r["frames"])
                    for r in ok)),
                ("budget", [r["budget"] for r in ok])):
            out[name] = {f"p{q}": stats_mod.percentile(values, q)
                         for q in (5, 50, 95, 99)}
    elif ok:
        values = [(r["end"] - r["due"]) * 1e3 for r in ok]
        out["latency_ms"] = {f"p{q}": stats_mod.percentile(values, q)
                             for q in (5, 50, 95, 99)}
    return out


def main() -> None:
    args = parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as handle:
        bench = json.load(handle)
    # cells that are written but not (yet) part of the benchmark
    for group, entries in load_json("candidates.json").items():
        if group in ("workloads", "end_to_end", "per_layer"):
            bench[group] = bench[group] + entries
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"run.py: no workload {args.workload!r} in BENCHMARK.json "
                 f"(have {sorted(cells)})")
    cell = cells[args.workload]
    config = load_json("configs", f"{cell['config']}.json")
    traffic = load_json("traffic", f"{cell['traffic']}.json")
    import systems

    if args.size == "tiny":
        traffic = rehearsal_traffic(traffic, config["tiny"])
    config = systems.published(config, args.size)
    if args.rate is not None:
        traffic["rate_rps"] = args.rate

    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # not /tmp/tpu_logs
    from gofr_tpu.tpu.compile_cache import configure_compile_cache

    # JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache: a
    # fixed path, so that only a checkout's first run of a cell compiles
    configure_compile_cache()
    import jax

    # cache the small programs too (inserts, the weights' maker): set-up
    # must find every program in the cache from the second run on
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    peaks_table = load_json("peaks.json")["peaks"]
    if platform != args.platform:
        sys.exit(f"run.py: needs a {args.platform}; JAX found platform "
                 f"{platform!r} ({kind}, {len(devices)} device(s))")
    if platform == "tpu":
        if len(devices) < cell["chips"]:
            sys.exit(f"run.py: {cell['name']} needs {cell['chips']} chip(s), "
                     f"JAX found {len(devices)}")
        if kind not in peaks_table:
            sys.exit(f"run.py: no published peaks for device_kind {kind!r}; "
                     f"add it to benchmark/peaks.json with its source")
    peaks = peaks_table.get(kind, {})
    system = systems.build(config, traffic, args.seed)
    line = asyncio.run(measure(args, bench, cell, config, traffic, system,
                               jax, peaks))
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
