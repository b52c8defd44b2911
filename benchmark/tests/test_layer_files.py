"""Every per-layer metric of ``BENCHMARK.json`` has its data file with a
reader kind ``layers.py`` knows, and the six metrics that read the engine's
loop clock (ISSUE 25) give the expected number on hand-made evidence: two
``stats()`` trees and two expositions, a window apart.

Run with ``python3 -m pytest benchmark/tests -q``; not part of tier-1.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import layers  # noqa: E402

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as handle:
    BENCHMARK = json.load(handle)


def metric_file(name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("entry", BENCHMARK["per_layer"],
                         ids=lambda entry: entry["name"])
def test_every_per_layer_entry_has_its_file(entry):
    body = metric_file(entry["name"])
    assert body["source"]["kind"] in layers.READERS
    for key in ("layer", "unit", "better", "moves"):
        assert body[key] == entry[key], key


# a 40 s window in which the loop ran 40 s: held 38 s of it, of which 6 s
# were CPU; 2 s of CPU accrued while it yielded; 300 ticks
STATS0 = {"decode_steps": 100,
          "loop": {"wall_s": 10.0, "held_s": 9.0, "held_cpu_s": 1.0,
                   "yield_cpu_s": 0.5}}
STATS1 = {"decode_steps": 400,
          "loop": {"wall_s": 50.0, "held_s": 47.0, "held_cpu_s": 7.0,
                   "yield_cpu_s": 2.5}}
# 10 requests in the window: 50 s queued and 8 s to the first token in
# all; 10 streams with 25 ms of server time in all. Another route's and
# another model's series must not leak in.
PROM0 = """
app_tpu_request_phase_seconds_sum{model="generate",phase="queue"} 5.0
app_tpu_request_phase_seconds_count{model="generate",phase="queue"} 2
app_tpu_request_phase_seconds_sum{model="generate",phase="first_token"} 1.0
app_tpu_request_phase_seconds_count{model="generate",phase="first_token"} 2
app_http_stream_self_seconds_sum{path="/generate/stream"} 0.005
app_http_stream_self_seconds_count{path="/generate/stream"} 2
"""
PROM1 = """
app_tpu_request_phase_seconds_sum{model="generate",phase="queue"} 55.0
app_tpu_request_phase_seconds_count{model="generate",phase="queue"} 12
app_tpu_request_phase_seconds_sum{model="generate",phase="first_token"} 9.0
app_tpu_request_phase_seconds_count{model="generate",phase="first_token"} 12
app_tpu_request_phase_seconds_sum{model="other",phase="queue"} 999.0
app_tpu_request_phase_seconds_count{model="other",phase="queue"} 1
app_http_stream_self_seconds_sum{path="/generate/stream"} 0.030
app_http_stream_self_seconds_count{path="/generate/stream"} 12
app_http_stream_self_seconds_sum{path="/elsewhere"} 7.0
app_http_stream_self_seconds_count{path="/elsewhere"} 1
"""


def evidence(**changes):
    base = dict(stats0=STATS0, stats1=STATS1,
                prom0=layers.parse_prometheus(PROM0),
                prom1=layers.parse_prometheus(PROM1),
                config={"route": "/generate/stream"}, seconds=40.0)
    base.update(changes)
    return layers.Evidence(**base)


@pytest.mark.parametrize("name,expected", [
    ("engine_loop_held_share", 38.0 / 40.0 * 100.0),
    ("engine_cpu_ms_per_tick", 6.0 / 300 * 1000.0),
    ("loop_other_cpu_share", 2.0 / 40.0 * 100.0),
    ("server_self_ms_per_stream", 0.025 / 10 * 1000.0),
    ("queue_wait_ms_mean", 50.0 / 10 * 1000.0),
    ("first_token_ms_mean", 8.0 / 10 * 1000.0),
])
def test_loop_clock_metric_reads_the_expected_number(name, expected):
    source = metric_file(name)["source"]
    assert layers.read(source, evidence()) == pytest.approx(expected)
    # a program without the clock (the parent commit): nothing, no raise
    assert layers.read(source, evidence(
        stats0={"decode_steps": 100}, stats1={"decode_steps": 400},
        prom0={}, prom1={})) is None
    listed = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    assert listed and listed[0]["workloads"] == ["mistral7b.batch"]
