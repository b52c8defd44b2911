"""The engine timeline's per-layer metrics (ISSUE 35): every file names a
layer of ``PERF.md`` section 3 and a reader ``layers.py`` knows, and each
reads a number from a recorded ``stats_start`` / ``stats_end`` pair (a
CPU rehearsal of ``mistral7b.batch``: the shape of ``stats()`` as the
program writes it, never a time of the device); a program without the
timeline (the parent commit) gives nothing and does not raise.

Run with ``python3 -m pytest benchmark/tests -q``; not part of tier-1.
"""

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import layers  # noqa: E402

ALL = ["mistral7b.batch", "pangu-ultra-moe-ep16.reason",
       "command-a-plus-ep8.mixed", "xing4-29b.docqa"]
# name -> (numerator, denominator, scale, cells)
NEW = {
    "prefill_device_share": ("prefill_s", "busy_s", 100.0, ALL),
    "decode_step_ms.engine": ("tick_s", "tick_steps", 1000.0, ALL),
    "prefill_ms_per_group.engine": ("prefill_s", "prefill_groups", 1000.0,
                                    ALL),
    "decode_stall_ms_per_token": ("stalled_slot_s", "tick_tokens", 1000.0,
                                  ALL),
    "gather_rows_live_share.pangu": ("rows_live", "rows_gathered", 100.0,
                                     ["pangu-ultra-moe-ep16.reason"]),
}

with open(os.path.join(ROOT, "BENCHMARK.json")) as handle:
    BENCHMARK = json.load(handle)
with open(os.path.join(HERE, "data", "timeline_stats_pair.json")) as handle:
    PAIR = json.load(handle)


def metric_file(name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def perf_layers():
    """The first cell of every row of PERF.md section 3's table, without
    the backticks."""
    with open(os.path.join(ROOT, "PERF.md")) as handle:
        text = handle.read()
    section = text.split("## 3. Layers")[1].split("\n## ")[0]
    return [row.split("|")[1].replace("`", "").strip()
            for row in section.splitlines() if row.startswith("| ")]


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_file_names_a_layer_a_reader_and_its_entry(name):
    body = metric_file(name)
    assert body["source"]["kind"] in layers.READERS
    entry = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    for key in ("layer", "unit", "better", "moves"):
        assert body[key] == entry[0][key], key
    assert entry[0]["workloads"] == NEW[name][3]
    assert entry[0]["source"] in ("program_span", "program_counter")
    # "generation engine (tpu/generate.py)": a row of PERF.md section 3
    # starts with the layer's words and names its module
    words, module = re.match(r"(.+) \((.+)\)$", body["layer"]).groups()
    assert any(cell.startswith(words) and module in cell
               for cell in perf_layers()), body["layer"]
    # a layer BENCHMARK.json already names is named letter for letter
    accepted = {m["layer"] for m in BENCHMARK["per_layer"]
                if m["name"] not in NEW}
    assert body["layer"] in accepted


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_reads_a_number_from_a_recorded_pair(name):
    source = metric_file(name)["source"]
    evidence = layers.Evidence(stats0=PAIR["stats_start"],
                               stats1=PAIR["stats_end"],
                               seconds=PAIR["seconds"])
    value = layers.read(source, evidence)
    over, under, scale, _ = NEW[name]
    start, end = (PAIR[k]["timeline"] for k in ("stats_start", "stats_end"))
    assert end[under] > start[under]
    assert value == pytest.approx(
        (end[over] - start[over]) / (end[under] - start[under]) * scale)
    assert value > 0.0
    if source.get("scale") == 100.0:
        assert value <= 100.0
    # the parent commit's stats() has no timeline: nothing, no raise
    bare = {k: {key: tree for key, tree in PAIR[k].items()
                if key != "timeline"}
            for k in ("stats_start", "stats_end")}
    assert layers.read(source, layers.Evidence(
        stats0=bare["stats_start"], stats1=bare["stats_end"])) is None


def test_the_recorded_window_is_whole():
    """What the timeline promises, on the recorded pair: device time is
    charged once (the growth of ``device_seconds`` is ``busy_s``'s and at
    most the loop's wall time), every tick has a rung, and the two laps
    are among the loop's holding phases."""
    start, end = PAIR["stats_start"], PAIR["stats_end"]

    def grew(path):
        return layers.dig(end, path) - layers.dig(start, path)

    charged = sum(end["device_seconds"].values()) \
        - sum(start["device_seconds"].values())
    assert charged == pytest.approx(grew("timeline.busy_s"), rel=0.01)
    assert grew("timeline.busy_s") <= grew("loop.wall_s")
    assert grew("timeline.busy_s") == pytest.approx(
        grew("timeline.tick_s") + grew("timeline.prefill_s")
        + grew("timeline.spec_s"))
    assert sum(end["timeline"]["ticks_by_width"].values()) \
        == end["timeline"]["ticks"]
    assert grew("timeline.ticks") == grew("decode_steps")
    assert grew("loop.held_s") == pytest.approx(sum(
        grew(f"loop.{phase}_s") for phase in
        ("admit", "dispatch", "publish", "enqueue", "upload")))
    assert set(end["loop"]["longest"]) <= {
        "admit", "dispatch", "publish", "enqueue", "upload", "wait", "park"}
