"""The dots3-note-ep32 cell's files: they parse, its entries join
``BENCHMARK.json`` at the ends of their lists, its per-layer metric files read
what the program's counters and its index kernel say on hand-made
evidence and nothing from a program without them, the parameter and
byte arithmetic of ``bytes_dsa_mla_moe.py`` equals the cut's, the
configuration holds every key of the catalog's row, and the reference
imports nothing of the program.

Run with ``python3 -m pytest benchmark/tests -q``; not part of tier-1.
"""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import bytes_dsa_mla_moe  # noqa: E402
import layers  # noqa: E402

CELL = "dots3-note-ep32.longctx"

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as handle:
    BENCHMARK = json.load(handle)
with open(os.path.join(BENCH, "configs", "dots3-note-ep32.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "traffic", "longctx-closed.json")) as f:
    TRAFFIC = json.load(f)
OWN = [m for m in BENCHMARK["per_layer"] if m["name"].endswith(".dots")]
SHARED = [m["name"] for group in ("end_to_end", "per_layer")
          for m in BENCHMARK[group]
          if CELL in m.get("workloads", ()) and m not in OWN]

# the adapter is what makes the byte functions reachable to the readers
_spec = importlib.util.spec_from_file_location(
    "adapter_dsa_mla_moe", os.path.join(BENCH, "adapters", "dsa_mla_moe.py"))
ADAPTER = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ADAPTER)


def metric_file(name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def test_the_entries_join_the_benchmark_at_the_ends_of_their_lists():
    assert BENCHMARK["configs"][-1]["name"] == "dots3-note-ep32"
    assert BENCHMARK["workloads"][-1]["name"] == CELL
    assert BENCHMARK["per_layer"][-len(OWN):] == OWN
    for group in ("end_to_end", "per_layer"):
        for metric in BENCHMARK[group]:
            if CELL in metric.get("workloads", ()):
                assert metric["workloads"][-1] == CELL
    for entry in OWN:
        assert entry["workloads"] == [CELL]
        assert len(entry["name"]) <= 64 and entry["better"] in (
            "lower", "higher")


def test_the_cell_its_traffic_and_its_metrics():
    cell = BENCHMARK["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dots3-note-ep32", "longctx-closed", 1)
    assert len(cell["why"]) <= 200
    assert (TRAFFIC["loop"], TRAFFIC["clients"], TRAFFIC["rounds"],
            TRAFFIC["ramp_s"], TRAFFIC["drain_s"]) == (
        "closed", 128, 64, 10, 60)
    # the mix with its one fallback taken: prompts clipped at 6144
    assert TRAFFIC["lengths"]["prompt"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.5, "min": 1024,
        "max": 6144}
    assert TRAFFIC["lengths"]["output"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 256,
        "max": 2048}
    engine = CONFIG["engine"]
    assert CONFIG["max_position_embeddings"] == engine["max_len"] == 8192
    assert TRAFFIC["lengths"]["prompt"]["max"] \
        + TRAFFIC["lengths"]["output"]["max"] <= engine["max_len"]
    assert TRAFFIC["clients"] == 2 * engine["max_slots"] == 128
    assert engine["prompt_buckets"] == [1024, 2048, 4096, 6144]
    own = {m["name"] for m in OWN}
    assert {"index_kernel_ms_per_call.dots", "index_kernel_roofline.dots",
            "dsa_selected_share.dots", "decode_step_roofline.dots",
            "moe_experts_hit.dots", "kv_window_freed_share.dots",
            "prefill_attn_ms_per_call.dots"} <= own
    assert {"output_tok_s", "tpot_p95_ms", "decode_step_ms.engine",
            "prefill_device_share", "kv_pool_used_peak",
            "decode_batch_mean"} <= set(SHARED)
    # the roofline's time is read before it: an accepted metric, and the
    # cell's own ones join the end of the list
    names = [m["name"] for m in OWN]
    assert names.index("index_kernel_ms_per_call.dots") \
        < names.index("index_kernel_roofline.dots")


@pytest.mark.parametrize("entry", OWN, ids=lambda entry: entry["name"])
def test_every_own_metric_has_its_file(entry):
    body = metric_file(entry["name"])
    assert body["source"]["kind"] in layers.READERS
    assert body["layer"] == entry["layer"]
    assert body["unit"] == entry["unit"] and body["moves"] == entry["moves"]


def test_parameter_and_byte_arithmetic_is_the_cuts():
    hp, precision = CONFIG, CONFIG["precision"]
    assert round(bytes_dsa_mla_moe.attention_params(hp, "full") / 1e6,
                 1) == 144.0
    assert round(bytes_dsa_mla_moe.attention_params(hp, "window") / 1e6,
                 1) == 90.8
    assert round(bytes_dsa_mla_moe.expert_params(hp) / 1e6, 1) == 23.6
    assert bytes_dsa_mla_moe.kinds(hp) == {"full": 3, "window": 3}
    # everything but the routed experts: 2.28 GB; the 8 held experts of
    # 5 layers beside them: 4.36 GB in all (2179.6M parameters and the
    # gains)
    resident = bytes_dsa_mla_moe.resident_params(hp)
    assert abs(resident * 2 / 1e9 - 2.28) < 0.005
    held = 5 * 8 * bytes_dsa_mla_moe.expert_params(hp)
    embedding = hp["vocab_size"] * hp["hidden_size"]
    assert abs((resident + held + embedding) / 1e6 - 2179.6) < 1.0
    # latent rows 640 and 1152 wide in bf16
    assert bytes_dsa_mla_moe.row_bytes(hp, "full") == 1280
    assert bytes_dsa_mla_moe.row_bytes(hp, "window") == 2304
    # a step at 64 slots of ~5.1k: ~4.9 GB (weights, ~7 experts hit a
    # layer, 2048 selected rows, the window's 513)
    step = bytes_dsa_mla_moe.decode_step_bytes(
        hp, precision, 7, 64 * 5100, 64 * 2048, 64 * 513)
    assert 4.8e9 < step < 5.0e9
    rows = (bytes_dsa_mla_moe.decode_step_bytes(hp, precision, 0, 100, 0, 0)
            - bytes_dsa_mla_moe.decode_step_bytes(hp, precision, 0, 0, 0, 0))
    assert rows == 3 * 100 * 256
    call = bytes_dsa_mla_moe.index_call_bytes(hp, precision, 1000)
    assert call == 1000 * 256 + 64 * 64 * (256 + 4) + 64 * 8192 * 4


def test_readers_read_the_new_counters_and_nothing_from_a_parent():
    stats0 = {"dsa": {"rows_live": 1000, "rows_selected": 500, "calls": 3},
              "attn": {"window_rows": 300},
              "moe": {"experts_hit": 70, "layer_steps": 10},
              "timeline": {"tick_s": 1.0, "tick_steps": 100}}
    stats1 = {"dsa": {"rows_live": 1000 + 300 * 64 * 5000,
                      "rows_selected": 500 + 300 * 64 * 2048,
                      "calls": 3 + 300},
              "attn": {"window_rows": 300 + 300 * 64 * 513},
              "moe": {"experts_hit": 70 + 500 * 7, "layer_steps": 510},
              "timeline": {"tick_s": 2.0, "tick_steps": 200}}
    peaks = {"hbm_bytes_s": 819e9, "bf16_flops": 197e12}
    evidence = layers.Evidence(stats0=stats0, stats1=stats1, samples=[{}],
                               config=CONFIG, peaks=peaks)

    def value(name):
        return layers.read(metric_file(name)["source"], evidence)

    assert value("dsa_selected_share.dots") == pytest.approx(40.96)
    assert value("index_kernel_roofline.dots") is None    # no trace
    evidence.values["decode_step_ms.engine"] = 10.0
    want = bytes_dsa_mla_moe.decode_step_bytes(
        CONFIG, CONFIG["precision"], 7, 64 * 5000, 64 * 2048, 64 * 513)
    assert value("decode_step_roofline.dots") == pytest.approx(
        want / 819e9 * 1e3 / 10.0 * 100)
    assert 0 < value("decode_step_roofline.dots") < 100
    # the kernel's events in reduce.short_name's form: three a step
    trace = {"window_s": 1.0, "host": [], "devices": {"/device:TPU:0": {
        "ops": [["index_scores.1 f32[64,1,8192] custom-call:tpu_custom_call",
                 0, 200_000],
                ["index_scores.2 f32[64,1,8192] custom-call:tpu_custom_call",
                 200_000, 200_000],
                ["index_scores f32[64,1,8192] custom-call:tpu_custom_call",
                 400_000, 200_000],
                ["fusion.12 bf16[64,2048,640] fusion", 600_000, 50_000]],
        "modules": [["jit_decode_k(2)", 0, 1_000_000]]}}}
    traced = layers.Evidence(stats0=stats0, stats1=stats1, samples=[{}],
                             config=CONFIG, peaks=peaks, trace=trace)
    kernel_ms = layers.read(
        metric_file("index_kernel_ms_per_call.dots")["source"], traced)
    assert kernel_ms == pytest.approx(0.2)
    traced.values["index_kernel_ms_per_call.dots"] = kernel_ms
    share = layers.read(metric_file("index_kernel_roofline.dots")["source"],
                        traced)
    assert share == pytest.approx(
        bytes_dsa_mla_moe.index_call_bytes(CONFIG, CONFIG["precision"],
                                           64 * 5000)
        / 819e9 * 1e3 / 0.2 * 100)
    assert 0 < share <= 100
    # a program without the counters or the kernel (the parent): nothing,
    # no raise
    parent = layers.Evidence(stats0={"timeline": stats0["timeline"]},
                             stats1={"timeline": stats1["timeline"]},
                             samples=[{}], config=CONFIG, peaks=peaks,
                             trace={"window_s": 1.0, "host": [],
                                    "devices": {}})
    parent.values["decode_step_ms.engine"] = 10.0
    for entry in OWN:
        assert layers.read(metric_file(entry["name"])["source"],
                           parent) is None


def test_the_configuration_states_its_cut():
    entry = BENCHMARK["configs"][-1]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/dots3-note-ep32.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    assert CONFIG["layer_types"] == ["full_attention"] * 2 \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert CONFIG["expert_parallel_size"] * CONFIG["n_routed_experts"] \
        == CONFIG["n_routed_experts_published"] == 256
    assert 8 * CONFIG["vocab_size"] == 152064
    for key in CONFIG["reduced"]:
        assert key in CONFIG["reduced_why"]
    for key in ("assumed", "deployment", "precision", "engine_why"):
        assert CONFIG[key], key
    for ours, theirs in CONFIG["model"]["from_keys"].items():
        assert theirs in CONFIG, (ours, theirs)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference_dsa_mla_moe.py")) as f:
        source = f.read()
    assert "import gofr" not in source and "from gofr" not in source
    assert 'default_matmul_precision("highest")' in source
    assert {"logits_rel_l2_q1", "logits_rel_l2_max", "select_overlap_min",
            "decode_overlap_min", "router_swap_share",
            "index_kernel_err"} <= set(ADAPTER.reference.LIMITS)
    assert {name for name in ADAPTER.CONTROLS if name} == {
        "dense", "no-relu", "no-ik-norm", "no-window", "no-gates",
        "stale-index-keys", "float8-weights"}
