"""The Command A+ cell's files (ISSUE 31): they parse, its per-layer
metric files read what the program's counters say on hand-made evidence,
the parameter and byte arithmetic of ``bytes_swa_moe.py`` equals the
issue's, the configuration holds every published width, and every
``reduced`` key has its why.

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

import bytes as bytes_mod  # noqa: E402
import bytes_swa_moe  # noqa: E402
import layers  # noqa: E402

CELL = "command-a-plus-ep8.mixed"

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as handle:
    BENCHMARK = json.load(handle)
with open(os.path.join(BENCH, "configs", "command-a-plus-ep8.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "traffic", "mixed-closed.json")) as f:
    TRAFFIC = json.load(f)

OWN = [m for m in BENCHMARK["per_layer"] if m.get("workloads") == [CELL]]

# the adapter is what makes the byte functions reachable to the readers
_spec = importlib.util.spec_from_file_location(
    "adapter_swa_moe", os.path.join(BENCH, "adapters", "swa_moe.py"))
ADAPTER = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ADAPTER)


def metric_file(name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def test_the_cell_its_traffic_and_its_metrics():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "command-a-plus-ep8", "mixed-closed", 1)
    assert len(cell["why"]) <= 200
    assert (TRAFFIC["loop"], TRAFFIC["clients"], TRAFFIC["rounds"],
            TRAFFIC["ramp_s"], TRAFFIC["drain_s"]) == ("closed", 64, 64,
                                                       10, 40)
    # ISSUE 31's mix, letter for letter, with the one fallback it allows:
    # the prompts' upper clip at 6144 where 8192 spread too widely
    assert TRAFFIC["lengths"]["prompt"] == {
        "dist": "lognormal", "median": 2048, "sigma": 1.0, "min": 128,
        "max": 6144}
    assert TRAFFIC["lengths"]["output"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.6, "min": 64,
        "max": 1024}
    assert CONFIG["max_position_embeddings"] == 9216
    assert CONFIG["engine"]["prompt_buckets"] == [512, 1024, 2048, 4096,
                                                  6144, 8192]
    assert sorted(m["name"] for m in OWN) == sorted([
        "decode_step_ms.cmda", "decode_step_roofline.cmda",
        "attn_kernel_ms_per_call.cmda", "attn_kernel_roofline.cmda",
        "prefill_ms_per_group.cmda", "kv_window_freed_share.cmda",
        "kv_pool_used_peak.window.cmda", "kv_pool_used_peak.full.cmda",
        "moe_tokens_per_expert.cmda", "moe_held_pair_share.cmda",
        "moe_hot_expert_share.cmda", "prefill_attn_ms_per_call.cmda",
        "prefill_attn_mxu_share.cmda"])
    shared = [m["name"] for m in BENCHMARK["per_layer"]
              if CELL in m.get("workloads", []) and m not in OWN]
    assert shared == [
        "generator_late_p99_ms.gen", "decode_batch_mean",
        "prefill_pad_share", "kv_pool_used_peak",
        "engine_loop_held_share", "engine_cpu_ms_per_tick",
        "loop_other_cpu_share", "server_self_ms_per_stream",
        "queue_wait_ms_mean", "first_token_ms_mean"]
    listed = [m["name"] for m in BENCHMARK["end_to_end"]
              if CELL in m.get("workloads", [CELL])]
    assert listed == ["output_tok_s", "tpot_p95_ms", "setup_s"]


@pytest.mark.parametrize("entry", OWN, ids=lambda entry: entry["name"])
def test_every_own_metric_has_its_file(entry):
    body = metric_file(entry["name"])
    assert body["source"]["kind"] in layers.READERS
    for key in ("layer", "unit", "better", "moves"):
        assert body[key] == entry[key], key


def test_parameter_and_byte_arithmetic_is_the_issues():
    hp = CONFIG
    assert round(bytes_swa_moe.attention_params(hp) / 1e6, 1) == 142.6
    assert round(bytes_swa_moe.expert_params(hp) / 1e6, 2) == 50.33
    assert round(bytes_swa_moe.layer_fixed_params(hp) / 1e6, 1) == 344.5
    assert round(bytes_swa_moe.total_params(hp) / 1e6) == 4733
    weight_gb = bytes_swa_moe.total_params(hp) * 2 / 1e9
    assert abs(weight_gb - 9.47) / 9.47 < 0.01
    assert bytes_swa_moe.cache_bytes_per_token_layer(
        hp, hp["precision"]) == 4096
    # a step at 32 rows: every weight outside the experts, 14 of 16
    # experts a layer, the head's slice: ~8.6 GB; the rows on top
    weights = bytes_swa_moe.decode_step_bytes(hp, hp["precision"], 14, 0, 0)
    assert 8.5e9 < weights < 8.7e9
    rows = bytes_swa_moe.decode_step_bytes(
        hp, hp["precision"], 14, 60000, 25000) - weights
    assert rows == 4 * 85000 * 4096
    assert bytes_swa_moe.attn_call_bytes(hp, hp["precision"], 60000,
                                         25000) == 85000 * 4096
    # the prefill kernel's band: a window layer's pairs stop growing
    # with the square past the window; 4 x 128 flops a pair a head
    assert bytes_swa_moe.band_pairs(2048, 4096) == 2048 * 2049 / 2
    assert bytes_swa_moe.band_pairs(6144, 4096) \
        == 4096 * 4097 / 2 + 2048 * 4096
    assert bytes_swa_moe.band_pairs(6144, None) == 6144 * 6145 / 2
    one = bytes_swa_moe.prefill_attn_flops(hp, hp["precision"], 6144, 1)
    assert one == (3 * bytes_swa_moe.band_pairs(6144, 4096)
                   + bytes_swa_moe.band_pairs(6144, None)) / 4 * 128 * 512
    assert 1.1e12 < one < 1.2e12
    assert bytes_swa_moe.prefill_attn_flops(
        hp, hp["precision"], 512, 16) == 16 * 512 * 513 / 2 * 128 * 512


def test_readers_read_the_new_counters_and_nothing_from_a_parent():
    stats0 = {"moe": {"held_pairs": 10, "experts_hit": 5, "routed_pairs": 80,
                      "hot_expert_pairs": 2, "layer_steps": 4},
              "attn": {"window_rows": 1000, "full_rows": 500, "calls": 4},
              "prefill_batches": 10, "prefill_rows": 12,
              "prefill_bucket_tokens": 20000,
              "kv_pool": {"kinds": {"window": {"freed_behind": 10,
                                               "allocs": 100}}}}
    stats1 = {"moe": {"held_pairs": 110, "experts_hit": 45,
                      "routed_pairs": 880, "hot_expert_pairs": 22,
                      "layer_steps": 44},
              "attn": {"window_rows": 61000, "full_rows": 25500,
                       "calls": 44},
              "prefill_batches": 20, "prefill_rows": 32,
              "prefill_bucket_tokens": 60960,
              "kv_pool": {"kinds": {"window": {"freed_behind": 40,
                                               "allocs": 200}}}}
    samples = [{"kv_pool": {"kinds": {
        "window": {"used_pages": used, "num_pages": 4000},
        "full": {"used_pages": used // 2, "num_pages": 8000}}}}
        for used in (1000, 2400, 2000)]
    evidence = layers.Evidence(stats0=stats0, stats1=stats1, samples=samples,
                               config=CONFIG,
                               peaks={"hbm_bytes_s": 819e9,
                                      "bf16_flops": 197e12})
    def value(name):
        return layers.read(metric_file(name)["source"], evidence)

    assert value("moe_tokens_per_expert.cmda") == pytest.approx(2.5)
    assert value("moe_held_pair_share.cmda") == pytest.approx(12.5)
    assert value("moe_hot_expert_share.cmda") == pytest.approx(3.2)
    assert value("kv_window_freed_share.cmda") == pytest.approx(30.0)
    assert value("kv_pool_used_peak.window.cmda") == pytest.approx(60.0)
    assert value("kv_pool_used_peak.full.cmda") == pytest.approx(15.0)
    # the rooflines build on a time the trace gives: none, nothing
    assert value("attn_kernel_roofline.cmda") is None
    evidence.values["attn_kernel_ms_per_call.cmda"] = 0.02
    # (1500 + 625) rows a call x 4096 B over 819 GB/s = 10.63 us of 20
    assert value("attn_kernel_roofline.cmda") == pytest.approx(
        2125 * 4096 / 819e9 * 1e3 / 0.02 * 100)
    evidence.values["decode_step_ms.cmda"] = 20.0
    # one expert hit a layer: 3.43 GB of weights and 35 MB of rows
    assert value("decode_step_roofline.cmda") == pytest.approx(21.13, abs=0.05)
    # 20 rows in 10 groups of 2048 bucket tokens a row: two causal bands
    # of 2048 a call, 1.07 TFLOP... over 197 TFLOP/s = 2.73 ms of 5
    assert value("prefill_attn_mxu_share.cmda") is None
    evidence.values["prefill_attn_ms_per_call.cmda"] = 5.0
    assert value("prefill_attn_mxu_share.cmda") == pytest.approx(
        2 * 2048 * 2049 / 2 * 128 * 512 / 197e12 * 1e3 / 5.0 * 100)
    # a program without the counters (the parent): nothing, no raise
    parent = layers.Evidence(stats0={}, stats1={}, samples=[{}],
                             config=CONFIG, peaks={"hbm_bytes_s": 819e9,
                                                   "bf16_flops": 197e12})
    for entry in OWN:
        assert layers.read(metric_file(entry["name"])["source"],
                           parent) is None


def test_the_configuration_holds_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if "command-a-plus-05-2026" in line)
    assert CONFIG["source"] == row["source_url"]
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == "command-a-plus-ep8")
    assert entry["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert set(CONFIG["reduced"]) == set(CONFIG["reduced_why"])
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:4]
    assert CONFIG["num_experts_published"] == row["config"]["num_experts"]
    for key in ("shared_expert_combination_strategy", "norm", "expert_width",
                "rotary", "vision", "weights"):
        assert key in CONFIG["assumed"], key
    assert "tiny" in CONFIG and CONFIG["expect_attn_path"] == "ragged"


def test_adapter_registers_the_byte_functions():
    assert bytes_mod.decode_step_bytes_swa_moe \
        is bytes_swa_moe.decode_step_bytes
    assert bytes_mod.attn_call_bytes_swa_moe is bytes_swa_moe.attn_call_bytes
    assert bytes_mod.prefill_attn_flops_swa_moe \
        is bytes_swa_moe.prefill_attn_flops
    assert set(ADAPTER.CONTROLS) >= {"window", "rope-full", "float8_e4m3fn"}
