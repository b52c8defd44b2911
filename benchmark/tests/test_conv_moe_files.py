"""The LFM2-8B-A1B cell's files: they parse, its per-layer metric files
read what the program's counters say on hand-made evidence and nothing
from a program without them, the parameter and byte arithmetic of
``bytes_conv_moe.py`` adds up to the published model's, the
configuration holds every key of the published config.json, and the
reference imports nothing of the program.

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

import bytes_conv_moe  # noqa: E402
import layers  # noqa: E402

CELL = "lfm2-8b-a1b-pp2.chat"

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as handle:
    BENCHMARK = json.load(handle)
with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b-pp2.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "traffic", "chat-closed.json")) as f:
    TRAFFIC = json.load(f)

OWN = [m for m in BENCHMARK["per_layer"] if m.get("workloads") == [CELL]]

# huggingface.co/LiquidAI/LFM2-8B-A1B, config.json: the keys that say
# something about the model's shape
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention",
                    "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}

# the adapter is what makes the byte functions reachable to the readers
_spec = importlib.util.spec_from_file_location(
    "adapter_conv_moe", os.path.join(BENCH, "adapters", "conv_moe.py"))
ADAPTER = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ADAPTER)


def metric_file(name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def test_the_cell_its_traffic_and_its_metrics():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-8b-a1b-pp2", "chat-closed", 1)
    assert len(cell["why"]) <= 200
    assert (TRAFFIC["loop"], TRAFFIC["clients"], TRAFFIC["generators"]) \
        == ("closed", 256, 2)
    assert TRAFFIC["lengths"]["prompt"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32,
        "max": 2048}
    assert TRAFFIC["lengths"]["output"] == {
        "dist": "lognormal", "median": 224, "sigma": 0.7, "min": 16,
        "max": 1024}
    engine = CONFIG["engine"]
    assert CONFIG["max_position_embeddings"] == engine["max_len"] == 3072
    assert TRAFFIC["clients"] == 2 * engine["max_slots"] == 256
    assert engine["prompt_buckets"] == [128, 256, 512, 1024, 2048]
    assert CONFIG["expect_attn_path"] == "ragged"
    assert sorted(m["name"] for m in OWN) == sorted([
        "moe_experts_hit.lfm2", "moe_tokens_per_expert.lfm2",
        "moe_hot_expert_share.lfm2", "moe_held_pair_share.lfm2",
        "attn_kernel_ms_per_call.lfm2", "attn_kernel_roofline.lfm2",
        "attn_kernel_share.lfm2", "decode_step_roofline.lfm2",
        "state_pool_used_peak.lfm2", "prefill_attn_ms_per_call.lfm2",
        "prefill_attn_mxu_share.lfm2"])
    listed = [m["name"] for m in BENCHMARK["end_to_end"]
              if CELL in m.get("workloads", [CELL])]
    assert listed == ["output_tok_s", "tpot_p95_ms", "setup_s"]
    # a reader builds on the time of a metric read before it
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    assert names.index("decode_step_ms.engine") \
        < names.index("decode_step_roofline.lfm2")
    assert names.index("attn_kernel_ms_per_call.lfm2") \
        < names.index("attn_kernel_roofline.lfm2")
    assert names.index("prefill_attn_ms_per_call.lfm2") \
        < names.index("prefill_attn_mxu_share.lfm2")


@pytest.mark.parametrize("entry", OWN, ids=lambda entry: entry["name"])
def test_every_own_metric_has_its_file(entry):
    body = metric_file(entry["name"])
    assert body["source"]["kind"] in layers.READERS
    for key in ("layer", "unit", "better", "moves"):
        assert body[key] == entry[key], key


def test_parameter_and_byte_arithmetic_adds_up():
    hp, precision = CONFIG, CONFIG["precision"]
    assert round(bytes_conv_moe.conv_params(hp) / 1e6, 2) == 16.78
    assert round(bytes_conv_moe.attention_params(hp) / 1e6, 2) == 10.49
    assert round(bytes_conv_moe.expert_params(hp) / 1e6, 2) == 11.01
    assert bytes_conv_moe.layer_kinds(hp) == {
        "conv": 11, "attn": 3, "dense": 2, "moe": 12}
    assert round(bytes_conv_moe.total_params(hp) / 1e6) == 4667
    assert abs(bytes_conv_moe.total_params(hp) * 2 / 1e9 - 9.33) < 0.005
    # the published 24 layers: 8.3B only with the head tied
    whole = dict(hp, num_hidden_layers=24,
                 layer_types=PUBLISHED["layer_types"])
    assert round(bytes_conv_moe.total_params(whole) / 1e6) == 8340
    assert bytes_conv_moe.kv_bytes_per_token_layer(hp, precision) == 2048
    assert bytes_conv_moe.window_bytes_per_slot_layer(hp, precision) \
        == 8192
    # a step: the 12 layers' 32 experts are 8.46 GB of ~9.9
    no_experts = bytes_conv_moe.decode_step_bytes(hp, precision, 0, 0, 0)
    experts = bytes_conv_moe.decode_step_bytes(hp, precision, 32, 0, 0) \
        - no_experts
    assert abs(experts - 8.46e9) < 0.01e9
    windows = bytes_conv_moe.decode_step_bytes(hp, precision, 0, 128, 0) \
        - no_experts
    assert windows == 11 * 128 * 2 * 8192
    rows = bytes_conv_moe.decode_step_bytes(hp, precision, 0, 0, 86000) \
        - no_experts
    assert rows == 3 * 86000 * 2048
    assert bytes_conv_moe.attn_call_bytes(hp, precision, 86000) \
        == 86000 * 2048
    # a prompt of one token: one (query, key) pair, 32 heads, two
    # products of 64 at two operations a lane
    assert bytes_conv_moe.prefill_attn_flops(hp, precision, 1, 1) \
        == 32 * 2 * 2 * 64


def test_readers_read_the_new_counters_and_nothing_from_a_parent():
    stats0 = {"moe": {"routed_pairs": 0, "held_pairs": 0, "experts_hit": 0,
                      "hot_expert_pairs": 0, "layer_steps": 0},
              "conv": {"layer_steps": 0, "rows_live": 0},
              "attn": {"calls": 0, "kernel_calls": 0, "rows_live": 0},
              "prefill_bucket_tokens": 0, "prefill_rows": 0,
              "prefill_batches": 0}
    steps = 100
    stats1 = {"moe": {"routed_pairs": 12 * steps * 512,
                      "held_pairs": 12 * steps * 512,
                      "experts_hit": 12 * steps * 32,
                      "hot_expert_pairs": 12 * steps * 32,
                      "layer_steps": 12 * steps},
              "conv": {"layer_steps": 11 * steps,
                       "rows_live": 11 * steps * 120},
              "attn": {"calls": 3 * steps, "kernel_calls": 3 * steps,
                       "rows_live": 3 * steps * 80000},
              # 10 groups of 2 rows in the 2048 bucket
              "prefill_bucket_tokens": 20 * 2048, "prefill_rows": 20,
              "prefill_batches": 10}
    samples = [{"kv_pool": {"kinds": {"conv": {"claimed": claimed,
                                               "slots": 128}}}}
               for claimed in (64, 128, 120)]
    peaks = {"hbm_bytes_s": 819e9, "bf16_flops": 197e12}
    evidence = layers.Evidence(stats0=stats0, stats1=stats1, samples=samples,
                               config=CONFIG, peaks=peaks)

    def value(name):
        return layers.read(metric_file(name)["source"], evidence)

    assert value("moe_experts_hit.lfm2") == pytest.approx(32.0)
    assert value("moe_tokens_per_expert.lfm2") == pytest.approx(16.0)
    assert value("moe_hot_expert_share.lfm2") == pytest.approx(2.0)
    assert value("moe_held_pair_share.lfm2") == pytest.approx(100.0)
    assert value("attn_kernel_share.lfm2") == pytest.approx(100.0)
    assert value("state_pool_used_peak.lfm2") == pytest.approx(100.0)
    # the rooflines build on a time: none, nothing
    assert value("decode_step_roofline.lfm2") is None
    assert value("attn_kernel_roofline.lfm2") is None
    evidence.values["decode_step_ms.engine"] = 15.0
    want = bytes_conv_moe.decode_step_bytes(CONFIG, CONFIG["precision"], 32,
                                            120, 80000)
    assert value("decode_step_roofline.lfm2") == pytest.approx(
        want / 819e9 * 1e3 / 15.0 * 100)
    # the packed kernel's result type, in reduce.short_name's form
    trace = {"window_s": 1.0, "host": [], "devices": {"/device:TPU:0": {
        "ops": [["branch_0_fun.6 bf16[128,32,128] "
                 "custom-call:tpu_custom_call", 0, 200_000],
                ["branch_0_fun.9 bf16[128,32,128] "
                 "custom-call:tpu_custom_call", 200_000, 200_000],
                # the prefill's flash kernel: left out
                ["branch_0_fun.2 bf16[1,2048,4096] "
                 "custom-call:tpu_custom_call", 400_000, 900_000]],
        "modules": [["jit_decode_k(2)", 0, 400_000]]}}}
    evidence = layers.Evidence(stats0=stats0, stats1=stats1, samples=samples,
                               config=CONFIG, peaks=peaks, trace=trace)
    assert value("attn_kernel_ms_per_call.lfm2") == pytest.approx(0.2)
    evidence.values["attn_kernel_ms_per_call.lfm2"] = 0.2
    assert value("attn_kernel_roofline.lfm2") == pytest.approx(
        80000 * 2048 / 819e9 * 1e3 / 0.2 * 100)
    # the flash kernel's, and the decode kernel's left out of it
    assert value("prefill_attn_ms_per_call.lfm2") == pytest.approx(0.9)
    assert value("prefill_attn_mxu_share.lfm2") is None
    evidence.values["prefill_attn_ms_per_call.lfm2"] = 0.9
    flops = 2 * 2048 * 2049 / 2 * 32 * 2 * 2 * 64
    assert value("prefill_attn_mxu_share.lfm2") == pytest.approx(
        flops / 197e12 * 1e3 / 0.9 * 100)
    # a program without the counters (the parent): nothing, no raise
    parent = layers.Evidence(stats0={}, stats1={}, samples=[{}],
                             config=CONFIG, peaks=peaks)
    for entry in OWN:
        assert layers.read(metric_file(entry["name"])["source"],
                           parent) is None


def test_the_configuration_holds_every_published_key():
    assert CONFIG["source"] == SOURCE
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == "lfm2-8b-a1b-pp2")
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/lfm2-8b-a1b-pp2.json"
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "layer_types", "max_position_embeddings"]
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["layer_types"] == PUBLISHED["layer_types"][:14]
    for key in CONFIG["reduced"]:
        assert key in CONFIG["reduced_why"]
    for key in ("assumed", "deployment", "precision", "engine_why"):
        assert CONFIG[key], key
    for ours, theirs in CONFIG["model"]["from_keys"].items():
        assert theirs in CONFIG, (ours, theirs)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference_conv_moe.py")) as f:
        source = f.read()
    assert "gofr_tpu" not in source.replace(
        "gofr_tpu/models/conv_moe.py", "").replace("``gofr_tpu", "")
    assert "import gofr" not in source and "from gofr" not in source
    assert 'default_matmul_precision("highest")' in source
    assert set(ADAPTER.reference.LIMITS) == {
        "logits_rel_l2_q1", "logits_rel_l2_max", "route_swap_share",
        "router_swap_share", "served_argmax_share_min",
        "served_margin_p99"}
    assert sorted(ADAPTER.STATE_CONTROLS) + sorted(ADAPTER.CONTROLS) == [
        "pad-unmasked", "stale-window", "window-dropped", "bf16-router",
        "float8_e4m3fn", "no-b-gate", "no-c-gate", "no-qk-norm",
        "no-router-bias"]
    assert sorted(ADAPTER.SERVED_CONTROLS) == [
        "served-stale-window", "served-window-dropped"]
