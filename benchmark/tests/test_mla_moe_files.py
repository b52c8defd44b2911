"""The pangu cell's files (ISSUE 27): its per-layer metric files load and
read what the program's new counters say, on hand-made evidence; the
bytes function gives the weight bytes reckoned in the issue; and the
configuration holds every published width.

Run with ``python3 -m pytest benchmark/tests -q``; not part of tier-1.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import bytes as bytes_mod  # noqa: E402
import bytes_mla_moe  # noqa: E402
import layers  # noqa: E402

CELL = "pangu-ultra-moe-ep16.reason"

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as handle:
    BENCHMARK = json.load(handle)
with open(os.path.join(BENCH, "configs", "pangu-ultra-moe-ep16.json")) as f:
    CONFIG = json.load(f)

PANGU = [m for m in BENCHMARK["per_layer"] if m.get("workloads") == [CELL]]
SHARED = [m["name"] for m in BENCHMARK["per_layer"]
          if m.get("workloads") == ["mistral7b.batch", CELL]]


def metric_file(name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def test_the_cell_lists_its_metrics_and_two_end_to_end():
    """Five of its own, where the source differs (the model step's two
    and the expert layer's three); the ten accepted ones whose sources
    read any generate cell list it beside ``mistral7b.batch``. Only
    ``attn_kernel_share`` has nothing to read: the module has no kernel."""
    assert sorted(m["name"] for m in PANGU) == [
        "decode_step_ms.pangu", "decode_step_roofline.pangu",
        "moe_held_pair_share.pangu", "moe_hot_expert_share.pangu",
        "moe_tokens_per_expert.pangu"]
    assert SHARED == [
        "generator_late_p99_ms.gen", "decode_batch_mean",
        "prefill_pad_share", "kv_pool_used_peak", "engine_loop_held_share",
        "engine_cpu_ms_per_tick", "loop_other_cpu_share",
        "server_self_ms_per_stream", "queue_wait_ms_mean",
        "first_token_ms_mean"]
    listed = [m["name"] for m in BENCHMARK["end_to_end"]
              if CELL in m.get("workloads", [CELL])]
    assert listed == ["output_tok_s", "tpot_p95_ms", "setup_s"]


@pytest.mark.parametrize("entry", PANGU, ids=lambda entry: entry["name"])
def test_every_pangu_entry_has_its_file(entry):
    body = metric_file(entry["name"])
    assert body["source"]["kind"] in layers.READERS
    for key in ("layer", "unit", "better", "moves"):
        assert body[key] == entry[key], key


# a window in which 100 ticks of 4 steps ran over 4 expert layers: 1600
# layer-steps; 64 rows x 8 choices each; 2 of 16 pairs held on average
STATS0 = {"moe": {"routed_pairs": 1000, "held_pairs": 100, "experts_hit": 50,
                  "hot_expert_pairs": 20, "layer_steps": 10}}
STATS1 = {"moe": {"routed_pairs": 1000 + 1600 * 512,
                  "held_pairs": 100 + 1600 * 32,
                  "experts_hit": 50 + 1600 * 14,
                  "hot_expert_pairs": 20 + 1600 * 5,
                  "layer_steps": 10 + 1600}}


@pytest.mark.parametrize("name,expected", [
    ("moe_tokens_per_expert.pangu", 32 / 14),
    ("moe_held_pair_share.pangu", 6.25),
    ("moe_hot_expert_share.pangu", 5 / 32 * 16),
])
def test_counter_metric_reads_the_expected_number(name, expected):
    source = metric_file(name)["source"]
    evidence = layers.Evidence(stats0=STATS0, stats1=STATS1)
    assert layers.read(source, evidence) == pytest.approx(expected)
    # a program without the counters (the parent): nothing, no raise
    assert layers.read(source, layers.Evidence(stats0={}, stats1={})) is None


def test_bytes_at_all_experts_hit_are_the_weights_the_issue_reckons():
    """4919.0M parameters less the embedding's 147.5M, of which a step
    reads 64 rows: 4771.7M and the norms' gains, 9.54 GB in bf16."""
    got = bytes_mla_moe.decode_step_bytes(CONFIG, CONFIG["precision"],
                                          experts_hit=16, live_tokens=0)
    assert f"{got / 1e9:.3g}" == "9.54"
    attention = (7680 * 1536 + 1536 * 128 * 192 + 7680 * 576
                 + 512 * 128 * 256 + 128 * 128 * 7680)          # 196.6M
    expert = 3 * 7680 * 2048                                    # 47.19M
    dense_layer = attention + 3 * 7680 * 18432                  # 621.2M
    expert_layer = attention + expert + 7680 * 256 + 16 * expert  # 1000.7M
    matrices = dense_layer + 4 * expert_layer + 2 * 19200 * 7680
    assert round(matrices / 1e6) == 4919
    gains = 5 * (4 * 7680 + 1536 + 512) + 7680
    assert got == 2 * (matrices - 19200 * 7680 + 64 * 7680 + gains)
    assert bytes_mla_moe.attention_params(CONFIG) == attention + 1536 + 512
    # 13.9 of 16 hit: the issue's 8.75 GB; a live token: 1152 B a layer
    less = bytes_mla_moe.decode_step_bytes(CONFIG, CONFIG["precision"],
                                           experts_hit=13.9, live_tokens=0)
    assert f"{less / 1e9:.3g}" == "8.75"
    assert bytes_mla_moe.cache_bytes_per_token(
        CONFIG, CONFIG["precision"]) == 5 * 1152


def test_roofline_reads_the_new_bytes_function_through_the_adapter():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "adapter_mla_moe", os.path.join(BENCH, "adapters", "mla_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert bytes_mod.decode_step_bytes_mla_moe \
        is bytes_mla_moe.decode_step_bytes
    source = metric_file("decode_step_roofline.pangu")["source"]
    samples = [{"kv_pool": {"used_pages": 1500, "page_tokens": 32}}]
    evidence = layers.Evidence(
        stats0=STATS0, stats1=STATS1, samples=samples, config=CONFIG,
        peaks={"hbm_bytes_s": 819e9},
        values={"decode_step_ms.pangu": 20.0})
    needed = bytes_mla_moe.decode_step_bytes(
        CONFIG, CONFIG["precision"], experts_hit=14, live_tokens=48000)
    assert layers.read(source, evidence) == pytest.approx(
        needed / 819e9 * 1e3 / 20.0 * 100.0)
    # without the step's time (an untraced run, the parent): nothing
    assert layers.read(source, layers.Evidence(
        stats0=STATS0, stats1=STATS1, samples=samples, config=CONFIG,
        peaks={"hbm_bytes_s": 819e9})) is None


def test_the_configuration_holds_every_published_width():
    published = {"hidden_size": 7680, "q_lora_rank": 1536,
                 "kv_lora_rank": 512, "num_attention_heads": 128,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "intermediate_size": 18432,
                 "moe_intermediate_size": 2048,
                 "n_routed_experts_published": 256,
                 "num_experts_per_tok": 8, "routed_scaling_factor": 2.5,
                 "n_shared_experts": 1, "num_key_value_heads": 128}
    assert {k: CONFIG[k] for k in published} == published
    assert sorted(CONFIG["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "max_position_embeddings",
        "num_nextn_predict_layers"])
    listed = [c for c in BENCHMARK["configs"]
              if c["name"] == "pangu-ultra-moe-ep16"][0]
    assert listed["reduced"] == CONFIG["reduced"]
    assert set(CONFIG["reduced"]) == set(CONFIG["reduced_why"])
    with open(os.path.join(BENCH, "traffic", "reason-closed.json")) as f:
        traffic = json.load(f)
    assert traffic["clients"] == 2 * CONFIG["engine"]["max_slots"] == 128
