"""The Jamba2-3B cell's files (ISSUE 37): they parse, its per-layer
metric files read what the program's counters say on hand-made evidence
and nothing from a program without them, the parameter and byte
arithmetic of ``bytes_jamba.py`` equals the issue's, the configuration
holds every key of the catalog's row, and the reference imports nothing
of the program.

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

import bytes_jamba  # noqa: E402
import layers  # noqa: E402

CELL = "jamba2-3b.chat"

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as handle:
    BENCHMARK = json.load(handle)
with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "traffic", "chat-closed.json")) as f:
    TRAFFIC = json.load(f)

OWN = [m for m in BENCHMARK["per_layer"] if m.get("workloads") == [CELL]]

# the adapter is what makes the byte functions reachable to the readers
_spec = importlib.util.spec_from_file_location(
    "adapter_jamba", os.path.join(BENCH, "adapters", "jamba.py"))
ADAPTER = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ADAPTER)


def metric_file(name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def test_the_cell_its_traffic_and_its_metrics():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jamba2-3b", "chat-closed", 1)
    assert len(cell["why"]) <= 200
    assert (TRAFFIC["loop"], TRAFFIC["clients"], TRAFFIC["generators"],
            TRAFFIC["rounds"], TRAFFIC["ramp_s"], TRAFFIC["drain_s"]) == (
        "closed", 256, 2, 64, 10, 40)
    # ISSUE 37's mix, letter for letter
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
    assert sorted(m["name"] for m in OWN) == sorted([
        "decode_step_ms.jamba", "decode_step_roofline.jamba",
        "prefill_ms_per_group.jamba", "scan_share.jamba",
        "scan_kernel_ms_per_call.jamba", "scan_kernel_roofline.jamba",
        "ssm_rows_live_share.jamba", "state_pool_used_peak.jamba"])
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
    hp, precision = CONFIG, CONFIG["precision"]
    assert round(bytes_jamba.mixer_params(hp) / 1e6, 2) == 41.24
    assert round(bytes_jamba.mlp_params(hp) / 1e6, 2) == 62.91
    assert round(bytes_jamba.attention_params(hp) / 1e6, 2) == 13.76
    assert bytes_jamba.layer_counts(hp) == {"attn": 2, "ssm": 26}
    assert round(bytes_jamba.total_params(hp) / 1e6) == 3029
    assert abs(bytes_jamba.total_params(hp) * 2 / 1e9 - 6.06) < 0.005
    # h 5120 x 16 float32 and 3 x 5120 bf16 a layer: 9.32 MB a slot
    assert bytes_jamba.state_bytes_per_slot_layer(hp, precision) \
        == 327680 + 30720
    assert round(26 * 358400 / 1e6, 2) == 9.32
    assert bytes_jamba.kv_bytes_per_token_layer(hp, precision) == 512
    # a step at 128 live rows of ~650 tokens: 6.06 GB of weights, 2.39 GB
    # of state read and written, 0.09 GB of keys and values
    weights = bytes_jamba.decode_step_bytes(hp, precision, 0, 0)
    assert abs(weights - 6.06e9) < 0.01e9
    state = bytes_jamba.decode_step_bytes(hp, precision, 128, 0) - weights
    assert state == 26 * 128 * 2 * 358400
    assert abs(state - 2.39e9) < 0.01e9
    rows = bytes_jamba.decode_step_bytes(hp, precision, 0, 83200) - weights
    assert rows == 2 * 83200 * 512
    # a scan call of 4 rows x 512 tokens: 10 B a channel a token, the
    # projections, the final states
    assert bytes_jamba.scan_call_bytes(hp, precision, 512, 4) == 4 * (
        512 * (5120 * 10 + 2 * 16 * 4) + 5120 * 16 * 4)


def test_readers_read_the_new_counters_and_nothing_from_a_parent():
    stats0 = {"ssm": {"rows_live": 100, "rows_read": 200, "layer_steps": 26},
              "attn": {"rows_live": 1000, "rows_read": 5000, "calls": 2},
              "prefill_batches": 10, "prefill_rows": 12,
              "prefill_bucket_tokens": 4096}
    stats1 = {"ssm": {"rows_live": 100 + 26 * 10 * 96,
                      "rows_read": 200 + 26 * 10 * 128,
                      "layer_steps": 26 + 260},
              "attn": {"rows_live": 1000 + 20 * 60000,
                       "rows_read": 5000 + 20 * 128 * 2048, "calls": 22},
              "prefill_batches": 20, "prefill_rows": 52,
              "prefill_bucket_tokens": 4096 + 40 * 512}
    samples = [{"kv_pool": {"kinds": {"ssm": {"claimed": claimed,
                                              "slots": 128}}}}
               for claimed in (64, 128, 120)]
    peaks = {"hbm_bytes_s": 819e9, "bf16_flops": 197e12}
    evidence = layers.Evidence(stats0=stats0, stats1=stats1, samples=samples,
                               config=CONFIG, peaks=peaks)

    def value(name):
        return layers.read(metric_file(name)["source"], evidence)

    assert value("ssm_rows_live_share.jamba") == pytest.approx(75.0)
    assert value("state_pool_used_peak.jamba") == pytest.approx(100.0)
    # the rooflines build on a time the trace gives: none, nothing
    assert value("decode_step_roofline.jamba") is None
    assert value("scan_kernel_roofline.jamba") is None
    evidence.values["decode_step_ms.jamba"] = 12.0
    want = bytes_jamba.decode_step_bytes(CONFIG, CONFIG["precision"], 96,
                                         60000)
    assert value("decode_step_roofline.jamba") == pytest.approx(
        want / 819e9 * 1e3 / 12.0 * 100)
    assert 70 < value("decode_step_roofline.jamba") < 85
    evidence.values["scan_kernel_ms_per_call.jamba"] = 1.0
    # 40 rows in 10 groups, each row in a 512 bucket
    assert value("scan_kernel_roofline.jamba") == pytest.approx(
        bytes_jamba.scan_call_bytes(CONFIG, CONFIG["precision"], 512, 4)
        / 819e9 * 1e3 / 1.0 * 100)
    # the trace readers against names in reduce.short_name's form
    trace = {"window_s": 1.0, "host": [], "devices": {"/device:TPU:0": {
        "ops": [["selective_scan.78 (f32[4,512,5120],..) "
                 "custom-call:tpu_custom_call", 0, 2_000_000],
                ["selective_scan (f32[4,512,5120],..) "
                 "custom-call:tpu_custom_call", 2_000_000, 2_000_000],
                ["branch_0_fun.6 bf16[4,512,2560] "
                 "custom-call:tpu_custom_call", 4_000_000, 1_000_000],
                # the call wrapped in a fusion with the update that
                # stacks its final state: the kernel's time too
                ["selective_scan.11 (f32[7,1,16,5120],..) fusion",
                 4_000_000, 2_000_000],
                ["select_dynamic-update-slice_fusion.38 "
                 "f32[26,128,16,5120] fusion", 5_000_000, 100_000]] * 1
        + [["select_dynamic-update-slice_fusion.3 f32[26,128,16,5120] "
            "fusion", 6_000_000 + i, 1] for i in range(51)],
        "modules": [["jit_prefill_batch(1)", 0, 5_000_000],
                    ["jit_decode_k(2)", 5_000_000, 3_000_000]]}}}
    evidence = layers.Evidence(stats0=stats0, stats1=stats1, samples=samples,
                               config=CONFIG, peaks=peaks, trace=trace)
    assert value("scan_kernel_ms_per_call.jamba") == pytest.approx(2.0)
    assert value("prefill_ms_per_group.jamba") == pytest.approx(5.0)
    # 52 state updates are 2 steps of 26 layers: 3 ms of decode over 2
    assert value("decode_step_ms.jamba") == pytest.approx(1.5)
    assert 0 < value("scan_share.jamba") <= 100
    # a program without the counters (the parent): nothing, no raise
    parent = layers.Evidence(stats0={}, stats1={}, samples=[{}],
                             config=CONFIG, peaks=peaks)
    for entry in OWN:
        assert layers.read(metric_file(entry["name"])["source"],
                           parent) is None


def test_the_configuration_holds_every_key_of_the_catalogs_row():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if '"AI21-Jamba2-3B"' in line)
    assert CONFIG["source"] == row["source_url"]
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == "jamba2-3b")
    assert entry["source"] == row["source_url"]
    assert entry["file"] == "benchmark/configs/jamba2-3b.json"
    assert entry["reduced"] == CONFIG["reduced"] \
        == ["max_position_embeddings"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    for key in CONFIG["reduced"]:
        assert key in CONFIG["reduced_why"]
    for key in ("assumed", "deployment", "precision", "engine_why"):
        assert CONFIG[key], key
    # the program's fields come from the published keys
    for ours, theirs in CONFIG["model"]["from_keys"].items():
        assert theirs in CONFIG, (ours, theirs)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference_jamba.py")) as f:
        source = f.read()
    assert "gofr_tpu" not in source.replace(
        "gofr_tpu/models/jamba.py", "").replace("``gofr_tpu", "")
    assert "import gofr" not in source and "from gofr" not in source
    assert 'default_matmul_precision("highest")' in source
    assert set(ADAPTER.reference.LIMITS) == {
        "logits_rel_l2_q1", "logits_rel_l2_max", "scan_rel_l2",
        "served_argmax_share_min", "served_margin_p99"}
    assert sorted(ADAPTER.STATE_CONTROLS) + sorted(ADAPTER.CONTROLS) == [
        "conv-from-end", "h-dropped", "pad-unmasked", "stale-h",
        "bf16-h", "float8_e4m3fn", "no-inner-norms"]
