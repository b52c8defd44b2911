"""The yardstick's own arithmetic: the trace reduction (busy union, idle
share, op table, gap attribution) on hand-made events and on a recorded
trace kept in the reduction's intermediate form, the percentile / TPOT
arithmetic on hand-made samples, and the traffic generator's promise
that every seed gets the same set of sizes and gaps.

Run with ``python3 -m pytest benchmark/tests -q``; not part of tier-1.
"""

import json
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import reduce  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402

RECORDED = os.path.join(HERE, "data", "classify_trace.json")

# one device line: a while loop [0, 100) enclosing two ops, then a lone op
KERNEL = "branch_0_fun.5 bf16[16,8,4,128] custom-call:tpu_custom_call"
OPS = [["while.1", 0, 100], ["fusion.1 bf16[4] fusion", 10, 20],
       [KERNEL, 40, 50], ["copy.3", 5000, 1000]]
HOST = [["tpu.engine.step", 90, 3000], ["resnet50/b4", 4000, 500],
        ["tpu.engine.prefill", 4400, 100]]


def test_merge_and_busy_union():
    assert reduce.merge([(5, 7), (0, 3), (2, 4), (7, 9)]) == [(0, 4), (5, 9)]
    # the while and its body count once: [0,100) + [5000,6000)
    assert reduce.busy_ns(OPS) == 1100


def test_self_times_subtract_nested_ops():
    got = dict(reduce.self_times(OPS))
    assert got == {"while.1": 30, "fusion.1 bf16[4] fusion": 20,
                   KERNEL: 50, "copy.3": 1000}
    assert sum(got.values()) == reduce.busy_ns(OPS)


def test_op_table_ranks_by_self_time():
    table = reduce.op_table(OPS, top=2)
    assert table == [("copy.3", 1e-6), (KERNEL, 5e-8)]


def test_matching_finds_a_kernel_by_its_custom_call_target():
    assert reduce.matching_ns(OPS, r"custom-call:tpu_custom_call") == (50, 1)
    assert reduce.matching_ns(OPS, r"nothing") == (0, 0)


def test_short_name_keeps_name_type_opcode_and_target():
    long = ('%branch_0_fun.5 = bf16[16,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} '
            'custom-call(s32[16,64]{1,0:T(8,128)} %get-tuple-element.1257, '
            's32[16]{0:T(128)} %x), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={s32[16,64]{1,0}}')
    assert reduce.short_name(long) == KERNEL
    assert reduce.short_name(
        '%fusion.159 = (f32[16]{0:T(128)S(1)}, bf16[16,1,4096]{2,0,1}) '
        'fusion(bf16[16,1,4096]{2,0,1} %p), kind=kLoop, calls=%fc') \
        == "fusion.159 (f32[16],..) fusion"
    assert reduce.short_name("jit_decode_k(123)") == "jit_decode_k(123)"


def test_idle_gap_goes_to_the_annotation_that_overlaps_most():
    # one gap, [100, 5000): the step covers 2990 ns of it, the executor's
    # annotation 500, the prefill 100
    assert reduce.idle_gaps(OPS, HOST) == [("tpu.engine.step", 4.9e-6)]
    assert reduce.idle_gaps(OPS, []) == [(reduce.NO_ANNOTATION, 4.9e-6)]


def test_reduce_form_idle_share_and_device_average():
    form = {"window_s": 11e-6, "host": HOST, "devices": {
        "/device:TPU:0": {"ops": OPS, "modules": []},
        "/device:TPU:1": {"ops": [], "modules": []}}}
    out = reduce.reduce_form(form)
    assert out["devices_used"] == 1
    assert out["busy_s"] == pytest.approx(1.1e-6)
    assert out["idle_share"] == pytest.approx(0.9)
    assert out["device_ops"][0] == ["copy.3", 1e-6]
    empty = reduce.reduce_form({"window_s": 1.0, "host": [], "devices": {}})
    assert empty["busy_s"] == 0.0 and empty["idle_share"] == 1.0


def test_crop_keeps_whole_events_inside_the_first_part():
    form = {"window_s": 1.0, "host": HOST,
            "devices": {"d": {"ops": OPS, "modules": []}}}
    cut = reduce.crop(form, 1e-3)            # the first 1000 ns
    assert [e[0] for e in cut["devices"]["d"]["ops"]] == [
        "while.1", "fusion.1 bf16[4] fusion", KERNEL]
    assert cut["window_s"] == pytest.approx(1e-6)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in tests/data")
def test_recorded_classify_trace_reduces_sanely():
    with open(RECORDED) as handle:
        form = json.load(handle)
    out = reduce.reduce_form(form)
    assert out["devices_used"] >= 1
    assert 0.0 < out["busy_s"] <= out["window_s"]
    assert 0.0 <= out["idle_share"] < 1.0
    ops = reduce.all_ops(form)
    # self times partition the busy time of one device line
    assert sum(ns for _n, ns in reduce.self_times(ops)) \
        == pytest.approx(reduce.busy_ns(ops), rel=1e-6)
    assert out["device_ops"] and out["device_ops"][0][1] > 0
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    # every gap is charged once: the gaps sum to span - busy
    starts = [e[1] for e in ops]
    span = max(e[1] + e[2] for e in ops) - min(starts)
    gaps = sum(s for _n, s in reduce.idle_gaps(ops, form["host"], top=10**6,
                                                min_ns=0))
    assert gaps == pytest.approx((span - reduce.busy_ns(ops)) / 1e9)
    # the executor's annotation is in the recorded host spans
    assert any("/b" in e[0] for e in form["host"])


def test_percentile_interpolates_like_numpy():
    values = [15.0, 20.0, 35.0, 40.0, 50.0]
    assert stats.percentile(values, 0) == 15.0
    assert stats.percentile(values, 50) == 35.0
    assert stats.percentile(values, 40) == pytest.approx(29.0)
    assert stats.percentile(values, 100) == 50.0
    assert stats.percentile([], 95) is None
    assert stats.percentile([7.0], 99) == 7.0


def test_a_failed_request_is_the_worst_latency():
    values = [10.0] * 18 + [math.inf, math.inf]
    assert stats.percentile(values, 50) == 10.0
    assert stats.percentile(values, 95) == math.inf


def test_tpot_is_span_over_frames_after_the_first():
    # 5 frames: first at 1.0 s, last at 1.2 s -> 4 gaps of 50 ms, however
    # the frames were bunched in between
    assert stats.tpot_ms(1.0, 1.2, 5) == pytest.approx(50.0)
    assert stats.tpot_ms(1.0, 1.0, 1) is None


LENGTHS = {"prompt": {"dist": "lognormal", "median": 320, "sigma": 0.8,
                      "min": 16, "max": 1024},
           "output": {"dist": "lognormal", "median": 100, "sigma": 0.7,
                      "min": 8, "max": 384}}


def test_open_schedule_same_sets_for_every_seed_in_another_order():
    mix = {"loop": "open", "rate_rps": 5.0, "ramp_s": 4, "drain_s": 1,
           "lengths": LENGTHS}
    a = traffic.schedule(mix, 7, 20.0)["requests"]
    b = traffic.schedule(mix, 3000000007, 20.0)["requests"]
    for plan in (a, b):
        measured = [r for r in plan if r["measured"]]
        assert len(measured) == 100 and len(plan) == 120
        assert all(0.0 <= r["due"] < 20.0 for r in measured)
        assert all(-4.0 <= r["due"] < 0.0 for r in plan
                   if not r["measured"])
        assert all(16 <= r["prompt_len"] <= 1024 for r in plan)

    def sizes(plan, key):
        return sorted(r[key] for r in plan if r["measured"])

    def gaps(plan):
        due = sorted(r["due"] for r in plan if r["measured"])
        return sorted(round(y - x, 9) for x, y in zip(due, due[1:]))

    assert sizes(a, "prompt_len") == sizes(b, "prompt_len")
    assert sizes(a, "max_new_tokens") == sizes(b, "max_new_tokens")
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
    assert a == traffic.schedule(mix, 7, 20.0)["requests"]


def test_closed_schedule_every_round_is_one_stratified_set():
    mix = {"loop": "closed", "clients": 8, "rounds": 3, "ramp_s": 1,
           "drain_s": 0, "lengths": LENGTHS}
    plan = traffic.schedule(mix, 5, 10.0)["clients"]
    assert len(plan) == 8 and all(len(seq) == 3 for seq in plan)
    want = sorted(traffic.quantile_set(LENGTHS["prompt"], 8))
    for j in range(3):
        assert sorted(seq[j]["prompt_len"] for seq in plan) == want


def test_prompt_ids_cover_the_vocabulary_and_repeat_by_seed():
    ids = traffic.prompt_ids(3000000007, 4, 512, 32768)
    assert ids == traffic.prompt_ids(3000000007, 4, 512, 32768)
    assert ids != traffic.prompt_ids(3000000007, 5, 512, 32768)
    assert 0 <= min(ids) and max(ids) < 32768 and max(ids) > 30000


def test_recorded_batch_trace_gives_the_kernel_and_the_step_time():
    """60 ms of mistral7b.batch's traced window (my chip run, PR 24): the
    layer-metric files' regex finds the ragged kernel; the step-time and
    roofline readers are checked on a hand-made tick."""
    import layers

    with open(os.path.join(HERE, "data", "batch_trace.json")) as handle:
        form = json.load(handle)
    root = os.path.dirname(HERE)

    def source(name):
        with open(os.path.join(root, "layer_metrics", name + ".json")) as f:
            return json.load(f)["source"]

    with open(os.path.join(root, "configs", "mistral7b.json")) as handle:
        config = json.load(handle)
    evidence = layers.Evidence(trace=form, config=config,
                               peaks={"hbm_bytes_s": 819e9},
                               samples=[{"kv_pool": {"used_pages": 300,
                                                     "page_tokens": 32}}])
    share = layers.read(source("attn_kernel_share"), evidence)
    assert 20.0 < share < 60.0          # the kernel is a third of the time
    # 60 ms hold no whole run of a decode executable (132 ms at K=4), so
    # that reader finds nothing and says so
    assert layers.read(source("decode_step_ms"), evidence) is None
    assert layers.read(source("attn_kernel_share"),
                       layers.Evidence(trace=None)) is None
    # one K=4 tick of 144 ms with its 4 x 32 kernel calls: 36 ms a step
    evidence.trace = {"window_s": 0.144, "host": [], "devices": {"d": {
        "modules": [["jit_decode_k(1)", 0, 144_000_000],
                    ["jit_prefill_batch(2)", 144_000_000, 50_000_000]],
        "ops": [[KERNEL, i * 1_000_000, 500_000] for i in range(128)]}}}
    step_ms = layers.read(source("decode_step_ms"), evidence)
    assert step_ms == pytest.approx(36.0)
    evidence.values["decode_step_ms"] = step_ms
    roofline = layers.read(source("decode_step_roofline"), evidence)
    # (7.12 GB of weights + 9600 tokens x 128 KiB) / 819 GB/s = 10.23 ms
    assert roofline == pytest.approx(10.23 / 36.0 * 100, rel=0.01)
