"""Property tests for pure components: BPE tokenizer round-trips,
window-rung selection, cron field edges, and env-file parsing
(test-depth push, VERDICT r3 #5; sampling semantics live in
test_sampling.py). Seeded RNG — failures reproduce."""

import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# -- tokenizer ---------------------------------------------------------------

from gofr_tpu.tokenizer import Tokenizer


def test_tokenizer_bytes_roundtrip_fuzz():
    """Byte-level tokenizer (no merges): encode∘decode is identity for
    arbitrary unicode, including astral plane and control chars."""
    tok = Tokenizer()
    rng = random.Random(5)
    pool = "abc 123 \t\n éü 日本語 🎉🚀 "
    for _ in range(100):
        text = "".join(rng.choice(pool) for _ in range(rng.randint(0, 80)))
        assert tok.decode(tok.encode(text)) == text


def test_trained_tokenizer_roundtrip_and_compression():
    corpus = ["the quick brown fox jumps over the lazy dog",
              "the lazy dog sleeps", "quick quick brown fox"] * 10
    tok = Tokenizer.train(corpus, vocab_size=300)
    for text in corpus + ["the fox", "dog dog dog", "völlig neu"]:
        ids = tok.encode(text)
        assert tok.decode(ids) == text
    # merges actually fire: trained encoding is shorter than byte-level
    assert len(tok.encode(corpus[0])) < len(corpus[0].encode())


def test_tokenizer_native_matches_python_path():
    """When the C++ extension is present both paths must agree exactly."""
    corpus = ["abcabcabc", "banana bandana"] * 5
    tok = Tokenizer.train(corpus, vocab_size=280)
    if tok._native is None:
        pytest.skip("native tokenizer not built in this environment")
    rng = random.Random(9)
    for _ in range(50):
        text = "".join(rng.choice("abnd ") for _ in range(rng.randint(0, 60)))
        assert tok._encode_native(text.encode()) == \
            tok._encode_python(text.encode())


# -- engine window-rung selection -------------------------------------------

def _ladder_engine(max_len):
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.generate import GenerationEngine
    cfg = llama.config("tiny")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return GenerationEngine(cfg, params, max_slots=2, max_len=max_len,
                            prompt_buckets=(8,))


def test_window_ladder_shape():
    assert _ladder_engine(64)._window_ladder == [None]
    assert _ladder_engine(512)._window_ladder == [128, 256, None]
    assert _ladder_engine(1024)._window_ladder == [128, 256, 512, None]


def test_window_rung_selection_boundaries():
    engine = _ladder_engine(512)
    assert engine._pick_window([100], 8) == 128      # 108 fits 128
    assert engine._pick_window([120], 8) == 128      # 128 exactly fits
    assert engine._pick_window([121], 8) == 256      # 129 spills to 256
    assert engine._pick_window([240], 8) == 256      # 248 fits 256
    assert engine._pick_window([250], 8) is None     # 258 → full cache
    assert engine._pick_window([300], 8) is None
    assert engine._pick_window([], 4) == 128         # no active fills
    # the max across slots drives the rung
    assert engine._pick_window([10, 200], 4) == 256


def test_window_ladder_off_by_flag():
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.generate import GenerationEngine
    cfg = llama.config("tiny")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    engine = GenerationEngine(cfg, params, max_slots=2, max_len=512,
                              prompt_buckets=(8,), window_ladder=False)
    assert engine._window_ladder == [None]
    assert engine._pick_window([10], 1) is None


# -- cron field edges --------------------------------------------------------

from gofr_tpu.cron import CronJob, CronParseError, parse_schedule  # noqa


def test_cron_dow_sunday_convention():
    job = CronJob("0 0 * * 0", "sunday-job", lambda ctx: None)
    sunday = time.struct_time((2026, 8, 2, 0, 0, 0, 6, 214, -1))   # tm_wday 6
    monday = time.struct_time((2026, 8, 3, 0, 0, 0, 0, 215, -1))
    assert job.due(sunday)
    assert not job.due(monday)


def test_cron_month_and_dom_bounds():
    assert parse_schedule("0 0 1 1 *")["month"] == {1}
    assert parse_schedule("0 0 31 12 *")["day"] == {31}
    for bad in ("0 0 0 * *", "0 0 32 * *", "0 0 * 13 *", "60 * * * *",
                "* 24 * * *", "* * * * 7"):
        with pytest.raises(CronParseError):
            parse_schedule(bad)


def test_cron_combined_list_range_step():
    minutes = parse_schedule("1,5-9,*/20 * * * *")["minute"]
    assert minutes == {1, 5, 6, 7, 8, 9, 0, 20, 40}


# -- env-file parsing --------------------------------------------------------

from gofr_tpu.config import EnvConfig, load_env_file  # noqa: E402


def test_env_file_parsing_edges(tmp_path):
    env = tmp_path / ".env"
    env.write_text(
        "# comment line\n"
        "PLAIN=value\n"
        "QUOTED=\"with spaces\"\n"
        "SINGLE='single quoted'\n"
        "EMPTY=\n"
        "SPACED =  padded  \n"
        "\n"
        "NOEQUALS\n"
        "INLINE=x # trailing comment not stripped\n")
    values = load_env_file(str(env))
    assert values["PLAIN"] == "value"
    assert values["QUOTED"] == "with spaces"
    assert values["SINGLE"] == "single quoted"
    assert values["EMPTY"] == ""
    assert values["SPACED"] == "padded"
    assert "NOEQUALS" not in values


def test_env_overlay_precedence(tmp_path, monkeypatch):
    configs = tmp_path / "configs"
    configs.mkdir()
    (configs / ".env").write_text("A=base\nB=base\nC=base\n")
    (configs / ".prod.env").write_text("B=prod\n")
    monkeypatch.setenv("APP_ENV", "prod")
    monkeypatch.setenv("C", "process")
    config = EnvConfig(str(configs))
    assert config.get("A") == "base"        # base survives
    assert config.get("B") == "prod"        # overlay wins over base
    assert config.get("C") == "process"     # process env wins over all


def test_window_rung_and_stats_exposed():
    """The attention-window rung is observable: stats() lists the ladder
    and counts the ticks each rung took (timeline.ticks_by_width)."""
    import asyncio

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.generate import GenerationEngine

    cfg = llama.config("tiny")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    container = new_mock_container()
    engine = GenerationEngine(cfg, params, max_slots=2, max_len=256,
                              prompt_buckets=(8,),
                              logger=container.logger,
                              metrics=container.metrics)
    assert engine.stats()["window_ladder"] == [128, 256]

    async def main():
        await engine.start()
        try:
            await asyncio.wait_for(
                engine.generate([1, 2, 3], max_new_tokens=4), 60.0)
            timeline = engine.stats()["timeline"]
            assert set(timeline["ticks_by_width"]) == {"128"}
            assert timeline["ticks_by_width"]["128"] == timeline["ticks"] > 0
        finally:
            await engine.stop()
    asyncio.run(main())
