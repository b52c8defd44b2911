"""Property tests for pure components: BPE tokenizer round-trips,
cron field edges, and env-file parsing
(test-depth push, VERDICT r3 #5; sampling semantics live in
test_sampling.py). Seeded RNG — failures reproduce."""

import random
import time

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
