"""Config tree: env compatibility with reference config.py + overrides."""

import json

from finchat_tpu.utils.config import load_config


def test_defaults():
    cfg = load_config()
    assert cfg.kafka.backend == "memory"
    assert cfg.engine.temperature == 0.5  # parity with llm_agent.py:37,44
    assert cfg.engine.watchdog_seconds == 100.0  # parity with main.py:138
    assert cfg.vector.default_limit == 10_000  # parity with qdrant_tool.py:145


def test_reference_env_names(monkeypatch):
    # The reference's .env drops in unchanged (config.py:8-47)
    monkeypatch.setenv("KAFKA_SERVER", "broker:9092")
    monkeypatch.setenv("KAFKA_USERNAME", "u")
    monkeypatch.setenv("KAFKA_PASSWORD", "p")
    monkeypatch.setenv("MONGODB_URI", "mongodb://x")
    monkeypatch.setenv("QDRANT_URL", "http://q")
    cfg = load_config()
    assert cfg.kafka.bootstrap_servers == "broker:9092"
    assert cfg.store.mongodb_uri == "mongodb://x"
    assert cfg.vector.url == "http://q"
    rendered = cfg.kafka.librdkafka_config()
    assert rendered["security.protocol"] == "SASL_SSL"
    assert rendered["sasl.mechanisms"] == "PLAIN"


def test_plaintext_switch(monkeypatch):
    monkeypatch.delenv("KAFKA_USERNAME", raising=False)
    monkeypatch.delenv("KAFKA_PASSWORD", raising=False)
    cfg = load_config()
    assert cfg.kafka.librdkafka_config()["security.protocol"] == "PLAINTEXT"


def test_unknown_override_key_rejected():
    import pytest

    with pytest.raises(KeyError):
        load_config(overrides={"engine.max_seq": 4})  # typo for max_seqs


def test_file_and_override_precedence(tmp_path):
    cfile = tmp_path / "cfg.json"
    cfile.write_text(json.dumps({"engine.max_seqs": 8, "model": {"preset": "llama3-8b"}}))
    cfg = load_config(str(cfile), overrides={"engine.max_seqs": 16})
    assert cfg.engine.max_seqs == 16  # explicit override wins
    assert cfg.model.preset == "llama3-8b"


def test_engine_env_readers(monkeypatch):
    from finchat_tpu.utils.config import load_config

    monkeypatch.setenv("FINCHAT_WARMUP", "0")
    monkeypatch.setenv("FINCHAT_RING_PREFILL_MIN", "2048")
    monkeypatch.setenv("FINCHAT_SPEC_TOKENS", "4")
    cfg = load_config()
    assert cfg.engine.warmup_on_start is False
    assert cfg.engine.ring_prefill_min_tokens == 2048
    assert cfg.engine.spec_tokens == 4

    monkeypatch.delenv("FINCHAT_SPEC_TOKENS")
    assert load_config().engine.spec_tokens == 0  # per-token default

    monkeypatch.setenv("FINCHAT_WARMUP", "1")
    cfg = load_config()
    assert cfg.engine.warmup_on_start is True


def test_mixed_step_env_reader(monkeypatch):
    from finchat_tpu.utils.config import load_config

    cfg = load_config()
    assert cfg.engine.mixed_step is True  # default on for the chunked path

    monkeypatch.setenv("FINCHAT_MIXED_STEP", "0")
    assert load_config().engine.mixed_step is False


def test_tool_streaming_and_hold_ttl_env_readers(monkeypatch):
    from finchat_tpu.utils.config import load_config

    cfg = load_config()
    assert cfg.engine.tool_streaming is True  # default on (ISSUE 9)
    assert cfg.engine.partial_hold_ttl_seconds == 30.0  # legacy HOLD_TTL_S

    monkeypatch.setenv("FINCHAT_TOOL_STREAMING", "0")
    monkeypatch.setenv("FINCHAT_PARTIAL_HOLD_TTL_SECONDS", "2.5")
    cfg = load_config()
    assert cfg.engine.tool_streaming is False
    assert cfg.engine.partial_hold_ttl_seconds == 2.5
