"""Tests for config / logging utilities and the profiler hook."""

import io
import logging as stdlib_logging

from fraud_detection_tpu.utils import (
    AppConfig,
    KafkaConfig,
    LLMConfig,
    load_dotenv,
    parse_env_file,
)
from fraud_detection_tpu.utils.logging import LogfmtFormatter, get_logger, kv


# ---------------------------------------------------------------------------
# .env parsing
# ---------------------------------------------------------------------------

def test_parse_env_file(tmp_path):
    f = tmp_path / ".env"
    f.write_text(
        "# comment\n"
        "DEEPSEEK_API_KEY=sk-abc123\n"
        'KAFKA_BOOTSTRAP_SERVERS="broker1:9092,broker2:9092"\n'
        "export KAFKA_INPUT_TOPIC=raw-topic\n"
        "QUOTED='with spaces'\n"
        "INLINE=value # trailing comment\n"
        "EMPTY=\n"
        "malformed line without equals ignored\n")
    env = parse_env_file(f)
    assert env["DEEPSEEK_API_KEY"] == "sk-abc123"
    assert env["KAFKA_BOOTSTRAP_SERVERS"] == "broker1:9092,broker2:9092"
    assert env["KAFKA_INPUT_TOPIC"] == "raw-topic"
    assert env["QUOTED"] == "with spaces"
    assert env["INLINE"] == "value"
    assert env["EMPTY"] == ""
    assert "malformed" not in env


def test_parse_env_file_missing(tmp_path):
    assert parse_env_file(tmp_path / "nope.env") == {}


def test_load_dotenv_dual_paths_no_override(tmp_path):
    # Reference semantics: root .env + utils/.env (Q8), existing env wins.
    (tmp_path / ".env").write_text("A=root\nB=root\n")
    sub = tmp_path / "utils"
    sub.mkdir()
    (sub / ".env").write_text("B=utils\nC=utils\n")
    environ = {"A": "preexisting"}
    applied = load_dotenv([tmp_path / ".env", sub / ".env"], environ=environ)
    assert environ == {"A": "preexisting", "B": "root", "C": "utils"}
    assert applied == {"B": "root", "C": "utils"}


# ---------------------------------------------------------------------------
# typed config
# ---------------------------------------------------------------------------

def test_kafka_config_from_env():
    env = {
        "KAFKA_BOOTSTRAP_SERVERS": "k1:9092",
        "KAFKA_INPUT_TOPIC": "in",
        "KAFKA_OUTPUT_TOPIC": "out",
        "KAFKA_CONSUMER_GROUP": "grp",
        "KAFKA_SECURITY_PROTOCOL": "SASL_SSL",
        "KAFKA_USERNAME": "u",
        "KAFKA_PASSWORD": "p",
    }
    c = KafkaConfig.from_env(env)
    assert c.bootstrap_servers == "k1:9092"
    assert c.security_protocol == "SASL_SSL"
    assert c.username == "u" and c.password == "p"


def test_kafka_config_defaults_match_reference():
    c = KafkaConfig.from_env({})
    assert c.bootstrap_servers == "localhost:9092"
    assert c.input_topic == "customer-dialogues-raw"
    assert c.output_topic == "dialogues-classified"
    assert c.consumer_group == "dialogue-classifier-group"
    assert c.security_protocol is None


def test_llm_config_and_backend():
    c = LLMConfig.from_env({"DEEPSEEK_API_KEY": "sk-x", "LLM_TEMPERATURE": "0.3"})
    assert c.api_key == "sk-x"
    assert c.base_url == "https://api.deepseek.com/v1"
    assert c.model == "deepseek-chat"
    assert c.temperature == 0.3
    be = c.make_backend(transport=lambda *a, **k: None)
    assert be.api_key == "sk-x" and be.timeout == 90.0 and be.max_attempts == 3


def test_app_config_aggregates():
    cfg = AppConfig.from_env({"FRAUD_BATCH_SIZE": "64", "FRAUD_MAX_WAIT": "0.2"})
    assert cfg.serving.batch_size == 64
    assert cfg.serving.max_wait == 0.2
    assert cfg.kafka.input_topic == "customer-dialogues-raw"


# ---------------------------------------------------------------------------
# logging
# ---------------------------------------------------------------------------

def test_logfmt_formatter_quotes_and_kv():
    rec = stdlib_logging.LogRecord(
        "fraud_detection_tpu.test", stdlib_logging.INFO, "f.py", 1,
        'scored batch with "quotes"', (), None)
    rec.kv = {"batch": 32, "topic": "my topic"}
    line = LogfmtFormatter().format(rec)
    assert "level=info" in line
    assert 'msg="scored batch with \\"quotes\\""' in line
    assert "batch=32" in line
    assert 'topic="my topic"' in line


def test_get_logger_emits_to_configured_stream():
    from fraud_detection_tpu.utils.logging import configure

    buf = io.StringIO()
    configure(level="DEBUG", stream=buf)
    log = get_logger("unit")
    log.info("hello world", extra=kv(n=7))
    out = buf.getvalue()
    assert 'msg="hello world"' in out
    assert "n=7" in out
    assert "logger=fraud_detection_tpu.unit" in out


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_device_trace_noop_without_dir(monkeypatch):
    from fraud_detection_tpu.utils import device_trace

    monkeypatch.delenv("FRAUD_TPU_PROFILE_DIR", raising=False)
    with device_trace("x"):
        pass  # must not require jax import or profiler state


# multi-host (DCN) mesh helper coverage lives in tests/test_mesh_multihost.py
