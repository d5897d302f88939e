"""Cross-cutting utilities: config, structured logging, the profiler hook.

Replaces the reference's import-time dotenv reads + print() observability
(SURVEY.md §5) with typed config dataclasses, logfmt logging, and real
measurement hooks.
"""

from fraud_detection_tpu.utils.config import (
    AppConfig,
    KafkaConfig,
    LLMConfig,
    ServingConfig,
    load_dotenv,
    parse_env_file,
)
from fraud_detection_tpu.utils.logging import configure, get_logger, kv
from fraud_detection_tpu.utils.tracing import device_trace

__all__ = [
    "AppConfig",
    "KafkaConfig",
    "LLMConfig",
    "ServingConfig",
    "load_dotenv",
    "parse_env_file",
    "configure",
    "get_logger",
    "kv",
    "device_trace",
]
