"""Per-layer metric `trainer_host_ms_per_step.tokens`: see span_readers.trainer_host_ms_per_step."""

from span_readers import trainer_host_ms_per_step as read  # noqa: F401
