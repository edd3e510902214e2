"""Per-layer metric `setup_first_log_s`: see span_readers.setup_first_log_s."""

from span_readers import setup_first_log_s as read  # noqa: F401
