"""Per-layer metric `setup_step_compile_s`: see span_readers.setup_step_compile_s."""

from span_readers import setup_step_compile_s as read  # noqa: F401
