"""Per-layer metric `step_dispatch_ms.tokens`: see span_readers.step_dispatch_ms."""

from span_readers import step_dispatch_ms as read  # noqa: F401
