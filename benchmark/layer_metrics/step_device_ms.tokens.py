"""Per-layer metric `step_device_ms.tokens`: see readers.step_device_ms."""

from readers import step_device_ms as read  # noqa: F401
