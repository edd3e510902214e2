"""Per-layer metric `collective_exposed_ms_per_step`: see readers.collective_exposed_ms_per_step."""

from readers import collective_exposed_ms_per_step as read  # noqa: F401
