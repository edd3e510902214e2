"""Per-layer metric `device_idle_pct.tokens`: see readers.device_idle_pct."""

from readers import device_idle_pct as read  # noqa: F401
