"""Per-layer metric `input_wait_pct.tokens`: see readers.input_wait_pct."""

from readers import input_wait_pct as read  # noqa: F401
