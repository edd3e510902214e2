"""Per-layer metric `flash_attn_ms_per_step`: see readers.flash_attn_ms_per_step."""

from readers import flash_attn_ms_per_step as read  # noqa: F401
