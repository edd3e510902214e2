"""Per-layer metric `flash_attn_roofline`: see readers.flash_attn_roofline."""

from readers import flash_attn_roofline as read  # noqa: F401
