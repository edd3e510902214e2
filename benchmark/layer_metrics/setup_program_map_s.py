"""Per-layer metric `setup_program_map_s`: see scope_readers.setup_program_map_s."""

from scope_readers import setup_program_map_s as read  # noqa: F401
