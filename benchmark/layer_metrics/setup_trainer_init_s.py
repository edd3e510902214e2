"""Per-layer metric `setup_trainer_init_s`: see span_readers.setup_trainer_init_s."""

from span_readers import setup_trainer_init_s as read  # noqa: F401
