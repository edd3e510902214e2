"""Per-layer metric `moe_expert_imbalance.tokens`: rows on the fullest held
expert over rows on the mean one (each a mean over the expert layers), the
newest value the trainer logged. The step reports both as metrics
(`moe_rows_fullest`, `moe_rows_mean`) and the trainer's log mirrors them
into the program's registry; a program without them (or without the
registry) gives None."""


def read(ctx):
    try:
        from pytorch_distributed_train_tpu.obs.registry import get_registry
    except ImportError:
        return None
    registry = get_registry()
    fullest = registry.get_value("train_moe_rows_fullest")
    mean = registry.get_value("train_moe_rows_mean")
    if fullest is None or not mean:
        return None
    return fullest / mean
