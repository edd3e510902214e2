"""Per-layer metric `moe_router_load_imbalance.tokens`: tokens on the
fullest router output over tokens on the mean one, over ALL the router's
outputs (each a mean over the expert layers), the newest value the trainer
logged: the number the selection bias's balancing update exists to bring
down, where `moe_expert_imbalance.tokens` sees the held experts alone. The
step reports both as metrics (`moe_load_fullest`, `moe_load_mean`) where the
bias has a rate, and the trainer's log mirrors them into the program's
registry; a program without them (or without the registry) gives None."""


def read(ctx):
    try:
        from pytorch_distributed_train_tpu.obs.registry import get_registry
    except ImportError:
        return None
    registry = get_registry()
    fullest = registry.get_value("train_moe_load_fullest")
    mean = registry.get_value("train_moe_load_mean")
    if fullest is None or not mean:
        return None
    return fullest / mean
