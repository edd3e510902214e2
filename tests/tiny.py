"""What the suite's drills share: the one tiny trainer and the head of a
worker script.

A drill whose contract is a recovery path, a plane or a CLI (and not the
model that trains under it) builds ``TINY``: a two-layer ViT of width 32 on
8x8 synthetic images, which initialises and compiles in a third of the
time of the ResNet-18 the drills used to build (measured on the sandbox:
Trainer() + a six-step fit 7 s against 20 s). Tests about batch norm,
convolutions or a preset's own shapes keep their models.
"""

import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = (
    "model.name=vit_b16", "model.num_classes=10", "model.image_size=8",
    "model.patch_size=4", "model.hidden_size=32", "model.num_layers=2",
    "model.num_heads=4", "model.mlp_dim=64", "model.dropout_rate=0.0",
    "data.dataset=synthetic_images", "data.synthetic_size=256",
    "data.batch_size=16", "data.num_workers=1", "data.prefetch=2",
    "optim.name=momentum", "optim.learning_rate=0.05",
    "optim.schedule=constant", "optim.warmup_steps=0",
    "checkpoint.async_save=false", "obs.log_every_steps=1",
)


def tiny_cfg(*overrides: str):
    """A TrainConfig of ``TINY`` plus the test's own ``key=value`` pairs."""
    from pytorch_distributed_train_tpu.config import TrainConfig

    cfg = TrainConfig()
    cfg.apply_overrides([*TINY, *overrides])
    return cfg


def set_flags(*overrides: str) -> list[str]:
    """``TINY`` plus the test's pairs as the CLI's ``--set`` arguments."""
    return [a for pair in (*TINY, *overrides) for a in ("--set", pair)]


# The head of a worker script: the repo on the path, the CPU pinned before
# any backend exists, and ``cfg`` = tiny_cfg(). A drill appends its own
# lines (they may use rank/world/gen, the launcher's env contract).
WORKER_HEAD = f"""
import os, sys, time
sys.path.insert(0, {REPO!r})
sys.path.insert(0, {os.path.join(REPO, "tests")!r})
import jax
jax.config.update("jax_platforms", "cpu")
from tiny import tiny_cfg
from pytorch_distributed_train_tpu.trainer import Trainer

rank = int(os.environ.get("PROCESS_ID", 0))
world = int(os.environ.get("NUM_PROCESSES", 1))
gen = os.environ.get("RESTART_GENERATION", "0")
cfg = tiny_cfg()
"""

