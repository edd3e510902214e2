#!/usr/bin/env python
"""A/B perf experiments for the ResNet-50 north-star (run on a real chip).

Each experiment toggles ONE hypothesis against the current default and
prints a JSON line per arm. Run when the device is healthy:

    python tools/perf_experiments.py --steps 20

Arms:
  baseline     — current defaults (bf16 compute, fp32 BN stats, fp32 input)
  bf16_input   — feed images as bf16 from the host (halves input H2D/read)
  bf16_bnstats — BN statistics reductions in bf16
                 (force_float32_reductions=False; MLPerf-era ResNets did
                 this — validate loss parity before adopting)
  s2d_stem     — space-to-depth stem rewrite (exact; MXU-friendly C_in 12)

Keep arms additive and honest: any adopted change must land in the model
code with its measured delta recorded in PERF.md.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def run_arm(name: str, *, steps: int, warmup: int, bn_fp32_stats: bool,
            input_dtype: str, stem: str = "conv", image_size: int = 224,
            bs: int = 128) -> dict:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.config import (
        MeshConfig, ModelConfig, OptimConfig, PrecisionConfig,
    )
    from pytorch_distributed_train_tpu.losses import get_loss_fn
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.optim import make_optimizer
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
    from pytorch_distributed_train_tpu.parallel.partition import rules_for_model
    from pytorch_distributed_train_tpu.train_state import TrainState

    mesh = build_mesh(MeshConfig(data=-1))
    model = build_model(ModelConfig(name="resnet50", num_classes=1000,
                                    image_size=image_size, stem=stem),
                        PrecisionConfig(compute_dtype="bfloat16"))
    tx, _ = make_optimizer(OptimConfig(name="momentum", learning_rate=0.1,
                                       schedule="constant", warmup_steps=0),
                           total_steps=1000)
    rules = rules_for_model("resnet50")

    orig_bn = nn.BatchNorm
    if not bn_fp32_stats:
        # Swap in a subclass with the default flipped. A plain class-attr
        # assignment would be a silent no-op: flax Modules are dataclasses,
        # so the default is baked into the generated __init__. resnet.py
        # resolves `nn.BatchNorm` at call time through the module attr, so
        # the swap takes effect for models built inside this arm.
        class _BF16StatsBN(nn.BatchNorm):
            force_float32_reductions: bool = False

        nn.BatchNorm = _BF16StatsBN
    try:
        def init_state(rng):
            variables = model.init({"params": rng},
                                   jnp.zeros((2, image_size, image_size, 3)),
                                   train=False)
            return TrainState.create(params=variables["params"], tx=tx,
                                     batch_stats=variables["batch_stats"])

        rng = jax.random.PRNGKey(0)
        shape = jax.eval_shape(init_state, rng)
        sharding = steps_lib.state_shardings(mesh, rules, shape)
        state = jax.jit(init_state, out_shardings=sharding)(rng)
        step = steps_lib.jit_train_step(
            steps_lib.make_train_step(model, get_loss_fn("softmax_xent"), tx),
            mesh, sharding)

        rng_np = np.random.default_rng(0)
        batch = {
            "image": jnp.asarray(
                rng_np.standard_normal((bs, image_size, image_size, 3)),
                                 jnp.dtype(input_dtype)),
            "label": jnp.asarray(rng_np.integers(0, 1000, bs), jnp.int32),
        }
        for _ in range(max(warmup, 1)):  # >=1: timing must exclude compile
            state, metrics = step(state, batch, rng)
        float(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, batch, rng)
        loss = float(metrics["loss"])
        wall = time.perf_counter() - t0
        return {"arm": name, "images_per_sec": round(bs * steps / wall, 1),
                "loss": round(loss, 4)}
    finally:
        nn.BatchNorm = orig_bn


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--arms", default="baseline,bf16_input,bf16_bnstats,s2d_stem")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--batch", type=int, default=128)
    args = p.parse_args()

    specs = {
        "baseline": dict(bn_fp32_stats=True, input_dtype="float32"),
        "bf16_input": dict(bn_fp32_stats=True, input_dtype="bfloat16"),
        "bf16_bnstats": dict(bn_fp32_stats=False, input_dtype="float32"),
        # exact 4x4/s1 rewrite of the 7x7/s2 stem over s2d input
        # (models/resnet.py SpaceToDepthStem)
        "s2d_stem": dict(bn_fp32_stats=True, input_dtype="float32",
                         stem="space_to_depth"),
    }
    for arm in args.arms.split(","):
        out = run_arm(arm, steps=args.steps, warmup=args.warmup,
                      image_size=args.image_size, bs=args.batch, **specs[arm])
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
