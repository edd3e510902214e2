#!/usr/bin/env python
"""Print the resolved parameter sharding table for a config — which mesh
axes shard every param, the per-device shard shape, and per-device memory.

The operator-facing answer to "what will FSDP/TP actually do to this
model before I burn pod time on it" (torch analogue: printing the FSDP
wrapping plan / DTensor placements). Runs anywhere: uses eval_shape (no
weights are materialized) on a virtual device mesh.

    python tools/show_sharding.py --config llama2_7b --devices 16 \
        --set mesh.fsdp=8 --set mesh.tensor=2
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--devices", type=int, default=8,
                   help="virtual device count for the mesh")
    p.add_argument("--set", action="append", default=[], metavar="K=V")
    p.add_argument("--top", type=int, default=0,
                   help="show only the N largest params (0 = all)")
    args = p.parse_args()

    # CPU-only, like tests/conftest.py: jax may have been imported
    # already, so set BOTH the env and the live jax config.
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={args.devices}"
    ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from flax import traverse_util

    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
    from pytorch_distributed_train_tpu.parallel.partition import (
        rules_for_model, validate_spec,
    )

    cfg = get_preset(args.config)
    cfg.apply_overrides(args.set)

    mesh = build_mesh(cfg.mesh)
    model = build_model(cfg.model, cfg.precision, mesh=mesh, mesh_cfg=cfg.mesh)
    rules = rules_for_model(cfg.model.name)

    from pytorch_distributed_train_tpu.steps import dummy_inputs

    def init(rng):
        # The same loss-keyed input dispatch the Trainer uses — covers
        # vision, LM, MLM, and seq2seq (t5) signatures.
        return model.init({"params": rng},
                          *dummy_inputs(cfg.loss, cfg.model, cfg.data),
                          train=False)

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))["params"]
    flat = traverse_util.flatten_dict(shapes)

    axes = {k: v for k, v in mesh.shape.items() if v > 1}
    print(f"config={args.config} devices={args.devices} mesh={axes or '{}'}")
    print(f"{'param':58s} {'shape':>20s} {'spec':>24s} {'shard/dev':>20s} "
          f"{'MB/dev':>8s}")

    rows = []
    for key, leaf in flat.items():
        name = "/".join(map(str, key))
        # same resolution the trainer uses: rule lookup, then divisibility
        # fallback (indivisible dims replicate — partition.py validate_spec)
        spec = validate_spec(rules.spec_for(name, leaf.shape), leaf.shape,
                             mesh)
        shard = list(leaf.shape)
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            names = axis if isinstance(axis, tuple) else (axis,)
            factor = int(np.prod([mesh.shape[a] for a in names]))
            shard[dim] //= factor
        itemsize = leaf.dtype.itemsize
        mb = np.prod(shard) * itemsize / 2**20
        rows.append((mb, name, leaf.shape, spec, tuple(shard), itemsize))

    rows.sort(key=lambda r: r[0], reverse=True)  # stable: ties keep layer order
    shown = rows[: args.top] if args.top else rows
    for mb, name, shape, spec, shard, _ in shown:
        print(f"{name:58s} {str(tuple(shape)):>20s} {str(tuple(spec)):>24s} "
              f"{str(shard):>20s} {mb:8.2f}")
    total = sum(r[0] for r in rows)
    full = sum(np.prod(r[2]) * r[5] / 2**20 for r in rows)
    print(f"-- params: {full:.0f} MB unsharded -> {total:.0f} MB/device "
          f"({len(rows)} tensors; optimizer state shards identically)")


if __name__ == "__main__":
    main()
