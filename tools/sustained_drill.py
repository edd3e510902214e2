#!/usr/bin/env python
"""Sustained real-data training drill (VERDICT r2 #5; BASELINE.json:8).

Configs 2-5's acceptance is SUSTAINED throughput, not 4-step smokes: the
feed-ratio math (several host cores per v5e chip even with native
decode; not measured on today's stack) predicts input-bound risk that only a long run exposes. This tool:

1. synthesizes a multi-GB WebDataset-style `imagenet_tar` set (photo-like
   JPEG entropy, 256-512 px, q85 — same generator as bench.py's decode
   arm) sized so the run cannot fit in page cache warm-up alone;
2. runs ResNet-50 training on it through the REAL trainer (native decode,
   HBM prefetch, the full step path) for ``--minutes`` of wall clock;
3. reports steady-state images/sec/chip and input_stall_pct (the
   trainer's per-log-window stall metric, data/pipeline.py::StallStats),
   acceptance: stall < 5%.

Run on the TPU:   python tools/sustained_drill.py --minutes 10
Host-only rehearsal (no chip): add --cpu (numbers are NOT comparable,
it validates the machinery).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _shard_ok(path: str, n: int) -> bool:
    """A reusable shard must hold exactly ``n`` images — a leftover from a
    smaller --images run would silently shrink the dataset below what the
    throughput accounting (and the larger-than-page-cache premise) assume."""
    import tarfile

    try:
        with tarfile.open(path) as tf:  # header scan only
            return sum(1 for x in tf.getnames() if x.endswith(".jpg")) == n
    except Exception:
        return False


def _write_shard(path: str, n: int, rng, start_key: int = 0) -> None:
    """One shard via the shared writer (atomic via rename; resumable only
    when the existing shard's size checks out)."""
    from pytorch_distributed_train_tpu.data.datasets import (
        write_jpeg_tar_shard,
    )

    if os.path.exists(path):
        if _shard_ok(path, n):
            return
        os.remove(path)  # stale partial/mis-sized shard from another run
    tmp = path + ".tmp"
    write_jpeg_tar_shard(tmp, n, rng, start_key=start_key)
    os.rename(tmp, path)


def synthesize_shards(root: str, n_images: int, shard_size: int = 2048,
                      seed: int = 0) -> None:
    import numpy as np

    os.makedirs(root, exist_ok=True)
    t0 = time.time()
    # One small val shard so epoch-boundary evals have data to read.
    _write_shard(os.path.join(root, "drill-val-000000.tar"),
                 512, np.random.default_rng(seed + 1))
    written = 0
    shard_i = 0
    while written < n_images:
        path = os.path.join(root, f"drill-train-{shard_i:06d}.tar")
        n = min(shard_size, n_images - written)
        _write_shard(path, n, np.random.default_rng((seed, shard_i)),
                     start_key=written)
        written += n
        shard_i += 1
        print(f"[drill] shard {shard_i} ready ({written}/{n_images} imgs, "
              f"{time.time() - t0:.0f}s)", flush=True)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--minutes", type=float, default=10.0)
    p.add_argument("--images", type=int, default=100_000,
                   help="synthetic dataset size (~0.5-1 GB per 20k imgs)")
    p.add_argument("--data-root", default="/tmp/drill_tar")
    p.add_argument("--batch-per-chip", type=int, default=128)
    p.add_argument("--cpu", action="store_true",
                   help="host-only rehearsal on the CPU backend")
    p.add_argument("--image-size", type=int, default=224,
                   help="train resolution (drop for CPU rehearsals — "
                        "full-shape ResNet-50 steps take minutes/core)")
    p.add_argument("--log-every", type=int, default=20,
                   help="steps per metric window (small for rehearsals "
                        "so short runs still capture windows)")
    p.add_argument("--log", default="/tmp/drill_metrics.jsonl")
    args = p.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    synthesize_shards(args.data_root, args.images)

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    n_chips = jax.device_count()

    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = get_preset("resnet50_imagenet")
    cfg.data.dataset = "imagenet_tar"
    cfg.data.data_dir = os.path.join(args.data_root, "drill-{split}-*.tar")
    cfg.data.native_decode = True
    cfg.data.batch_size = args.batch_per_chip * n_chips
    cfg.data.randaugment_num_ops = 0  # jpeg-only shards, native-decode path
    cfg.model.image_size = args.image_size
    cfg.obs.log_every_steps = args.log_every
    cfg.obs.jsonl_path = args.log
    cfg.checkpoint.dir = "/tmp/drill_ckpt"
    cfg.checkpoint.save_every_steps = 10_000_000  # not under test here
    cfg.eval_every_steps = 0  # epoch-boundary evals only (tiny val shard)
    # Enough steps that wall-clock, not the step budget, ends the run.
    cfg.epochs = 0
    cfg.total_steps = 10_000_000

    if os.path.exists(args.log):
        os.remove(args.log)

    t = Trainer(cfg)

    orig_tick = t.meter.tick
    state = {"deadline": None}

    def tick_with_deadline():
        # Clock starts at the FIRST step (post-compile): the drill
        # measures sustained stepping, and compile time would otherwise
        # swallow short rehearsal budgets entirely.
        now = time.monotonic()
        if state["deadline"] is None:
            state["deadline"] = now + args.minutes * 60.0
        elif now >= state["deadline"]:
            raise KeyboardInterrupt  # unwind like a user stop; ckpt saves
        return orig_tick()

    t.meter.tick = tick_with_deadline
    t0 = time.time()
    try:
        t.fit()
    except KeyboardInterrupt:
        pass
    wall = time.time() - t0

    # Steady state: drop the first quarter of log windows (compile + cache
    # warm-up), report the rest.
    rows = []
    with open(args.log) as f:
        for line in f:
            r = json.loads(line)
            if r.get("tag") == "train":
                rows.append(r)
    tail = rows[len(rows) // 4:]
    if not tail:
        raise SystemExit("no steady-state windows captured — run longer")
    ips = [r["images_per_sec_per_chip"] for r in tail
           if "images_per_sec_per_chip" in r]
    stalls = [r["input_stall_pct"] for r in tail if "input_stall_pct" in r]
    result = {
        "metric": "sustained_resnet50_images_per_sec_per_chip",
        "value": round(sum(ips) / max(len(ips), 1), 1),
        "unit": "images/sec/chip (sustained)",
        "wall_minutes": round(wall / 60.0, 1),
        "windows": len(tail),
        "input_stall_pct_mean": round(sum(stalls) / max(len(stalls), 1), 2),
        "input_stall_pct_max": round(max(stalls), 2) if stalls else None,
        "stall_acceptance_lt_5pct":
            bool(stalls) and max(stalls) < 5.0,
        "n_chips": n_chips,
        "backend": "cpu" if args.cpu else "tpu",
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
