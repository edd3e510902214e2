"""Profile the grain-vs-threads loader gap (VERDICT r4 weak #4).

At native JPEG decode the grain loader ran about a quarter slower per
core than the threads loader (not measured on today's stack), root-caused
only as "grain machinery overhead". This tool reproduces both arms on the same
synthetic tar shard and cProfiles the GRAIN run so the overhead has
names: per-record time in grain's iterator machinery, the batch-of-1
dict repack in the load transform, rng construction, and the final
np.asarray copies are separately attributable. Prints one JSON line
with both throughputs and the top grain-side cost centers.

Run: python tools/grain_profile.py [--n 1024] [--batch 128] [--image-size 224]
"""

from __future__ import annotations

import argparse
import cProfile
import io
import json
import os
import pstats
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _run_epoch(loader) -> tuple[int, float]:
    it = loader.epoch(0)
    if next(it, None) is None:  # warm
        raise SystemExit(
            "epoch yielded zero batches — shrink --batch or raise --n")
    t0 = time.perf_counter()
    seen = 0
    for b in it:
        seen += len(b["label"])
    return seen, time.perf_counter() - t0


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=1024)
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--decoder", default="native",
                   choices=["native", "pil"])
    args = p.parse_args(argv)

    import numpy as np

    from pytorch_distributed_train_tpu.config import DataConfig
    from pytorch_distributed_train_tpu.data.datasets import (
        TarShardImageDataset,
        write_jpeg_tar_shard,
    )
    from pytorch_distributed_train_tpu.data.grain_pipeline import (
        GrainHostDataLoader,
    )
    from pytorch_distributed_train_tpu.data.pipeline import HostDataLoader

    tmp = tempfile.mkdtemp(prefix="grain-profile-")
    try:
        shard = os.path.join(tmp, "p-000000.tar")
        write_jpeg_tar_shard(shard, args.n, np.random.default_rng(0))
        ds = TarShardImageDataset(
            shard, args.image_size, train=True,
            native_decode=args.decoder == "native")
        cfg = DataConfig(batch_size=args.batch, num_workers=1)

        threads = HostDataLoader(ds, cfg, train=True, num_hosts=1,
                                 host_id=0)
        seen_t, wall_t = _run_epoch(threads)
        if seen_t == 0:
            raise SystemExit(
                f"--n {args.n} / --batch {args.batch} leaves nothing "
                "after the warm-up batch — need at least 2 batches per "
                "epoch")

        grain = GrainHostDataLoader(ds, cfg, train=True, num_hosts=1,
                                    host_id=0)
        # Throughput epoch runs UNPROFILED — cProfile adds per-call
        # overhead that would inflate exactly the gap this tool
        # quantifies; a second, profiled epoch supplies the cost
        # centers only.
        seen_g, wall_g = _run_epoch(grain)
        prof = cProfile.Profile()
        prof.enable()
        _run_epoch(grain)
        prof.disable()

        s = io.StringIO()
        stats = pstats.Stats(prof, stream=s).sort_stats("cumulative")
        stats.print_stats(30)
        report = s.getvalue()
        # keep the machine-readable top rows: drop pure-wait frames
        # (queue.get / threading waits / time.sleep — consumer
        # blocking is not grain overhead, and misattributing it would
        # recreate the exact confusion this tool resolves)
        WAIT = ("queue.py", "threading.py", "selectors.py",
                "{built-in method time.sleep}", "_wait")
        tops = []
        for line in report.splitlines():
            if "/" in line and "{" not in line and "pstats" not in line:
                if any(w in line for w in WAIT):
                    continue
                parts = line.split()
                if len(parts) >= 6 and parts[0][0].isdigit():
                    tops.append({"ncalls": parts[0],
                                 "cumtime_s": parts[3],
                                 "where": parts[5][-120:]})
            if len(tops) >= 14:
                break
        out = {
            "tool": "grain_profile",
            "decoder": args.decoder,
            "threads_img_s": round(seen_t / wall_t, 1),
            "grain_img_s": round(seen_g / wall_g, 1),
            "gap_pct": round(100 * (1 - (seen_g / wall_g)
                                    / (seen_t / wall_t)), 1),
            "grain_top_cost_centers": tops,
        }
        print(json.dumps(out))
        with open("/tmp/grain_profile_full.txt", "w") as f:
            f.write(report)
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
