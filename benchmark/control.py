"""The control of ``correct``: the plain reference put in the program's place
and computed in the precision below the one the configuration states (fp8 for
bfloat16), compared with the float32 reference by the very numbers and limits
a run uses. It has to come out NOT correct.

    python benchmark/control.py --workload <cell> --seeds 11 12 13

Runs at the cell's own sizes on the chip (readings in PERF.md), and at the
rehearsal's sizes under an explicit JAX_PLATFORMS=cpu, where
tests/benchmark keeps it. The benchmark's own runs never call it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import run  # noqa: E402

PRECISION = "fp8"  # the nearest below the configurations' bfloat16


def control(config: dict, cell: dict, seeds: list[int]) -> list:
    """One entry a seed: the compared numbers of the lower-precision
    reference against the float32 one, and whether it would pass.
    ``config`` and ``cell`` are the loaded configuration and traffic files."""
    refmod = run.load_module(os.path.join(HERE, "references",
                                          config["reference"] + ".py"))
    ref = refmod.Reference(config, rehearsal=run.is_rehearsal())
    out = []
    for seed in seeds:
        seed = run.fold_seed(seed)
        batches = ref.make_batches(seed, cell, ref.check_steps)
        sound = ref.follow(seed, batches)
        low = ref.follow(seed, batches, PRECISION)
        numbers = refmod.compare(sound, low)
        out.append({"seed": seed, "precision": PRECISION, "numbers": numbers,
                    "correct": all(n["value"] <= n["limit"] for n in numbers
                                   if n["limit"] is not None)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    _, _, config, cell = run.load_cell(args.workload)
    results = control(config, cell, args.seeds)
    for r in results:
        print(json.dumps(r), flush=True)
    # the control passes its own test when every seed fails the comparison
    return 0 if not any(r["correct"] for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
