"""Speculative-serving host-loop soak: is proposal time flat in context?

The round-4 review flagged the prompt-lookup proposal path as a
potential host-side bottleneck: the original implementation rescanned
each row's full history every round (O(context) Python per row per
step), invisible in any stat. Round 5 replaced it with a per-row
incremental n-gram index (serving._ngram_build/_append/_propose,
O(1) per committed token) and exposed host_ms/device_ms in
ContinuousBatcher.stats.

This soak measures BOTH implementations' per-round proposal cost at
growing context lengths (slots x contexts of 512..8k tokens, the
shapes a 4k-context serving host actually sees) and prints one JSON
line. Pass/fail intuition: rescan cost grows ~linearly with context;
index cost must stay flat (sublinear) — the row's verdict field says
whether it did. Pure host benchmark: no device, no model, runs
anywhere in milliseconds.

Usage: python tools/spec_soak.py [--slots 16] [--k 4] [--ngram 3]
"""

import argparse
import json
import statistics
import sys
import time

sys.path.insert(0, ".")


def _mk_ctx(n: int, seed: int) -> list[int]:
    # zipf-ish token stream with enough repetition for real matches —
    # the regime prompt lookup exists for
    import random

    r = random.Random(seed)
    ctx: list[int] = []
    while len(ctx) < n:
        if ctx and r.random() < 0.4:  # echo an earlier span
            start = r.randrange(len(ctx))
            ctx.extend(ctx[start:start + r.randrange(2, 8)])
        else:
            ctx.append(r.randrange(256))
    return ctx[:n]


def _median_us_per_row(rounds: int, slots: int, one_round) -> float:
    """Each round timed on its own and the MEDIAN round taken: on a busy
    host a preemption lengthens one round of a few dozen microseconds
    many times over, and a mean over the window reads it as scaling."""
    times = []
    for i in range(rounds):
        t0 = time.perf_counter()
        one_round(i)
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e6 / slots


def main(argv=None) -> int:
    from pytorch_distributed_train_tpu.serving import (
        _ngram_append,
        _ngram_build,
        _ngram_propose,
    )
    from pytorch_distributed_train_tpu.speculative import (
        propose_from_context,
    )

    p = argparse.ArgumentParser()
    p.add_argument("--slots", type=int, default=16)
    p.add_argument("--k", type=int, default=4)
    p.add_argument("--ngram", type=int, default=3)
    p.add_argument("--rounds", type=int, default=200)
    args = p.parse_args(argv)

    lengths = [512, 1024, 2048, 4096, 8192]
    rows = []
    for n in lengths:
        ctxs = [_mk_ctx(n, s) for s in range(args.slots)]
        idxs = [_ngram_build(c, args.ngram) for c in ctxs]

        def propose(_):
            for c, ix in zip(ctxs, idxs):
                _ngram_propose(c, ix, args.ngram, args.k)

        # amortized index maintenance: one commit per row per round
        def append(i):
            for c, ix in zip(ctxs, idxs):
                _ngram_append(c, ix, i % 256, args.ngram)

        def rescan(_):
            for c in ctxs:
                propose_from_context(c, args.k, args.ngram)

        idx_us = _median_us_per_row(args.rounds, args.slots, propose)
        app_us = _median_us_per_row(args.rounds, args.slots, append)
        # rescan is slow; sample
        scan_us = _median_us_per_row(max(1, args.rounds // 10), args.slots,
                                     rescan)
        rows.append({"context": n, "index_us_per_row": round(idx_us, 2),
                     "append_us_per_row": round(app_us, 2),
                     "rescan_us_per_row": round(scan_us, 2)})

    # verdict: index cost at 8k vs 512 must not scale with context
    # (allow 3x noise headroom; the rescan typically scales ~16x)
    idx_ratio = rows[-1]["index_us_per_row"] / max(
        rows[0]["index_us_per_row"], 1e-9)
    scan_ratio = rows[-1]["rescan_us_per_row"] / max(
        rows[0]["rescan_us_per_row"], 1e-9)
    out = {
        "tool": "spec_soak",
        "slots": args.slots, "k": args.k, "ngram": args.ngram,
        "rows": rows,
        "index_8k_over_512": round(idx_ratio, 2),
        "rescan_8k_over_512": round(scan_ratio, 2),
        "index_sublinear": idx_ratio < 3.0,
    }
    print(json.dumps(out))
    return 0 if out["index_sublinear"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
