#!/usr/bin/env python
"""Perf-ledger CLI: history import, regression gate, kernel-gap audit.

    python -m tools.perf_ledger --show                  # tail the ledger
    python -m tools.perf_ledger --import                # BENCH_r*.json → rows
    python -m tools.perf_ledger --check                 # regression gate
    python -m tools.perf_ledger --audit                 # kernel-gap report

Thin CLI over ``pytorch_distributed_train_tpu.obs.perf.PerfLedger``
(docs/performance.md has the row schema and workflow). The ledger is an
append-only JSONL written by bench.py (every measured record) and
trainer summaries (one row per fit); ``--check`` is the CI gate: it
compares every metric's NEWEST row against the prior rows' median+MAD
(the sentinel SpikeDetector's statistics) and exits nonzero NAMING the
regressed metric, so a throughput/MFU regression fails loudly instead
of drifting into the history it will later be judged against.

Default ledger path: $PDTT_PERF_LEDGER, else <repo>/bench_ledger.jsonl.
Pure stdlib + the repo's obs package; no jax import — safe on a login
host.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pytorch_distributed_train_tpu.obs.perf import (  # noqa: E402
    AUDIT_PRESETS,
    PerfLedger,
    default_ledger_path,
    fusion_worklist,
    fusion_worklist_report,
    kernel_gap_report,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def show(ledger: PerfLedger, tail: int = 20) -> int:
    rows = ledger.load()
    if not rows:
        print(f"perf-ledger: no rows at {ledger.path}")
        return 0
    print(f"perf-ledger: {len(rows)} row(s) at {ledger.path} "
          f"(last {min(tail, len(rows))}):")
    for r in rows[-tail:]:
        mfu = (f" mfu={r['mfu_pct']}%"
               if isinstance(r.get("mfu_pct"), (int, float)) else "")
        src = f" [{r['source']}]" if r.get("source") else ""
        stall = ""
        if isinstance(r.get("stall_split"), dict) and r["stall_split"]:
            top = max(r["stall_split"], key=r["stall_split"].get)
            stall = f" stall_top={top}:{r['stall_split'][top]:.0%}"
        print(f"  {r['metric']:<48} {r['value']:>12} "
              f"{r.get('unit', ''):<18}{mfu}{stall}{src}")
    return 0


def check(ledger: PerfLedger, args) -> int:
    regs = ledger.check(min_rows=args.min_rows, sigma=args.sigma,
                        min_rel=args.min_rel,
                        metrics=args.metric or None)
    if not regs:
        n = len({r["metric"] for r in ledger.load()})
        print(f"perf-ledger: OK — no regression across {n} metric(s) "
              f"({ledger.path})")
        return 0
    for reg in regs:
        print(f"perf-ledger: REGRESSION {reg['metric']}.{reg['key']} = "
              f"{reg['value']} vs median {reg['median']} over "
              f"{reg['n_prior']} prior row(s)")
    return 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--path", default="",
                   help="ledger JSONL (default $PDTT_PERF_LEDGER or "
                        "<repo>/bench_ledger.jsonl)")
    p.add_argument("--show", action="store_true",
                   help="print the newest rows")
    p.add_argument("--tail", type=int, default=20)
    p.add_argument("--import", dest="do_import", action="store_true",
                   help="back-import BENCH_r*.json round records "
                        "(idempotent: already-imported files skip)")
    p.add_argument("--repo", default=_REPO,
                   help="repo root the import scans for BENCH_r*.json")
    p.add_argument("--check", action="store_true",
                   help="regression gate: newest row per metric vs the "
                        "prior median+MAD; exit 1 naming regressions")
    p.add_argument("--min-rows", type=int, default=4,
                   help="prior rows a metric needs before it is gated")
    p.add_argument("--sigma", type=float, default=4.0,
                   help="robust sigmas of deviation that count as a "
                        "regression")
    p.add_argument("--min-rel", type=float, default=0.05,
                   help="absolute deviation floor, relative to the "
                        "median (guards near-zero-MAD histories)")
    p.add_argument("--metric", action="append", default=[],
                   help="gate only these metrics (repeatable)")
    p.add_argument("--audit", action="store_true",
                   help="kernel-gap report: op classes ranked by "
                        "roofline gap per preset")
    p.add_argument("--suggest", action="store_true",
                   help="with --audit: render the gap ranking as an "
                        "actionable fusion worklist (top-N op-class "
                        "gaps per preset -> the repo lever that closes "
                        "them, with config digest + measuring capture)")
    p.add_argument("--top", type=int, default=3,
                   help="worklist entries per preset for --suggest")
    p.add_argument("--presets", default=",".join(AUDIT_PRESETS),
                   help="comma-separated preset prefixes for --audit")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output for --check/--suggest")
    args = p.parse_args(argv)

    ledger = PerfLedger(args.path or default_ledger_path(_REPO))
    did = False
    rc = 0
    if args.do_import:
        did = True
        n = ledger.import_bench_history(args.repo)
        print(f"perf-ledger: imported {n} BENCH round record(s) into "
              f"{ledger.path}")
    if args.check:
        did = True
        if args.json:
            regs = ledger.check(min_rows=args.min_rows, sigma=args.sigma,
                                min_rel=args.min_rel,
                                metrics=args.metric or None)
            json.dump({"regressions": regs, "path": ledger.path},
                      sys.stdout, indent=1)
            print()
            rc = max(rc, 1 if regs else 0)
        else:
            rc = max(rc, check(ledger, args))
    if args.audit or args.suggest:
        did = True
        presets = tuple(s for s in args.presets.split(",") if s)
        rows = ledger.load()
        if args.audit:
            print(kernel_gap_report(rows, presets=presets))
        if args.suggest:
            if args.json:
                json.dump({"worklist": fusion_worklist(
                    rows, presets=presets, top_n=args.top)},
                    sys.stdout, indent=1)
                print()
            else:
                print(fusion_worklist_report(rows, presets=presets,
                                             top_n=args.top))
    if args.show or not did:
        rc = max(rc, show(ledger, tail=args.tail))
    return rc


if __name__ == "__main__":
    sys.exit(main())
