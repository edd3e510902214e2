#!/usr/bin/env python
"""Run the full queued benchmark battery and write one JSON report.

Every measurement the roadmap queues runs with ONE command, on a machine
with a chip:

    python tools/bench_sweep.py                 # full battery
    python tools/bench_sweep.py --only serve    # name-substring filter
    python tools/bench_sweep.py --dry-run       # print commands only

Each arm is `bench.py` in a subprocess; failures are recorded and the
sweep continues (a bench.py that finds no TPU exits non-zero, and the
sweep stops: every further arm would fail the same way). Results land in BENCH_SWEEP.json: {name: {cmd, rc, parsed,
seconds}} — parsed is bench.py's JSON line when one was emitted.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The ROADMAP battery. Names are stable keys for --only and the report.
ARMS: list[tuple[str, list[str]]] = [
    ("resnet50_baseline", []),
    ("resnet50_s2d_stem", ["--stem", "space_to_depth"]),
    ("vit_b16", ["--model", "vit_b16"]),
    # ViT batch-scaling probe (MFU chase, VERDICT r2 weak #2): at seq 197
    # the attention backends are equivalent (chunked tiles start at 256 —
    # a chunked "A/B" would measure dense vs dense), so the lever to probe
    # is per-chip batch: 742 img/s at bs128 leaves the MXU underfed if
    # step time is launch/HBM-bound rather than FLOPs-bound.
    ("vit_b16_bs256", ["--model", "vit_b16", "--batch-per-chip", "256"]),
    ("bert_base_mlm", ["--model", "bert_base"]),
    ("llama_train_best", ["--model", "llama", "--fused-head",
                          "--optimizer", "adafactor"]),
    ("llama_quant_training_int8", ["--model", "llama",
                                   "--quant-training", "int8"]),
    ("t5_train", ["--model", "t5"]),
    ("llama_decode", ["--model", "llama", "--decode-tokens", "64"]),
    ("llama_decode_int8", ["--model", "llama", "--decode-tokens", "64",
                           "--quantize", "int8"]),
    ("llama_decode_int4", ["--model", "llama", "--decode-tokens", "64",
                           "--quantize", "int4"]),
    ("llama_decode_fp8kv", ["--model", "llama", "--decode-tokens", "64",
                            "--kv-cache-dtype", "float8_e4m3fn"]),
    ("llama_spec_floor", ["--model", "llama", "--speculative", "4"]),
    ("llama_spec_ceiling", ["--model", "llama", "--speculative", "4",
                            "--spec-self"]),
    ("llama_spec_plookup", ["--model", "llama", "--speculative", "4",
                            "--prompt-lookup", "3"]),
    ("llama_spec_plookup_periodic", ["--model", "llama", "--speculative",
                                     "4", "--prompt-lookup", "3",
                                     "--plookup-periodic"]),
    ("serve_mixed", ["--model", "llama", "--serve", "64"]),
    ("serve_mixed_spec", ["--model", "llama", "--serve", "64",
                          "--serve-spec", "4"]),
    ("serve_mixed_paged", ["--model", "llama", "--serve", "64",
                           "--serve-paged", "128"]),
    ("serve_chat_sessions", ["--model", "llama", "--serve", "32",
                             "--serve-turns", "4"]),
    ("serve_chat_resend", ["--model", "llama", "--serve", "32",
                           "--serve-turns", "4", "--serve-resend"]),
    ("serve_prefix_fork", ["--model", "llama", "--serve", "32",
                           "--serve-prefix", "1024"]),
    ("serve_prefix_resend", ["--model", "llama", "--serve", "32",
                             "--serve-prefix", "1024", "--serve-resend"]),
    ("host_pipeline_decode_native", ["--model", "pipeline",
                                     "--pipeline-decode",
                                     "--decoder", "native"]),
    # C17 multiprocess-loader arms (grain): first measured 2026-07-31 on
    # the 1-core sandbox (in-process mode); on real multi-core TPU hosts
    # these record the process-worker numbers the torch comparison wants.
    ("host_pipeline_decode_grain_native", ["--model", "pipeline",
                                           "--pipeline-decode",
                                           "--loader", "grain",
                                           "--decoder", "native"]),
    ("host_pipeline_decode_grain_pil", ["--model", "pipeline",
                                        "--pipeline-decode",
                                        "--loader", "grain",
                                        "--decoder", "pil"]),
]

# Arms that are NOT bench.py invocations. The sustained drill (VERDICT r2
# #5 / BASELINE.json:8) runs the real trainer on a synthesized multi-GB
# tar set for wall-clock minutes — so it runs only after every quick arm
# passed.
EXTRA_ARMS: list[tuple[str, list[str]]] = [
    ("sustained_resnet50_10min",
     [sys.executable, os.path.join(REPO, "tools", "sustained_drill.py"),
      "--minutes", "10"]),
    # VERDICT r3 #6: execute 7B per-layer geometry at 2 depths; slope
    # replaces MEMFIT_7B.md's extrapolated temps with measured ones.
    ("llama7b_geometry_step",
     [sys.executable, os.path.join(REPO, "tools", "probe_7b_step.py")]),
    # VERDICT r3 #3: profiler-backed limiter breakdown for the weakest
    # MFU rows — XPlane per-class % + top ops on the default shapes.
    ("resnet50_profile_toptops",
     [sys.executable, os.path.join(REPO, "tools", "profile_toptops.py"),
      "--model", "resnet50"]),
    ("vit_b16_profile_toptops",
     [sys.executable, os.path.join(REPO, "tools", "profile_toptops.py"),
      "--model", "vit_b16"]),
]


def run_arm(name: str, extra: list[str], timeout_s: int,
            tiny: bool) -> dict:
    cmd = [sys.executable, os.path.join(REPO, "bench.py"), *extra]
    if tiny:
        cmd.append("--tiny")
    return run_cmd(cmd, timeout_s)


def run_cmd(cmd: list[str], timeout_s: int) -> dict:
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout_s, cwd=REPO)
        rc, out = proc.returncode, proc.stdout
        tail = (proc.stderr or "")[-800:]
    except subprocess.TimeoutExpired as e:
        rc, out = 124, (e.stdout or "")
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        tail = "sweep-level timeout"
    parsed = None
    for line in reversed((out or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    return {"cmd": " ".join(cmd), "rc": rc, "parsed": parsed,
            "seconds": round(time.time() - t0, 1),
            **({} if rc == 0 else {"stderr_tail": tail})}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--only", default="",
                   help="run arms whose name contains this substring")
    p.add_argument("--timeout", type=int, default=1200,
                   help="per-arm wall clock budget (seconds)")
    p.add_argument("--tiny", action="store_true",
                   help="CI smoke: pass --tiny to the arms that take it "
                        "(numbers are NOT comparable to real runs)")
    p.add_argument("--out", default=os.path.join(REPO, "BENCH_SWEEP.json"))
    p.add_argument("--dry-run", action="store_true")
    args = p.parse_args(argv)

    arms = [(n, a) for n, a in ARMS if args.only in n]
    extra_arms = [] if args.tiny else [
        (n, c) for n, c in EXTRA_ARMS if args.only in n]
    if args.tiny:
        # --tiny exists on the llama decode/spec/serve benches only
        arms = [(n, a) for n, a in arms
                if any(k in n for k in ("decode", "spec", "serve"))
                and "host" not in n]
    if not arms and not extra_arms:
        print(f"no arms match --only {args.only!r}", file=sys.stderr)
        return 2
    if args.dry_run:
        for name, extra in arms:
            print(f"{name}: python bench.py {' '.join(extra)}"
                  f"{' --tiny' if args.tiny else ''}")
        for name, cmd in extra_arms:
            print(f"{name}: {' '.join(cmd[1:] if cmd[0] == sys.executable else cmd)}")
        return 0

    report: dict[str, dict] = {}

    def record(name: str, r: dict) -> None:
        report[name] = r
        status = (r["parsed"]["metric"] + "=" + str(r["parsed"]["value"])
                  if r["parsed"] and r["parsed"].get("metric")
                  else f"rc={r['rc']}")
        print(f"    {status} ({r['seconds']}s)", flush=True)
        with open(args.out, "w") as f:  # persist incrementally
            json.dump(report, f, indent=1)

    for i, (name, extra) in enumerate(arms, 1):
        print(f"[{i}/{len(arms)}] {name} ...", flush=True)
        record(name, run_arm(name, extra, args.timeout, args.tiny))
        r = report[name]
        if r["rc"] == 124 or "bench.py: no TPU" in r.get("stderr_tail", ""):
            print("no TPU (or arm hang) — aborting the sweep (every "
                  "further arm would fail the same way)", file=sys.stderr)
            return 3
    # Non-bench arms (sustained drill): long-horizon — run only when every
    # quick arm passed (a sweep with failures shouldn't burn 10+ minutes
    # of chip time on the drill); --only can still target them directly.
    quick_ok = all(r["rc"] == 0 for r in report.values())
    if extra_arms and (quick_ok or not arms):
        for name, cmd in extra_arms:
            print(f"[extra] {name} ...", flush=True)
            record(name, run_cmd(cmd, timeout_s=max(args.timeout, 2400)))
    elif extra_arms:
        print("skipping extra arms (quick arms had failures)",
              file=sys.stderr)
    ok = sum(1 for r in report.values() if r["rc"] == 0)
    print(f"done: {ok}/{len(report)} arms ok → {args.out}")
    return 0 if ok == len(report) else 1


if __name__ == "__main__":
    sys.exit(main())
