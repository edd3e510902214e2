"""Performance attribution plane: MFU/roofline, stall stages, perf ledger.

The reliability planes (spans, journal, profiler, sentinel) answer
"what broke"; this module answers "where did the step go" — the
diagnostic instrument every ROADMAP item-2 optimisation is measured
with. Three instruments, one module:

1. **MFU / op-class attribution** — analytic model FLOPs
   (utils/flops.py, optionally cross-checked against jax AOT
   ``cost_analysis()`` via ``utils.flops.aot_fwd_flops_per_item``) over
   the chip's bf16 peak, and the achieved step decomposed into op
   classes (matmul / conv / attention / elementwise / collective /
   infeed — ``utils.xplane.classify_op_class``) from a profiler
   capture's top-ops. Exported as ``perf_mfu_pct`` /
   ``perf_opclass_ms{class=}`` registry gauges and one ``perf``
   journal record per capture (``attribute_capture``, called by the
   managed profiler at window close).

2. **Staged input-pipeline attribution** — the single ``input_stall``
   goodput bucket becomes a per-stage breakdown: datasets and loaders
   time their read / decode / augment work through ``stage(name)``
   and the device assembly path times host→device transfer (``h2d``),
   all accumulated in a process-global :class:`InputStageStats`
   mirrored into ``input_stage_seconds_total{stage=}``. A gap between
   the chip's rate and the host's is then "decode is most of the
   stall" (not measured on today's stack), not one opaque bucket.
   Stage clocks are
   ``time.monotonic()`` (the monotonic-clock pass stance: durations
   must not jump with NTP).

3. **Perf ledger** — an append-only JSONL of throughput/MFU/stall
   rows (:class:`PerfLedger`), written by bench.py and trainer
   summaries, back-importable from the BENCH_r*.json history, and
   gated by a median+MAD regression check that reuses
   ``sentinel.numeric.SpikeDetector`` — ``python -m tools.perf_ledger
   --check`` exits nonzero naming the regressed metric. The
   kernel-gap audit (``kernel_gap_report``) ranks op classes by
   roofline gap per preset from the same rows.

No jax at module scope (the obs/ package contract): data workers and
login-host tools import this without touching a device backend.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import re
import sys
import threading
import time

from pytorch_distributed_train_tpu.obs import events as events_lib
from pytorch_distributed_train_tpu.obs.registry import get_registry

# Closed stage vocabulary (docs/performance.md): read = storage bytes →
# host RAM (tar seeks, file opens, fancy-index gathers), decode = JPEG →
# pixels, augment = crop/flip/RandAugment/normalize, h2d = host batch →
# device HBM (make_array_from_process_local_data). Closed so dashboards
# can stack them and the ledger's stall split is comparable across runs.
STAGES = ("read", "decode", "augment", "h2d")

# Default filename of the program's own bench-history ledger at the repo
# root (trainer rows go to the run dir — docs/performance.md). Not
# PERF_LEDGER.jsonl: that name at the repo root is the driver's record
# and nothing here writes it.
LEDGER_BASENAME = "bench_ledger.jsonl"
ENV_LEDGER = "PDTT_PERF_LEDGER"


class InputStageStats:
    """Cumulative per-stage input-pipeline seconds.

    Same thread model as data/pipeline.py's StallStats: plain float
    adds under the GIL (decode pools and the producer thread write
    concurrently; a torn read costs a scrape one addend, never a
    crash). Every add also feeds ``input_stage_seconds_total{stage=}``
    so the live split is scrapable without the ledger.
    """

    def __init__(self) -> None:
        self.seconds: dict[str, float] = {s: 0.0 for s in STAGES}
        self.calls: dict[str, int] = {s: 0 for s in STAGES}
        self._counters = {
            s: get_registry().counter(
                "input_stage_seconds_total", labels={"stage": s},
                help="cumulative host input-pipeline seconds by stage "
                     "(read/decode/augment/h2d)")
            for s in STAGES
        }

    def add(self, stage_name: str, dt: float) -> None:
        if stage_name not in self.seconds:  # closed vocabulary
            raise KeyError(
                f"unknown input stage {stage_name!r} (stages: {STAGES})")
        self.seconds[stage_name] += dt
        self.calls[stage_name] += 1
        self._counters[stage_name].inc(dt)

    def merge(self, seconds: dict[str, float]) -> None:
        """Fold another process's stage deltas into this one — the
        shared-memory decode workers (data/workers.py) time their
        read/decode/augment stages process-locally and ship the
        per-batch delta with each result; merging here keeps the
        attribution (and the scrape counters) whole-pipeline even when
        the stages run in forked workers. Unknown stage keys are
        rejected the same way add() rejects them."""
        for name, dt in seconds.items():
            if dt > 0.0:
                self.add(name, dt)

    def snapshot(self) -> dict[str, float]:
        return {s: self.seconds[s] for s in STAGES}

    def split(self) -> dict[str, float]:
        """Normalized stage fractions (sum 1.0), or {} when nothing was
        timed — the ledger's ``stall_split`` field. The split answers
        "when the consumer stalls, which stage is it waiting on": the
        stages' cumulative time shares are the blame proxy (the stall
        itself is one queue.get; only the producer side is staged)."""
        return normalize_split(self.seconds)

    def top_stage(self) -> str | None:
        split = self.split()
        if not split:
            return None
        return max(split, key=split.get)

    def reset(self) -> None:
        for s in STAGES:
            self.seconds[s] = 0.0
            self.calls[s] = 0


def normalize_split(seconds: dict[str, float]) -> dict[str, float]:
    """{stage: seconds} → normalized fractions (sum 1.0), zero stages
    dropped; {} when nothing was timed."""
    total = sum(seconds.values())
    if total <= 0.0:
        return {}
    return {s: round(v / total, 4) for s, v in seconds.items() if v > 0.0}


_STATS: InputStageStats | None = None
_STATS_LOCK = threading.Lock()


def get_input_stats() -> InputStageStats:
    global _STATS
    if _STATS is None:
        with _STATS_LOCK:
            if _STATS is None:
                _STATS = InputStageStats()
    return _STATS


@contextlib.contextmanager
def stage(name: str):
    """``with stage("decode"): ...`` — time one pipeline-stage region
    into the process-global stats. Monotonic clock: stage durations are
    deadline-ish arithmetic inputs (stall splits, regression gates) and
    must not jump with the wall clock."""
    t0 = time.monotonic()
    try:
        yield
    finally:
        get_input_stats().add(name, time.monotonic() - t0)


def _reset_for_tests() -> None:
    global _STATS
    with _STATS_LOCK:
        _STATS = None


# ---------------------------------------------------------------------------
# MFU + op-class attribution
# ---------------------------------------------------------------------------


def record_mfu(mfu_pct: float) -> None:
    """Publish the latest achieved MFU %% as the ``perf_mfu_pct`` gauge
    (trainer log cadence, bench one-shots)."""
    get_registry().gauge(
        "perf_mfu_pct",
        help="latest achieved MFU % (analytic model FLOPs over the "
             "chip's bf16 peak)").set(mfu_pct)


def publish_opclass_split(split_ms: dict[str, float]) -> None:
    """Export one capture's op-class milliseconds as
    ``perf_opclass_ms{class=}`` gauges (closed class vocabulary —
    utils.xplane.PERF_OP_CLASSES — so the label set is bounded)."""
    for cls, ms in split_ms.items():
        get_registry().gauge(
            "perf_opclass_ms", labels={"class": cls},
            help="device milliseconds by op class in the last profiler "
                 "capture").set(ms)


def attribute_capture(logdir: str, step: int | None = None,
                      mfu_pct: float | None = None,
                      top: int = 5) -> dict | None:
    """Attribute one profiler capture: newest xplane dump under
    ``logdir`` → op-class split (ms) + top-ops head, exported as
    gauges and journaled as one ``perf`` record. Returns the
    attribution dict, or None when there is nothing to attribute (no
    dump, or the xplane proto is unavailable in this environment) —
    best-effort by contract: attribution must never fail a capture."""
    try:
        from pytorch_distributed_train_tpu.utils import xplane

        files = xplane.find_xplane_files(logdir)
        if not files:
            return None
        xs = xplane.load_xspace(files[0])
        planes = xplane.summarize_xspace(xs)
        if not planes:  # CPU-only trace (tests): take any plane
            planes = xplane.summarize_xspace(xs, device_only=False)
        if not planes:
            return None
        plane = planes[0]
        split_ms = xplane.opclass_split(plane["ops"])
    except Exception:
        return None
    out = {
        "plane": plane["plane"],
        "total_ms": round(plane["total_ms"], 3),
        "opclass_ms": {c: round(ms, 3) for c, ms in split_ms.items()},
        "top_ops": [(n, round(ms, 3)) for n, ms, _ in plane["ops"][:top]],
    }
    if mfu_pct is not None:
        out["mfu_pct"] = mfu_pct
    publish_opclass_split(split_ms)
    events_lib.emit("perf", "attribution", step=step, dir=logdir, **out)
    return out


# ---------------------------------------------------------------------------
# Perf ledger
# ---------------------------------------------------------------------------


def config_digest(obj) -> str:
    """Short stable digest of a config (dict/json string) — the ledger
    key that tells "same config, new code" rows from config changes."""
    if not isinstance(obj, str):
        obj = json.dumps(obj, sort_keys=True, default=repr)
    return hashlib.sha256(obj.encode()).hexdigest()[:12]


def default_ledger_path(repo_root: str | None = None) -> str:
    """PDTT_PERF_LEDGER env override, else <repo_root>/bench_ledger.jsonl
    (repo root = next to bench.py, two levels above this package)."""
    env = os.environ.get(ENV_LEDGER)
    if env:
        return env
    if repo_root is None:
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    return os.path.join(repo_root, LEDGER_BASENAME)


# The ledger keys the regression gate watches. Both are
# higher-is-better, so "spike AND below the median" is a regression.
GATED_KEYS = ("value", "mfu_pct")


class PerfLedger:
    """Append-only JSONL of performance rows.

    Row schema (one JSON object per line; absent keys simply not
    measured that round)::

        {ts, metric, value, unit, mfu_pct, goodput_pct, stall_split,
         opclass_ms, top_ops, config_digest, argv, source, platform}

    Append never rewrites history (the whole point is a trajectory the
    regression gate can trust); a read-only checkout degrades to the
    printed record.
    """

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()

    # ------------------------------------------------------------- write
    def append(self, metric: str, value: float, *, unit: str = "",
               source: str = "", config=None, **extra) -> dict:
        row = {"ts": time.time(), "metric": str(metric),
               "value": float(value)}
        if unit:
            row["unit"] = unit
        if source:
            row["source"] = source
        if config is not None:
            row["config_digest"] = config_digest(config)
        for k, v in extra.items():
            if v is not None:
                row[k] = v
        row.setdefault("argv", " ".join(sys.argv[1:]))
        line = json.dumps(row, default=repr)
        with self._lock:
            try:
                os.makedirs(os.path.dirname(self.path) or ".",
                            exist_ok=True)
                with open(self.path, "a") as f:
                    f.write(line + "\n")
            except OSError as e:
                print(f"[perf-ledger] append failed ({e}); row not "
                      "persisted", flush=True)
                return row
        get_registry().counter(
            "perf_ledger_rows_total",
            help="perf-ledger rows appended by this process").inc()
        return row

    def append_record(self, record: dict, source: str = "") -> dict | None:
        """Append a bench.py-style record (``{metric, value, unit,
        ...}``); rows without a measured metric are skipped."""
        if not record.get("metric") or record.get("value") is None:
            return None
        extra = {k: v for k, v in record.items()
                 if k not in ("metric", "value", "unit")}
        return self.append(record["metric"], record["value"],
                           unit=record.get("unit", ""), source=source,
                           **extra)

    # -------------------------------------------------------------- read
    def load(self) -> list[dict]:
        rows: list[dict] = []
        try:
            with open(self.path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        row = json.loads(line)
                    except ValueError:
                        continue  # torn tail line of a killed writer
                    if isinstance(row, dict) and row.get("metric"):
                        rows.append(row)
        except OSError:
            return []
        return rows

    # ------------------------------------------------------------- check
    def check(self, *, min_rows: int = 4, sigma: float = 4.0,
              min_rel: float = 0.05, metrics=None) -> list[dict]:
        """Median+MAD regression gate: for every metric with enough
        history, the NEWEST row's gated keys (throughput ``value``,
        ``mfu_pct``) are judged against the prior rows'
        median — a spike below the median is a regression (reusing the
        sentinel's robust detector so the statistics can't drift from
        the loss-spike plane's). Returns one dict per regression;
        journals each as an ``anomaly``/``perf_regression`` event (the
        timeline landmark)."""
        from pytorch_distributed_train_tpu.sentinel.numeric import (
            SpikeDetector,
        )

        # Grouped by (metric, config_digest): a deliberate config change
        # (different batch/shape under the same metric name) starts its
        # own trajectory instead of reading as a regression — the whole
        # reason rows carry the digest. Rows are ordered by their OWN
        # timestamps, not file position: --import back-fills history
        # with original (file-mtime) stamps, and an imported old round
        # must never be judged as "the newest measurement".
        by_group: dict[tuple, list[dict]] = {}
        for row in self.load():
            key = (row["metric"], row.get("config_digest", ""))
            by_group.setdefault(key, []).append(row)
        out: list[dict] = []
        for (metric, _digest), rows in sorted(by_group.items()):
            if metrics and metric not in metrics:
                continue
            rows.sort(key=lambda r: float(r.get("ts", 0.0)))
            for key in GATED_KEYS:
                if not isinstance(rows[-1].get(key), (int, float)):
                    # the newest row didn't measure this key (CPU run
                    # without mfu_pct): don't re-judge an OLDER row's
                    # value as if it were current
                    continue
                series = [float(r[key]) for r in rows
                          if isinstance(r.get(key), (int, float))]
                if len(series) < min_rows + 1:
                    continue
                prior, newest = series[:-1], series[-1]
                det = SpikeDetector(window=max(len(prior), 2),
                                    sigma=sigma,
                                    min_samples=min_rows,
                                    min_rel=min_rel)
                for v in prior:
                    det.add(v)
                med = sorted(prior)[len(prior) // 2]
                if det.is_spike(newest) and newest < med:
                    reg = {"metric": metric, "key": key,
                           "value": newest, "median": round(med, 4),
                           "n_prior": len(prior)}
                    out.append(reg)
                    get_registry().counter(
                        "perf_regressions_total",
                        help="perf-ledger regression-gate failures"
                    ).inc()
                    events_lib.emit("anomaly", "perf_regression", **reg)
        return out

    # ------------------------------------------------------------ import
    def import_bench_history(self, repo_root: str) -> int:
        """Back-import the BENCH_r*.json round records (driver format:
        ``{"parsed": {metric, value, ...}}``) as ledger rows, stamped
        with their source file and the FILE'S mtime as ``ts`` (not
        import time — the regression gate orders rows by ts, and an
        imported old round must sort into its historical place, never
        after live rows as "the newest measurement"); files already
        imported (a row with the same ``source``) are skipped, so the
        import is idempotent."""
        import glob

        have = {r.get("source") for r in self.load()}
        n = 0
        for path in sorted(glob.glob(os.path.join(repo_root,
                                                  "BENCH_r*.json"))):
            src = os.path.basename(path)
            if src in have:
                continue
            try:
                with open(path) as f:
                    rec = json.load(f)
                mtime = os.path.getmtime(path)
            except (OSError, ValueError):
                continue
            parsed = rec.get("parsed") if isinstance(rec, dict) else None
            # a round that measured nothing carries no metric: skipped
            if not isinstance(parsed, dict) or not parsed.get("metric"):
                continue
            row = self.append_record({**parsed, "ts": mtime}, source=src)
            if row is not None:
                n += 1
        return n


# ---------------------------------------------------------------------------
# Kernel-gap audit
# ---------------------------------------------------------------------------

# Op classes that do model FLOPs on the MXU; everything else in a step
# is overhead against the roofline (its whole share is gap).
COMPUTE_CLASSES = ("matmul", "conv", "attention")

# The ROADMAP item-2 presets the audit ranks by default.
AUDIT_PRESETS = ("resnet50", "bert_base", "vit_b16")


def kernel_gap(mfu_pct: float, opclass_ms: dict[str, float] | None
               ) -> list[tuple[str, float, float]]:
    """Rank op classes by roofline gap for one measured row.

    With ``achieved = mfu/100`` as the fraction of the step that was
    roofline-ideal work, the remaining ``1 - achieved`` is gap, split
    over classes: a non-compute class's entire time share is gap
    (collectives, infeed, elementwise glue do no model FLOPs); a
    compute class's gap is its share minus its proportional slice of
    the ideal time. The ideal allocation is capped at the compute
    classes' measured share — a capture whose op shares disagree with
    the MFU sample (different steps, approximate classification) must
    not produce negative per-class gaps — so gap shares sum to
    ``1 - min(mfu/100, compute_share)`` exactly (``1 - mfu/100`` when
    the capture's compute share covers the MFU, the normal case).

    Returns ``[(class, time_share, gap_share), ...]`` sorted by gap
    (descending); with no op-class data the whole gap is one
    ``unattributed`` row.
    """
    ideal = max(0.0, min(1.0, mfu_pct / 100.0))
    if not opclass_ms or sum(opclass_ms.values()) <= 0.0:
        return [("unattributed", 1.0, round(1.0 - ideal, 4))]
    total = sum(opclass_ms.values())
    shares = {c: ms / total for c, ms in opclass_ms.items() if ms > 0}
    compute_share = sum(shares.get(c, 0.0) for c in COMPUTE_CLASSES)
    ideal_eff = min(ideal, compute_share)
    out = []
    for cls, share in shares.items():
        if cls in COMPUTE_CLASSES and compute_share > 0:
            gap = share - ideal_eff * (share / compute_share)
        else:
            gap = share
        out.append((cls, round(share, 4), round(max(0.0, gap), 4)))
    out.sort(key=lambda t: -t[2])
    return out


# Op-class → the concrete lever in THIS repo that closes it: the audit
# names where the roofline gap lives; the worklist names what to flip.
# Closed over the classifier's vocabulary (utils.xplane) + the audit's
# synthetic 'unattributed' row.
FUSION_SUGGESTIONS = {
    "elementwise": ("fuse block epilogues: model.fused_epilogues "
                    "(bias+GELU / residual+LayerNorm, ops/"
                    "fused_update.py) + train.fused_epilogue "
                    "(one-pass clip+update+gate optimizer epilogue)"),
    "collective": ("overlap grad reductions: train.overlap_collectives "
                   "+ train.grad_bucket_mb (bucketed in-scan pmeans) "
                   "with the latency-hiding scheduler preset"),
    "infeed": ("input pipeline: data.mp_workers / packed_cache_dir / "
               "device_augment (docs/performance.md, input side)"),
    "attention": ("Pallas flash attention: model.attention_impl=pallas "
                  "(ops/flash_attention.py); chunked as the XLA "
                  "fallback"),
    "matmul": ("int8 quantized training (model.quant_training) or "
               "remat_policy=dots to stop recomputing MXU work"),
    "conv": ("space_to_depth stem (model.stem) and NHWC layout audit "
             "(models/resnet.py)"),
    "unattributed": ("no op-class capture for this row — run with "
                     "obs.profile_every_steps so attribute_capture "
                     "can split the gap"),
}


# bench.py's compute-graph arm tokens (_ga4/_overlap/_fusedep — ISSUE
# 14): arm rows own their OWN ledger trajectories and must never be
# cross-judged as the canonical preset's newest audited row.
_ARM_METRIC = re.compile(r"_(ga\d+|overlap|fusedep)_")


def _newest_audited_row(rows: list[dict], preset: str) -> dict | None:
    row = None
    for r in rows:  # newest wins: rows are append-ordered
        metric = str(r.get("metric", ""))
        if metric.startswith(preset) \
                and not _ARM_METRIC.search(metric) \
                and isinstance(r.get("mfu_pct"), (int, float)):
            row = r
    return row


def fusion_worklist(rows: list[dict], presets=AUDIT_PRESETS,
                    top_n: int = 3) -> list[dict]:
    """Turn the kernel-gap ranking into an ACTIONABLE fusion worklist:
    for each preset's newest audited ledger row, the top-N op-class
    gaps with the row's config digest, the capture/source that measured
    it, and the concrete repo lever that closes that class
    (FUSION_SUGGESTIONS). Consumed by ``tools/perf_ledger --audit
    --suggest`` and obs_report's perf section."""
    out: list[dict] = []
    for preset in presets:
        row = _newest_audited_row(rows, preset)
        if row is None:
            continue
        mfu = float(row["mfu_pct"])
        for cls, share, gap in kernel_gap(mfu, row.get("opclass_ms"))[:top_n]:
            if gap <= 0.0:
                continue
            out.append({
                "preset": preset,
                "metric": row.get("metric"),
                "op_class": cls,
                "gap_share": gap,
                "time_share": share,
                "mfu_pct": mfu,
                "config_digest": row.get("config_digest"),
                "source": row.get("source"),
                "measured": row.get("measured") or row.get("ts"),
                "capture": row.get("capture") or row.get("argv"),
                "suggestion": FUSION_SUGGESTIONS.get(
                    cls, "no catalogued lever — profile deeper"),
            })
    out.sort(key=lambda d: -d["gap_share"])
    return out


def fusion_worklist_report(rows: list[dict], presets=AUDIT_PRESETS,
                           top_n: int = 3) -> str:
    """Rendered worklist (one actionable line per gap entry)."""
    items = fusion_worklist(rows, presets=presets, top_n=top_n)
    if not items:
        return ("fusion worklist: no audited ledger rows (need mfu_pct "
                "rows — run bench.py per preset, or --import history)")
    lines = ["fusion worklist (top kernel-gap classes -> repo lever):"]
    for it in items:
        digest = f" cfg={it['config_digest']}" if it["config_digest"] else ""
        cap = f" [{it['capture']}]" if it.get("capture") else ""
        lines.append(
            f"  {it['preset']:<12} {it['op_class']:<12} "
            f"gap {it['gap_share']:>6.1%} (share {it['time_share']:.1%}, "
            f"{it['mfu_pct']:.1f}% MFU{digest}){cap}")
        lines.append(f"    -> {it['suggestion']}")
    return "\n".join(lines)


def kernel_gap_report(rows: list[dict],
                      presets=AUDIT_PRESETS) -> str:
    """The audit: newest ledger row per preset (metric prefix match)
    that carries ``mfu_pct``, ranked through :func:`kernel_gap`.
    Presets with no measured row say so rather than vanish (a silent
    hole reads as 'audited clean')."""
    lines = ["kernel-gap audit (roofline gap by op class; gap shares "
             "sum to 1 - MFU, capped by the capture's compute share):"]
    for preset in presets:
        row = _newest_audited_row(rows, preset)
        if row is None:
            lines.append(f"  {preset}: no ledger row with mfu_pct — run "
                         f"bench.py --model {preset}")
            continue
        mfu = float(row["mfu_pct"])
        lines.append(f"  {preset}: {row['metric']} = {row['value']} "
                     f"{row.get('unit', '')} @ {mfu:.2f}% MFU "
                     f"(gap {100.0 - mfu:.2f}%)")
        lines.append(f"    {'class':<14} {'time share':>10} "
                     f"{'gap share':>10}")
        for cls, share, gap in kernel_gap(mfu, row.get("opclass_ms")):
            lines.append(f"    {cls:<14} {share:>10.1%} {gap:>10.1%}")
    return "\n".join(lines)
