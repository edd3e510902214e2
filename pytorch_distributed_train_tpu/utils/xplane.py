"""XPlane trace reader: turn jax.profiler dumps into step-time reports.

The reference stack reads Kineto traces in TensorBoard or chrome://tracing
(torch:profiler/profiler.py:773 `profile`, SURVEY §5.1). On TPU the profiler
emits XPlane protobufs; the TensorBoard profile plugin renders them, but an
operator debugging throughput wants the top-ops table WITHOUT a TensorBoard
server — this module aggregates a dump directly:

    python -m pytorch_distributed_train_tpu.utils.xplane /tmp/trace --top 20

Works on the `*.xplane.pb` files produced by `jax.profiler.trace` (the
trainer's obs.profile_* window writes them). Op names are classified into
MXU/HBM-meaningful buckets (fusion, convolution, matmul, collective, copy,
infeed/outfeed) so the report answers "where did the step go" at a glance.
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Any

_CLASS_PATTERNS = (
    ("collective", ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")),
    ("convolution", ("convolution", "conv")),
    ("matmul", ("dot", "einsum")),
    ("copy", ("copy",)),
    ("infeed/outfeed", ("infeed", "outfeed", "send", "recv")),
    ("fusion", ("fusion",)),
)


def classify_op(name: str) -> str:
    """HLO-ish op name → report bucket."""
    n = name.lower().lstrip("%")
    for cls, pats in _CLASS_PATTERNS:
        if any(p in n for p in pats):
            return cls
    return "other"


# Perf-attribution op classes (obs/perf.py; docs/performance.md): a CLOSED
# roofline-meaningful vocabulary, distinct from the human report buckets
# above. Ordered — first match wins — so attention fusions (named
# "...attn..."/"flash..." by the pallas kernels and xla fusion naming)
# claim their ops before the generic matmul/elementwise patterns do, and
# data movement (copy/infeed) is never mistaken for compute. Plain
# "fusion.N" names are predominantly XLA loop fusions → elementwise; a
# fusion whose name carries dot/conv hints lands in the right compute
# class via the earlier patterns.
PERF_OP_CLASSES = ("matmul", "conv", "attention", "elementwise",
                   "collective", "infeed")

_PERF_CLASS_PATTERNS = (
    ("collective", ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all", "psum",
                    "ppermute")),
    ("infeed", ("infeed", "outfeed", "send", "recv", "copy",
                "transfer", "host")),
    ("attention", ("attention", "attn", "flash", "mha", "sdpa")),
    ("conv", ("convolution", "conv")),
    ("matmul", ("dot", "einsum", "gemm", "matmul")),
    ("elementwise", ("fusion", "add", "subtract", "multiply", "divide",
                     "exp", "tanh", "rsqrt", "sqrt", "log", "power",
                     "reduce", "broadcast", "select", "compare",
                     "convert", "maximum", "minimum", "scatter",
                     "gather", "slice", "pad", "transpose", "reshape",
                     "iota", "concatenate", "clamp", "softmax", "norm",
                     "bitcast", "and", "or", "not", "floor", "sort")),
)


def classify_op_class(name: str) -> str:
    """HLO-ish op name → perf op class (matmul/conv/attention/
    elementwise/collective/infeed), "other" when nothing matches."""
    n = name.lower().lstrip("%")
    for cls, pats in _PERF_CLASS_PATTERNS:
        if any(p in n for p in pats):
            return cls
    return "other"


def opclass_split(ops) -> dict[str, float]:
    """``[(name, ms, count), ...]`` (summarize_xspace's per-plane op
    list) → milliseconds per perf op class, zero classes dropped."""
    out = collections.Counter()
    for name, ms, _count in ops:
        out[classify_op_class(name)] += ms
    return {c: float(ms) for c, ms in out.most_common() if ms > 0}


def _import_xplane_pb2():
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2  # type: ignore
        return xplane_pb2
    except Exception as e:  # pragma: no cover - env-specific
        raise ImportError(
            "reading xplane dumps needs the tsl xplane proto "
            "(tensorflow.tsl.profiler.protobuf.xplane_pb2); not available "
            f"in this environment: {e}"
        ) from None


def load_xspace(path: str):
    """Parse one .xplane.pb file."""
    xplane_pb2 = _import_xplane_pb2()
    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def find_xplane_files(logdir: str) -> list[str]:
    """Newest-first xplane dumps under a jax.profiler logdir."""
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return sorted(paths, key=os.path.getmtime, reverse=True)


def summarize_xspace(xs, device_only: bool = True) -> list[dict[str, Any]]:
    """Per-plane aggregation: op totals, counts, and class buckets.

    Returns one dict per plane: {plane, total_ms, ops: [(name, ms, count)...]
    (descending), by_class: {cls: ms}}. ``device_only`` keeps planes whose
    name mentions TPU/GPU (the host CPU plane is python-profiling noise for
    a step-time report).
    """
    out = []
    for plane in xs.planes:
        if device_only and not any(
            tag in plane.name for tag in ("TPU", "GPU", "/device:")
        ):
            continue
        ev_meta = {m.id: m.name for m in plane.event_metadata.values()}
        total_ps = collections.Counter()
        count = collections.Counter()
        for line in plane.lines:
            for ev in line.events:
                name = ev_meta.get(ev.metadata_id, f"id{ev.metadata_id}")
                total_ps[name] += ev.duration_ps
                count[name] += 1
        by_class = collections.Counter()
        for name, ps in total_ps.items():
            by_class[classify_op(name)] += ps
        out.append({
            "plane": plane.name,
            "total_ms": sum(total_ps.values()) / 1e9,
            "ops": [(n, ps / 1e9, count[n])
                    for n, ps in total_ps.most_common()],
            "by_class": {c: ps / 1e9 for c, ps in by_class.most_common()},
        })
    return out


def report(logdir: str, top: int = 15, describe=None) -> str:
    """Human-readable top-ops report for the newest dump in ``logdir``.
    ``describe(name) -> str`` may say what an operation is (the program's
    map of its compiled step gives ``backward/ffn``): printed in brackets
    after the name where it says anything."""
    files = find_xplane_files(logdir)
    if not files:
        return f"no *.xplane.pb files under {logdir}"
    lines = [f"trace: {files[0]}"]
    xs = load_xspace(files[0])
    planes = summarize_xspace(xs)
    if not planes:  # CPU-only trace (tests, local debugging): show all
        planes = summarize_xspace(xs, device_only=False)
    for plane in planes:
        lines.append(f"\n=== {plane['plane']} — {plane['total_ms']:.1f} ms "
                     "summed over trace lines ===")
        lines.append("  by class:")
        for cls, ms in plane["by_class"].items():
            pct = 100.0 * ms / max(plane["total_ms"], 1e-9)
            lines.append(f"    {ms:10.2f} ms  {pct:5.1f}%  {cls}")
        lines.append(f"  top {top} ops:")
        for name, ms, n in plane["ops"][:top]:
            what = describe(name) if describe is not None else ""
            lines.append(f"    {ms:10.2f} ms  n={n:<6d} {name[:100]}"
                         + (f"  [{what}]" if what else ""))
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> None:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("logdir", help="jax.profiler trace dir (or a .xplane.pb)")
    p.add_argument("--top", type=int, default=15)
    args = p.parse_args(argv)
    logdir = args.logdir
    if logdir.endswith(".xplane.pb"):
        logdir = os.path.dirname(logdir)
    print(report(logdir, top=args.top))


if __name__ == "__main__":
    main()
