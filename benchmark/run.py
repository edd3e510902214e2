"""One cell of the benchmark, once, in a new process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The timed path is the one users run: ``Trainer.fit`` with its loader, planes
and logging at the preset's defaults. The runner reads it from outside: it
wraps the trainer's ``train_step`` attribute so that every step's loss goes to
a watcher thread, which waits for it (``block_until_ready``) and stamps the
host clock. Those stamps are step COMPLETIONS; the window runs from one
completion to a later one, so it ends in finished work. One ``fit`` call
covers warm-up and window (a second call would restart the loader); the
runner ends it by raising from the wrapper once the steps already dispatched
will carry the last completion past ``--seconds``.

Everything that belongs to one cell, configuration, reference or per-layer
metric lives in a file found by its name in BENCHMARK.json; nothing here
names any of them. The last stdout line is the result; see README.md.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(HERE, ".work")
if HERE not in sys.path:  # the benchmark's own modules, for the files it loads
    sys.path.insert(0, HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/compile_requests_use_cache": "requests"}


class WindowClosed(Exception):
    """Raised from the step wrapper to leave ``Trainer.fit``."""


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str):
    """(manifest, the cell's entry, its configuration file, its traffic file),
    each found by the name in BENCHMARK.json; the rehearsal's sizes folded in
    under an explicit JAX_PLATFORMS=cpu."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        die(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    cell = load_json(os.path.join(HERE, "workloads", entry["name"] + ".json"))
    if is_rehearsal():
        cell.update(cell.get("rehearsal", {}))
    return manifest, entry, config, cell


def is_rehearsal() -> bool:
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def reported_in(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def fold_seed(seed: int) -> int:
    """Any whole number -> 31 bits (numpy's and JAX's seeds are 32-bit)."""
    seed = abs(int(seed))
    out = 0
    while seed:
        out ^= seed & 0x7FFFFFFF
        seed >>= 31
    return out


def quantile95(values: list[float]) -> float:
    return statistics.quantiles(values, n=20, method="inclusive")[18]


def die(msg: str, code: int = 2):
    print(f"benchmark/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


class Watch:
    """The step wrapper (main thread) and the completion watcher (its own
    thread). All shared fields are written by one side only."""

    def __init__(self, inner, *, seconds, warmup_steps, check_steps, hooks,
                 trace_dir, trace_steps, read_counters):
        self.inner, self.seconds = inner, seconds
        self.read_counters = read_counters
        self.warmup_steps, self.check_steps = warmup_steps, check_steps
        self.hooks = hooks            # the reference module's device-side probes
        self.trace_dir, self.trace_steps = trace_dir, trace_steps
        self.dispatched = 0           # main thread
        self.batches = []             # first check_steps batches, on the host
        self.probes = {}              # device scalars enqueued between steps
        self.q: queue.Queue = queue.Queue()
        self.stamps: list[float] = []  # watcher thread: one per completion
        self.losses = []              # device scalars, fetched after the window
        self.skips = []               # the in-graph guard's flag, where it runs
        self.t_first = None           # stamp that opens the window
        self.at_open = None           # main thread: its counters at that time
        self.dt = None                # running median interval, seconds
        self.trace_stop_at = None     # completion count at which tracing ends
        self.error = None
        self.thread = threading.Thread(target=self._watch, name="bench-watch",
                                       daemon=True)
        self.thread.start()

    # ------------------------------------------------------- main thread
    def __call__(self, state, batch, rng):
        k = self.dispatched
        if self.error is not None:
            raise self.error
        if self.t_first is not None and self.at_open is None:
            self.at_open = self.read_counters()
        if self.t_first is not None and self.dt is not None:
            # completion time of the last step already dispatched
            done = len(self.stamps)
            eta = max(time.perf_counter(), self.stamps[done - 1]) \
                + max(k - done, 0) * self.dt
            if eta >= self.t_first + self.seconds:
                raise WindowClosed()
        if k < self.check_steps:
            import jax

            self.batches.append(jax.device_get(batch))
        state, metrics = self.inner(state, batch, rng)
        self.dispatched = k + 1
        if k < self.check_steps:
            # enqueued behind step k+1 and ahead of the donation by step k+2
            for name, fn in self.hooks.items():
                out = fn(k + 1, state)
                if out is not None:
                    self.probes[name] = out
        self.q.put((metrics["loss"], metrics.get("update_skipped", 0.0)))
        return state, metrics

    # ----------------------------------------------------- watcher thread
    def _watch(self):
        import jax

        try:
            n = 0
            while True:
                item = self.q.get()
                if item is None:
                    return
                loss, skipped = item
                jax.block_until_ready(loss)
                now = time.perf_counter()
                self.stamps.append(now)
                self.losses.append(loss)
                self.skips.append(skipped)
                n += 1
                if n == self.warmup_steps:
                    self.t_first = now
                if n >= 3:
                    tail = self.stamps[-9:]
                    self.dt = statistics.median(
                        b - a for a, b in zip(tail, tail[1:]))
                if self.trace_dir and n == self.warmup_steps + 5:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0  # leave the host loop alone:
                    if jax.devices()[0].platform == "tpu":
                        opts.host_tracer_level = 0  # only device planes are read
                    jax.profiler.start_trace(self.trace_dir,
                                             profiler_options=opts)
                    # two more than asked: the first and last come in part
                    self.trace_stop_at = n + self.trace_steps + 2
                elif n == self.trace_stop_at:
                    jax.profiler.stop_trace()
                    self.trace_stop_at = None
        except Exception as e:  # noqa: BLE001 - re-raised by the main thread
            self.error = e

    def finish(self):
        self.q.put(None)
        self.thread.join(timeout=300)
        if self.thread.is_alive():
            raise RuntimeError("completion watcher did not drain")
        if self.error is not None:
            raise self.error
        if self.trace_stop_at is not None:  # the window closed first
            import jax

            jax.profiler.stop_trace()


def build_config(config: dict, cell: dict, seed: int, rehearsal: bool,
                 run_dir: str):
    from pytorch_distributed_train_tpu.config import get_preset

    cfg = get_preset(config["preset"])
    pairs = list(config.get("overrides", [])) + list(cell.get("overrides", []))
    if rehearsal:
        pairs += list(config.get("rehearsal_overrides", []))
        pairs += list(cell.get("rehearsal_overrides", []))
    pairs += [f"seed={seed}", f"data.seed={seed}", f"checkpoint.dir={run_dir}"]
    cfg.apply_overrides(pairs)
    if not rehearsal:
        # the benchmark's own copy of the numbers must be what the program runs
        for dotted, want in {**config.get("expect", {}),
                             **cell.get("expect", {})}.items():
            obj = cfg
            for part in dotted.split("."):
                obj = getattr(obj, part)
            if obj != want:
                die(f"the program's {dotted} is {obj!r}, the benchmark's files "
                    f"say {want!r}")
    return cfg


def install_weights(trainer, variables):
    """Put the benchmark's seeded weights in the trainer's (fresh) state. The
    trees must agree leaf for leaf: names, shapes and types are the interface."""
    import jax

    state = trainer.state
    for key, have in (("params", state.params),
                      ("batch_stats", state.batch_stats or {})):
        want = variables.get(key, {})
        a = jax.tree_util.tree_flatten_with_path(have)[0]
        b = jax.tree_util.tree_flatten_with_path(want)[0]
        sig = lambda leaves: [(jax.tree_util.keystr(p), tuple(x.shape),  # noqa: E731
                               str(x.dtype)) for p, x in leaves]
        if sig(a) != sig(b):
            diff = sorted(set(sig(a)) ^ set(sig(b)))[:6]
            die(f"reference and program disagree on the {key} tree: {diff}")
    sh = trainer.state_sharding
    new = {"params": jax.device_put(variables["params"], sh.params)}
    if state.batch_stats:
        new["batch_stats"] = jax.device_put(variables["batch_stats"],
                                            sh.batch_stats)
    trainer.state = state.replace(**new)


def device_report(devices, chips: int) -> dict:
    # The TPU runtime keeps a program's temporaries in a region it RESERVES,
    # outside ``peak_bytes_in_use`` (which holds live arrays only): the peak
    # on a chip is the two peaks together (PERF.md section 7).
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": min(len(devices), chips), "memory_peak_bytes": peak}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    manifest, entry, config, cell = load_cell(args.workload)
    chips = int(entry["chips"])
    rehearsal = is_rehearsal()
    sys.path.insert(0, ROOT)
    try:
        import pytorch_distributed_train_tpu  # noqa: F401
    except ImportError as e:
        die(f"the program is not importable from {ROOT}: {e}")
    import jax
    t_imported = time.perf_counter()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        die(f"no accelerator: {e}")
    t_devices = time.perf_counter()
    if not rehearsal and devices[0].platform != "tpu":
        die(f"no TPU (found {devices[0].platform}); only an explicit "
            "JAX_PLATFORMS=cpu runs the tiny rehearsal")
    if len(devices) != chips and not (rehearsal and len(devices) > chips):
        die(f"cell {entry['name']} asks for {chips} chip(s), "
            f"JAX sees {len(devices)}")

    compiles: list[float] = []        # perf_counter stamp of each compile
    cache = {"hits": 0, "requests": 0}
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiles.append(time.perf_counter())
        if name == COMPILE_EVENT else None)
    jax.monitoring.register_event_listener(
        lambda name, **_k: cache.__setitem__(
            CACHE_EVENTS[name], cache[CACHE_EVENTS[name]] + 1)
        if name in CACHE_EVENTS else None)

    from pytorch_distributed_train_tpu.trainer import Trainer

    seed = fold_seed(args.seed)
    run_dir = os.path.join(WORK, entry["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir, exist_ok=True)
    trace_dir = os.path.join(run_dir, "trace") if args.trace else None
    refmod = load_module(os.path.join(HERE, "references",
                                      config["reference"] + ".py"))
    ref = refmod.Reference(config, rehearsal=rehearsal)

    cfg = build_config(config, cell, seed, rehearsal, run_dir)
    mesh = None  # the program builds its own, as its users' runs do
    if rehearsal and len(devices) > chips:
        from pytorch_distributed_train_tpu.parallel.mesh import build_mesh

        mesh = build_mesh(cfg.mesh, devices[:chips])
    trainer = Trainer(cfg, mesh=mesh)
    t_built = time.perf_counter()
    install_weights(trainer, ref.init_variables(seed))
    trainer.ckpt.save = lambda *_a, **_k: False  # saves are a later cell's subject
    watch = Watch(trainer.train_step, seconds=args.seconds,
                  warmup_steps=int(cell["warmup_steps"]),
                  check_steps=int(ref.check_steps),
                  hooks=ref.probes(seed), trace_dir=trace_dir,
                  trace_steps=int(cell.get("trace_steps", 10)),
                  read_counters=lambda: {"input_wait_s": float(
                      trainer.train_loader.stall_stats.wait_s)})
    trainer.train_step = watch
    try:
        trainer.fit()
        die("Trainer.fit returned before the window closed: the cell's "
            "horizon is too short", 3)
    except WindowClosed:
        pass
    watch.finish()
    device = device_report(devices, chips)
    stall_s = watch.read_counters()["input_wait_s"] \
        - watch.at_open["input_wait_s"]
    goodput = trainer.goodput.snapshot()

    # ------------------------------------------------- the window's numbers
    w0 = watch.warmup_steps - 1       # index of the stamp that opens it
    stamps = watch.stamps[w0:]
    window_s = stamps[-1] - stamps[0]
    intervals = [b - a for a, b in zip(stamps, stamps[1:])]
    losses = [float(x) for x in jax.device_get(watch.losses)]
    skips = [float(x) for x in jax.device_get(watch.skips)]
    in_window = losses[w0 + 1:]
    attempted = len(intervals)
    in_compiles = sum(1 for t in compiles if stamps[0] < t <= stamps[-1])
    failed = attempted if in_compiles else sum(
        1 for x, skipped in zip(in_window, skips[w0 + 1:])
        if not math.isfinite(x) or skipped > 0)
    observed = {"losses": losses[:ref.check_steps],
                **{k: jax.device_get(v) for k, v in watch.probes.items()}}
    batches = watch.batches
    setup_s = stamps[0] - _T0

    # free the program's state before the reference runs
    trainer.close()
    del trainer, watch.inner, watch.losses, watch.skips, watch.probes, \
        watch.read_counters
    gc.collect()

    t_ref = time.perf_counter()
    numbers = ref.check(seed, batches, observed)
    tenth = max(len(in_window) // 10, 1)
    numbers.append({
        "name": "window_loss_last_tenth_minus_first",
        "value": statistics.fmean(in_window[-tenth:])
        - statistics.fmean(in_window[:tenth]),
        "limit": float(cell.get("loss_trend_limit", 0.0))})
    correct = failed == 0 and all(
        math.isfinite(n["value"]) and n["value"] <= n["limit"]
        for n in numbers if n["limit"] is not None)
    ref_s = time.perf_counter() - t_ref

    items = attempted * int(cell["items_per_step"])
    e2e = {
        config["throughput_metric"]: items / window_s / chips,
        "step_ms_p95": 1e3 * quantile95(intervals),
        "setup_s": setup_s,
    }
    units = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "device": device}
    if args.trace:
        reduce_mod = load_module(os.path.join(HERE, "trace_reduce.py"))
        trace = reduce_mod.reduce_trace(trace_dir, chips, rehearsal)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"trace": trace, "config": config, "cell": cell,
               "device_kind": device["kind"], "chips": chips,
               "counters": {"input_wait_s": stall_s, "window_s": window_s,
                            "steps": attempted}}
        metrics = {}
        for m in manifest["per_layer"]:
            if not reported_in(m, entry["name"]):
                continue
            reader = load_module(os.path.join(HERE, "layer_metrics",
                                              m["name"] + ".py"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = value
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    else:
        metrics = {m["name"]: e2e[m["name"]] for m in manifest["end_to_end"]
                   if m["name"] in e2e and reported_in(m, entry["name"])}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in metrics.items()}

    print(json.dumps({
        "steps": {"count": attempted, "median_ms": 1e3 * statistics.median(intervals),
                  "p95_ms": e2e["step_ms_p95"], "max_ms": 1e3 * max(intervals),
                  "window_s": window_s, "warmup_steps": watch.warmup_steps},
        "setup": {"setup_s": setup_s, "imported_s": t_imported - _T0,
                  "devices_s": t_devices - _T0, "trainer_built_s": t_built - _T0,
                  "goodput_s_compile": goodput.get("goodput_s_compile"),
                  "reference_s": ref_s, "total_s": time.perf_counter() - _T0},
        "compile_cache": {**cache, "compiles_in_window": in_compiles,
                          "compiles": len(compiles),
                          "dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          or os.path.join(ROOT, ".jax_cache")},
        "input_wait_s": stall_s,
        "loss": {"first": losses[0], "window_first": in_window[0],
                 "window_last": in_window[-1]},
        "throughput": e2e[config["throughput_metric"]],
    }), flush=True)
    for n in numbers:
        print(json.dumps({"compared": n.pop("name"),
                          "ok": n["limit"] is None or bool(n["value"] <= n["limit"]),
                          **n}), flush=True)
    shutil.rmtree(run_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
