"""Multi-process shared-memory decode plane (ROADMAP item 2, ISSUE 12a).

The GIL wall this replaces: one chip consumes ResNet images several
times faster than one Python process delivers them (not measured on
today's stack) — the decode and augment work runs in ONE Python process, and threads only help
where PIL/numpy drop the GIL. This pool runs the decode in N forked
worker PROCESSES (the torch DataLoader worker model, SURVEY C17,
torch:utils/data/_utils/worker.py:244) with one crucial difference:
decoded pixel batches come back through preallocated SHARED-MEMORY ring
slots, not a pickle stream — the parent pays one memcpy per batch, the
workers never serialize pixels.

Design points:

- **fork, not spawn**: workers are created with the POSIX fork context,
  so the ``make_batch`` closure (dataset handle included) is inherited
  by address space, never pickled. Task messages carry only index
  arrays and small ints. Platforms without fork degrade to in-process
  loading (``available()`` gates the pool at the loaders).
- **anonymous shared mappings**: ring slots are ``mmap.mmap(-1, n)``
  MAP_SHARED|MAP_ANONYMOUS regions created BEFORE the fork — no
  /dev/shm names, no resource-tracker bookkeeping, freed with the
  processes. Each slot holds one host batch; a worker writes the raw
  array bytes and ships a tiny (key, dtype, shape, offset) layout over
  the result queue.
- **ordered delivery, composition-exact**: tasks are numbered; a
  reorder buffer yields batch b strictly in submission order, so the
  byte-level batch stream is IDENTICAL to the in-process path (the
  PR 6 invariant: batch composition and ``start_batch`` resume must be
  invariant to how the work is parallelized). Randomness never depends
  on worker scheduling because every task carries its own rng key
  material — the loaders' (seed, epoch, batch/record) keying runs
  inside the worker unchanged.
- **per-worker stage timers**: workers accumulate the same
  read/decode/augment stage seconds (obs/perf.py) their dataset code
  already emits — process-locally — and ship the per-batch delta with
  each result; the parent merges the deltas into the process-global
  ``input_stage_seconds_total`` attribution, so the staged stall split
  keeps working when the stages run in other processes.
- **epoch tokens**: an abandoned epoch (early break, step cap) leaves
  in-flight tasks behind; results are stamped with the submitting
  epoch's token and stale arrivals are dropped (slot reclaimed), so the
  next epoch can never interleave another epoch's batches.

The pool is deliberately loader-agnostic: ``make_batch(task) -> dict``
is supplied by the threads loader (data/pipeline.py) and the grain
loader (data/grain_pipeline.py), each preserving its own rng-keying
convention.
"""

from __future__ import annotations

import mmap
import os
import queue
import threading
import time
import traceback
from typing import Callable, Iterable, Iterator

import numpy as np

from pytorch_distributed_train_tpu.obs import perf as perf_lib
from pytorch_distributed_train_tpu.obs.registry import get_registry


def available() -> bool:
    """The pool needs POSIX fork (closure inheritance — see module doc)."""
    return hasattr(os, "fork")


def process_thread_budget(solo_threads: int) -> int:
    """Per-process thread fan-out for decode helpers (item/record thread
    pools): the solo count, clamped by the PDTT_NATIVE_THREADS budget a
    pool worker runs under (x2 — decode threads block on I/O about half
    the time, C++ threads don't). The one definition both loaders' module
    pools share."""
    env = os.environ.get("PDTT_NATIVE_THREADS")
    if env:
        try:
            return max(1, min(solo_threads, max(1, int(env)) * 2))
        except ValueError:
            pass
    return max(1, solo_threads)


def python_thread_budget(solo_threads: int) -> int:
    """Per-process thread budget for PYTHON/PIL decode pools — the x2
    I/O allowance of ``process_thread_budget`` removed.

    The x2 was sized for C++ decode (libjpeg/imgops release the GIL for
    the whole call); PIL item decode holds the GIL through its Python
    framing, so N pool workers each running 2x their core share contend
    instead of overlapping — the ``pil_grain_mp8`` regression (slower
    than plain threads, ISSUE 14 satellite): 8 forked workers
    x (2 cores x2 = 4) PIL threads = 32 GIL-bound threads on a 24-core
    host. Inside a pool worker this clamps to exactly the worker's
    PDTT_NATIVE_THREADS core share."""
    env = os.environ.get("PDTT_NATIVE_THREADS")
    if env:
        try:
            return max(1, min(solo_threads, max(1, int(env))))
        except ValueError:
            pass
    return max(1, solo_threads)


def worker_core_share(num_workers: int, avail: int | None = None) -> int:
    """Per-worker core share of the pool: (cpus - 1) split across the
    workers, floor 1 — THE definition, used both at fork time (the
    PDTT_NATIVE_THREADS budget each worker runs under) and by the
    parent-side mirrors that report/warn about it
    (``pool_decode_threads``, the grain clamp note). One formula so the
    gauge/ledger identity can never drift from what the workers
    actually use."""
    if avail is None:
        avail = os.cpu_count() or 2
    return max(1, (avail - 1) // max(num_workers, 1))


def pool_decode_threads(num_workers: int, solo_threads: int = 8,
                        avail: int | None = None) -> int:
    """The PIL-decode thread count ONE forked pool worker will use —
    the parent-side mirror of ``python_thread_budget`` under the
    per-worker core share the pool sets at fork (worker_core_share).
    Lets loaders/benches report and warn about the total decode fan-out
    before any worker forks."""
    if avail is None:
        avail = os.cpu_count() or 2
    if num_workers <= 0:
        return max(1, min(solo_threads, avail))
    return max(1, min(solo_threads, worker_core_share(num_workers, avail)))


def pool_budget(requested: int, avail: int | None = None) -> int:
    """Worker-process budget for the shared-memory pool.

    One core always stays with the consumer/train loop (same rationale
    as grain_pipeline.bounded_workers), but unlike grain's clamp the
    floor is 0 only when the caller asked for 0: a 1-core host with
    ``mp_workers>0`` gets 1 worker, because the pool's workers block on
    a queue when idle instead of spinning grain's IPC machinery — the
    measured pathology behind the old clamp-to-zero does not apply.
    """
    if requested <= 0:
        return 0
    if avail is None:
        avail = os.cpu_count() or 1
    return max(1, min(requested, avail - 1))


def _write_slot(view: memoryview, batch: dict) -> list | None:
    """Serialize a batch dict's raw bytes into one ring slot.

    Returns the (key, dtype-str, shape, offset) layout, or None when the
    batch doesn't fit (caller falls back to the pickle path — loud, and
    counted)."""
    off = 0
    layout = []
    for k in sorted(batch):
        a = np.ascontiguousarray(batch[k])
        n = a.nbytes
        if off + n > len(view):
            return None
        view[off:off + n] = memoryview(a).cast("B")
        layout.append((k, a.dtype.str, a.shape, off))
        off += n
    return layout


def _read_slot(view: memoryview, layout: list) -> dict:
    """Copy a batch back out of a ring slot (the one memcpy the parent
    pays; the slot is reusable the moment this returns)."""
    out = {}
    for k, dtype, shape, off in layout:
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        out[k] = np.frombuffer(
            view, dtype=np.dtype(dtype), count=n, offset=off
        ).reshape(shape).copy()
    return out


def reset_thread_local_state(dataset) -> None:
    """Drop a dataset's per-thread handle caches after a fork.

    fork duplicates the fd table but file OFFSETS live in the shared
    open-file description: a TarShardImageDataset handle opened in the
    parent before the fork would have every worker (and the parent)
    seek/read through the SAME offset — racing reads return other
    workers' bytes. The pickle path already drops `_local`
    (__getstate__); this is the fork-path equivalent, called by
    _worker_main before any task runs."""
    if hasattr(dataset, "_local"):
        import threading as _threading

        dataset._local = _threading.local()


def _worker_main(task_q, result_q, views, make_batch,
                 native_threads: int = 0, post_fork=None) -> None:
    """Worker loop: drain tasks, decode, write the slot, ship the layout
    plus the batch's stage-seconds delta. Runs until the None sentinel.

    Never touches jax (the obs/ package contract keeps perf_lib
    jax-free); errors ship as formatted tracebacks — the parent raises
    them on the consumer thread."""
    # Shed the parent's inherited diagnostics: the trainer installs
    # signal-dump handlers (flight recorder, faulthandler SIGTERM
    # stacks) that a torn-down decode worker must not replay — a worker
    # dying at parent exit is routine, not an incident.
    import faulthandler
    import signal

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, signal.SIG_DFL)
        except (OSError, ValueError):
            pass
    try:
        faulthandler.disable()
    except Exception:
        pass
    if native_threads > 0:
        # Split the host's C++ decode/augment thread budget across the
        # pool: N workers each running the SOLO default (up to 8
        # libjpeg/imgops threads) oversubscribe the host into a
        # slowdown — measured 607 vs 2235 img/s on the 24-core bench
        # box before this cap.
        os.environ["PDTT_NATIVE_THREADS"] = str(native_threads)
    if post_fork is not None:
        post_fork()
    stats = perf_lib.get_input_stats()
    reg = get_registry()
    # Counters this worker's dataset/fault code bumps (cache reads,
    # decode retries/substitutions) live in the CHILD's registry copy;
    # each result ships the per-batch counter delta home so the
    # parent's /metrics stays whole-pipeline. input_stage_seconds_total
    # is excluded: the stage-seconds merge below already re-increments
    # it parent-side.
    _SKIP = ("input_stage_seconds_total",)

    def _counters():
        return {k: v for k, v in reg.counter_values().items()
                if k[0] not in _SKIP}

    while True:
        msg = task_q.get()
        if msg is None:
            return
        token, seq, slot, task = msg
        before = dict(stats.seconds)
        c_before = _counters()
        t0 = time.monotonic()
        try:
            batch = make_batch(task)
            layout = _write_slot(views[slot], batch)
            busy = time.monotonic() - t0
            delta = {s: stats.seconds[s] - before.get(s, 0.0)
                     for s in stats.seconds
                     if stats.seconds[s] > before.get(s, 0.0)}
            c_delta = {k: v - c_before.get(k, 0.0)
                       for k, v in _counters().items()
                       if v > c_before.get(k, 0.0)}
            if layout is None:
                # Oversized batch (shouldn't happen with static shapes;
                # ragged text tails can): pickle path keeps correctness.
                result_q.put((token, seq, "pickle", slot, batch, delta,
                              c_delta, busy))
            else:
                result_q.put((token, seq, "shm", slot, layout, delta,
                              c_delta, busy))
        except BaseException:
            result_q.put((token, seq, "error", slot,
                          traceback.format_exc(), {}, {},
                          time.monotonic() - t0))


class SharedMemoryWorkerPool:
    """N forked decode processes + a shared-memory result ring.

    ``run(tasks)`` is a generator: it computes the FIRST task in the
    parent (sizing the ring from its byte footprint on first use, and
    warming dataset caches the way the in-process path would), then
    streams the remaining tasks through the workers, yielding batches
    in task order. One pool instance serves many epochs; ``close()``
    (also registered via the workers being daemonic) tears it down.
    """

    def __init__(self, make_batch: Callable[[object], dict],
                 num_workers: int, *, slots: int = 0,
                 slot_headroom: float = 1.1, post_fork=None):
        if num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got {num_workers}")
        if not available():
            raise RuntimeError(
                "SharedMemoryWorkerPool needs os.fork (POSIX)")
        self.make_batch = make_batch
        self.post_fork = post_fork
        self.num_workers = num_workers
        self.slots = slots or num_workers + 2
        self._headroom = slot_headroom
        self._started = False
        self._closed = False
        self._token = 0
        self._procs: list = []
        self._maps: list[mmap.mmap] = []
        self._views: list[memoryview] = []
        self._task_q = None
        self._result_q = None
        # Parent-side slot free-list: plain queue.Queue — only parent
        # threads (submitter + consumer generator) touch it.
        self._free: queue.Queue = queue.Queue()
        self._abort = threading.Event()
        reg = get_registry()
        self._g_workers = reg.gauge(
            "input_worker_pool_workers",
            help="shared-memory decode pool size (worker processes); 0 "
                 "when the pool is off")
        self._g_occupancy = reg.gauge(
            "input_worker_occupancy",
            help="decode-pool busy fraction (busy worker-seconds over "
                 "pool capacity since the epoch started)")
        self._c_batches = reg.counter(
            "input_worker_batches_total",
            help="batches decoded by shared-memory pool workers")
        self._c_busy = reg.counter(
            "input_worker_busy_seconds_total",
            help="cumulative busy seconds across decode-pool workers")
        self._c_fallback = reg.counter(
            "input_worker_fallback_total",
            help="pool batches that overflowed their ring slot and "
                 "shipped pickled (oversized batch — ring undersized)")

    # ------------------------------------------------------------ lifecycle
    def _start(self, slot_bytes: int) -> None:
        import multiprocessing as mp

        ctx = mp.get_context("fork")
        self._task_q = ctx.Queue()
        self._result_q = ctx.Queue()
        for i in range(self.slots):
            m = mmap.mmap(-1, slot_bytes)  # anonymous MAP_SHARED region
            self._maps.append(m)
            self._views.append(memoryview(m))
            self._free.put(i)
        import warnings

        with warnings.catch_warnings():
            # jax warns on ANY os.fork under its threads; these workers
            # never touch jax (decode is numpy/PIL/native), so the
            # deadlock it warns about cannot involve a jax lock. The
            # start is done from the consumer side before batches flow.
            warnings.filterwarnings(
                "ignore", message=".*os.fork.*", category=RuntimeWarning)
            native_threads = worker_core_share(self.num_workers)
            for _ in range(self.num_workers):
                p = ctx.Process(
                    target=_worker_main,
                    args=(self._task_q, self._result_q, self._views,
                          self.make_batch, native_threads,
                          self.post_fork),
                    daemon=True)
                p.start()
                self._procs.append(p)
        self._started = True
        self._g_workers.set(self.num_workers)

    def close(self) -> None:
        """Stop workers and release the ring. Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._abort.set()
        if self._started:
            for _ in self._procs:
                try:
                    self._task_q.put(None)
                except (OSError, ValueError):
                    pass
            for p in self._procs:
                p.join(timeout=5.0)
                if p.is_alive():
                    p.terminate()
            # release queue feeder threads before unmapping
            for q_ in (self._task_q, self._result_q):
                try:
                    q_.close()
                    q_.join_thread()
                except (OSError, ValueError):
                    pass
            for v in self._views:
                v.release()
            for m in self._maps:
                try:
                    m.close()
                except BufferError:
                    pass  # a copied-out view still alive somewhere
        self._g_workers.set(0)

    def __del__(self):  # best-effort; daemons die with the parent anyway
        try:
            self.close()
        except Exception:
            pass

    # -------------------------------------------------------------- running
    def _slot_bytes_for(self, batch: dict) -> int:
        total = sum(np.ascontiguousarray(v).nbytes for v in batch.values())
        return max(1 << 16, int(total * self._headroom) + 4096)

    def run(self, tasks: Iterable) -> Iterator[dict]:
        """Stream ``tasks`` through the pool, yielding batches in order.

        One epoch owns the pool at a time, but an ABANDONED epoch's
        generator may still be suspended (a producer thread that hasn't
        been collected yet) when the next one starts: every epoch gets
        its OWN abort event (a stale generator's teardown can then never
        kill its successor), and a consumer that sees a NEWER token —
        in a message, or on the pool itself — hands the message back
        and retires, so two overlapping generators can't steal each
        other's batches."""
        if self._closed:
            raise RuntimeError("worker pool is closed")
        it = iter(tasks)
        first = next(it, None)
        if first is None:
            return
        # First batch in-parent: sizes the ring on first use and keeps
        # the epoch's first yield latency equal to the in-process path
        # (workers fill the ring behind it).
        batch0 = self.make_batch(first)
        if not self._started:
            self._start(self._slot_bytes_for(batch0))
        self._token += 1
        token = self._token
        abort = threading.Event()  # THIS epoch's, never a successor's
        self._abort = abort        # close() aborts the current epoch
        yield batch0

        submitted = [0]
        done = threading.Event()

        def _submit():
            n = 0
            try:
                for task in it:
                    slot = None
                    while slot is None:
                        if abort.is_set():
                            return
                        try:
                            slot = self._free.get(timeout=0.1)
                        except queue.Empty:
                            continue
                    self._task_q.put((token, n, slot, task))
                    n += 1
            finally:
                submitted[0] = n
                done.set()

        submitter = threading.Thread(target=_submit, daemon=True)
        submitter.start()
        t_epoch0 = time.monotonic()
        busy_total = 0.0
        pending: dict[int, dict] = {}
        next_seq = 0
        stats = perf_lib.get_input_stats()
        try:
            while True:
                if done.is_set() and next_seq >= submitted[0] \
                        and not pending:
                    return
                if self._token != token:
                    return  # a newer epoch owns the pool; retire quietly
                try:
                    msg = self._result_q.get(timeout=0.1)
                except queue.Empty:
                    dead = [p for p in self._procs if not p.is_alive()]
                    if dead:
                        # A worker died mid-epoch (OOM kill, segfault):
                        # its in-flight seq would block the reorder
                        # buffer forever — fail LOUDLY instead.
                        raise RuntimeError(
                            f"{len(dead)}/{len(self._procs)} shared-"
                            "memory decode worker(s) died (exitcodes "
                            f"{[p.exitcode for p in dead]}) — batch "
                            f"{next_seq} can never arrive")
                    continue
                tok, seq, kind, slot, payload, stage_delta, c_delta, \
                    busy = msg
                if tok != token:
                    if tok > token:
                        # a successor epoch's result — hand it back and
                        # retire; dropping it would wedge that epoch
                        self._result_q.put(msg)
                        return
                    self._free.put(slot)  # stale epoch: reclaim only
                    continue
                if kind == "error":
                    self._free.put(slot)
                    raise RuntimeError(
                        f"decode worker failed on batch {seq}:\n{payload}")
                if kind == "pickle":
                    self._c_fallback.inc()
                    batch = payload
                    self._free.put(slot)
                else:
                    batch = _read_slot(self._views[slot], payload)
                    self._free.put(slot)
                stats.merge(stage_delta)
                if c_delta:
                    get_registry().merge_counter_deltas(c_delta)
                busy_total += busy
                self._c_batches.inc()
                self._c_busy.inc(busy)
                elapsed = time.monotonic() - t_epoch0
                if elapsed > 0:
                    self._g_occupancy.set(
                        min(1.0, busy_total / (self.num_workers * elapsed)))
                pending[seq] = batch
                while next_seq in pending:
                    yield pending.pop(next_seq)
                    next_seq += 1
        finally:
            abort.set()
            submitter.join(timeout=5.0)
