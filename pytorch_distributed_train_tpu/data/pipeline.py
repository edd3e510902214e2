"""Host data loader + HBM prefetch (SURVEY C17, §3.4 TPU mapping).

Pipeline stages, each overlapped with the next:

  sampler indices ─→ [worker threads: decode/augment/collate]
                 ─→ [background producer thread, bounded queue]
                 ─→ [jax.make_array_from_process_local_data → HBM,
                     `prefetch`-deep buffer]  ─→ jitted step

Threads replace the reference's DataLoader worker *processes*
(torch:utils/data/_utils/worker.py:244): PIL decode and numpy release the
GIL, and there is no CUDA pinned-memory dance — device_put DMAs straight to
HBM while the previous step runs (the double-buffer the reference gets from
its pin-memory thread + non_blocking copies, torch:utils/data/_utils/
pin_memory.py:18).
"""

from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from pytorch_distributed_train_tpu.data.sampler import DistributedSampler
from pytorch_distributed_train_tpu.obs import perf as perf_lib
from pytorch_distributed_train_tpu.obs.spans import span as _span


class StallStats:
    """Input-stall accounting: cumulative time the CONSUMER blocked waiting
    for the host pipeline to produce a batch.

    The feed-ratio question (SURVEY §7.4.1 — the #1-ranked hard part) is
    whether the host can keep the chip fed; sustained-run acceptance is
    "input_stall_pct < 5" (BASELINE.json:8 drill). The counter sits at the
    producer-queue get: with async device_put downstream, that wait IS the
    time the step loop would have idled on input. Plain float adds under
    the GIL — one writer (the consumer thread) — no lock needed.

    Each add also mirrors into the scrape registry
    (``input_stall_seconds_total{split=...}``) so a live /metrics poll
    sees the stall trend without waiting for the next JSONL window.
    """

    def __init__(self, split: str = "train") -> None:
        self.waits = 0
        self.wait_s = 0.0
        from pytorch_distributed_train_tpu.obs.registry import get_registry

        self._counter = get_registry().counter(
            "input_stall_seconds_total", labels={"split": split},
            help="cumulative seconds the consumer blocked on the host "
                 "input pipeline")

    def add(self, dt: float) -> None:
        self.waits += 1
        self.wait_s += dt
        self._counter.inc(dt)


# Per-process thread pool for item-style collate INSIDE shared-memory
# decode workers (data/workers.py): a module global rebuilt lazily per
# process (pid-guarded — executor threads never survive a fork). The
# in-process path keeps the loader-owned pool (self._pool) unchanged.
_ITEM_POOL: tuple[int, ThreadPoolExecutor] | None = None


def _item_pool(num_workers: int) -> ThreadPoolExecutor:
    global _ITEM_POOL
    if _ITEM_POOL is None or _ITEM_POOL[0] != os.getpid():
        from pytorch_distributed_train_tpu.data import workers as workers_lib

        # python_thread_budget (no x2): PIL item decode holds the GIL
        # through its Python framing — inside a forked mp worker the
        # pool clamps to exactly the worker's core share (the
        # pil_grain_mp8 oversubscription fix, ISSUE 14 satellite).
        _ITEM_POOL = (os.getpid(), ThreadPoolExecutor(
            max_workers=workers_lib.python_thread_budget(num_workers)))
    return _ITEM_POOL[1]


def collate_chunk(dataset, chunk: np.ndarray, *, seed: int, epoch: int,
                  batch_index: int, host_id: int, train: bool,
                  pool=None, num_workers: int = 4) -> dict:
    """Collate ONE host batch — the single definition of the threads
    loader's batch semantics, shared byte-exactly by the in-process path
    (HostDataLoader._collate) and the shared-memory decode workers.

    The per-batch rng is keyed on (seed, epoch, batch-index, host), so
    batch b is identical wherever (and in whichever process) it is
    materialized — the invariant every resume/elastic test pins.
    `data.decode` fault point + retry/backoff (faults/): transient decode
    errors back off and retry; a record that stays undecodable is
    substituted-and-counted — static SPMD shapes forbid dropping a row.
    """
    from pytorch_distributed_train_tpu import faults as faults_lib

    rng = np.random.default_rng(
        np.random.SeedSequence((seed, epoch, batch_index, host_id)))
    if not getattr(dataset, "is_item_style", False):
        def _load_batch(_i=None):
            faults_lib.maybe_fire("data.decode")
            return dataset.get_batch(chunk, rng, train)

        return faults_lib.retry_call(_load_batch, point="data.decode")
    seeds = rng.integers(0, 2**63, size=len(chunk))
    n = len(dataset)

    def _load_one(a):
        i, item_seed = int(a[0]), int(a[1])

        def load(j):
            faults_lib.maybe_fire("data.decode")
            return dataset.get_item(j, np.random.default_rng(item_seed))

        return faults_lib.decode_with_retry(load, i, n)

    if pool is None:
        pool = _item_pool(num_workers)
    items = list(pool.map(_load_one, zip(chunk, seeds)))
    return {k: np.stack([it[k] for it in items]) for k in items[0]}


class HostDataLoader:
    """Per-host loader: yields this host's shard of each global batch.

    Length semantics: drop_last=True (training) truncates to full batches —
    required for SPMD static shapes (SURVEY §7.4.5); eval pads the tail batch
    by wrapping (sampler already padded to host-divisibility).

    With ``data.mp_workers > 0`` the collate runs in the shared-memory
    decode pool (data/workers.py) instead of this process — same batch
    bytes, same resume semantics, N processes of decode/augment.
    """

    def __init__(self, dataset, data_cfg, *, train: bool,
                 num_hosts: int | None = None, host_id: int | None = None):
        self.dataset = dataset
        self.train = train
        self.num_hosts = num_hosts if num_hosts is not None else jax.process_count()
        self.host_id = host_id if host_id is not None else jax.process_index()
        global_batch = data_cfg.batch_size if train else (
            data_cfg.eval_batch_size or data_cfg.batch_size
        )
        if global_batch % self.num_hosts != 0:
            raise ValueError(
                f"global batch {global_batch} not divisible by {self.num_hosts} hosts"
            )
        self.host_batch = global_batch // self.num_hosts
        self.global_batch = global_batch
        self.seed = data_cfg.seed
        self.num_workers = data_cfg.num_workers
        if train and getattr(data_cfg, "weighted_sampling", ""):
            from pytorch_distributed_train_tpu.data.sampler import (
                make_weighted_sampler,
            )

            self.sampler = make_weighted_sampler(
                dataset, data_cfg, self.num_hosts, self.host_id)
        else:
            self.sampler = DistributedSampler(
                len(dataset), self.num_hosts, self.host_id,
                shuffle=train and data_cfg.shuffle, seed=data_cfg.seed,
                drop_last=False,
            )
        self._pool: ThreadPoolExecutor | None = None
        self._owner_pid = os.getpid()
        # Shared-memory decode pool (data/workers.py) — built lazily on
        # the first epoch so tests/tools constructing loaders never fork.
        from pytorch_distributed_train_tpu.data import workers as workers_lib

        self.mp_workers = (
            workers_lib.pool_budget(getattr(data_cfg, "mp_workers", 0))
            if workers_lib.available() else 0)
        self.mp_slots = getattr(data_cfg, "mp_slots", 0)
        self._mp_pool = None

    @property
    def steps_per_epoch(self) -> int:
        n = self.sampler.num_samples
        if self.train:
            return n // self.host_batch
        return (n + self.host_batch - 1) // self.host_batch

    def close(self) -> None:
        """Release the shared-memory pool (bench/tests; the trainer's
        daemonic workers die with the process either way)."""
        if self._mp_pool is not None:
            self._mp_pool.close()
            self._mp_pool = None

    def _epoch_chunks(self, epoch: int) -> np.ndarray:
        self.sampler.set_epoch(epoch)
        idx = self.sampler.indices()
        n_steps = self.steps_per_epoch
        if not self.train:
            # pad tail by wrapping so every step is full-size (weights unused
            # rows are the caller's concern only for exact eval metrics).
            # np.resize tiles cyclically — datasets smaller than one batch
            # (tiny eval holdouts) still fill a whole batch, where a single
            # wrap-around concat would come up short and break the sharded
            # device_put's divisibility contract.
            need = n_steps * self.host_batch
            if len(idx) < need:
                idx = np.resize(idx, need)
        return idx

    def epoch(self, epoch: int, start_batch: int = 0) -> Iterator[dict]:
        """Yield host-local numpy batches for one epoch.

        ``start_batch`` fast-forwards a mid-epoch resume: the per-batch rng
        is seeded by (seed, epoch, batch-index, host), so batch b is
        identical whether or not batches before it were materialized — the
        resumed stream continues exactly where the crashed run stopped
        (stronger than the reference, which replays the epoch). Batch
        composition is also invariant to ``mp_workers``: the pool receives
        the SAME (batch-index, chunk) tasks this loop would collate."""
        idx = self._epoch_chunks(epoch)
        n_steps = self.steps_per_epoch
        tasks = ((epoch, b, idx[b * self.host_batch:(b + 1) * self.host_batch])
                 for b in range(start_batch, n_steps))
        if self.mp_workers > 0:
            if self._mp_pool is None:
                from pytorch_distributed_train_tpu.data import (
                    workers as workers_lib,
                )

                self._mp_pool = workers_lib.SharedMemoryWorkerPool(
                    self._pool_collate, self.mp_workers,
                    slots=self.mp_slots,
                    post_fork=lambda: workers_lib.reset_thread_local_state(
                        self.dataset))
            return self._mp_pool.run(tasks)
        return (self._pool_collate(t) for t in tasks)

    def _pool_collate(self, task) -> dict:
        """One (epoch, batch-index, chunk) task → batch dict. Runs on
        the consumer thread OR inside a forked decode worker — both call
        the same collate_chunk, so the bytes cannot diverge. The loader-
        owned item thread pool is only usable in the process that built
        it (executor threads never survive a fork); elsewhere
        collate_chunk falls back to the per-process module pool."""
        epoch, b, chunk = task
        pool = None
        if getattr(self.dataset, "is_item_style", False) \
                and os.getpid() == self._owner_pid:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=max(1, self.num_workers))
            pool = self._pool
        return collate_chunk(
            self.dataset, chunk, seed=self.seed, epoch=epoch,
            batch_index=b, host_id=self.host_id, train=self.train,
            pool=pool, num_workers=self.num_workers)


class _Producer(threading.Thread):
    """Background producer draining an iterator into a bounded queue —
    keeps host-side collate off the step critical path.

    Shut-down safe: an abandoned consumer (early break from the epoch, step
    cap reached) calls stop() from the iterator's finally, which unblocks a
    producer wedged on a full queue — no leaked threads holding prefetch
    buffers."""

    _DONE = object()

    def __init__(self, it: Iterator, depth: int,
                 stats: StallStats | None = None):
        super().__init__(daemon=True)
        self.it = it
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.error: BaseException | None = None
        self.stats = stats
        self._stopped = threading.Event()
        from pytorch_distributed_train_tpu.obs.registry import get_registry

        # Prefetch-occupancy gauge (obs/perf.py plane): queue fill
        # fraction sampled at every consumer get — 0.0 sustained means
        # the producer never gets ahead (input-bound), 1.0 means the
        # chip is the bottleneck. The scrapable twin of input_stall_pct.
        self._occupancy = get_registry().gauge(
            "input_prefetch_occupancy",
            help="producer->consumer prefetch queue fill fraction at "
                 "consumer gets (0 = input-bound, 1 = chip-bound)")
        self.start()

    _EXHAUSTED = object()

    def run(self):
        try:
            it = iter(self.it)
            while True:
                # span per produced batch: the trace shows host collate
                # time interleaved with the consumer's step spans (the
                # two-thread overlap the pipeline exists to create).
                # next(it, sentinel), not try/except StopIteration — a
                # StopIteration raised through the span contextmanager
                # generator would become a PEP 479 RuntimeError.
                with _span("data.produce"):
                    item = next(it, self._EXHAUSTED)
                if item is self._EXHAUSTED:
                    break
                while not self._stopped.is_set():
                    try:
                        self.q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stopped.is_set():
                    return
        except BaseException as e:  # surfaced on the consumer side
            self.error = e
        finally:
            # blocking-with-stop-check put: the queue may be full here, and
            # dropping the marker would wedge the consumer on q.get() forever
            while not self._stopped.is_set():
                try:
                    self.q.put(self._DONE, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def stop(self) -> None:
        self._stopped.set()
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass

    def __iter__(self):
        try:
            while True:
                self._occupancy.set(
                    self.q.qsize() / max(self.q.maxsize, 1))
                t0 = time.perf_counter()
                item = self.q.get()
                if self.stats is not None:
                    # Non-empty-queue gets cost microseconds; genuine
                    # stalls dominate the sum, so unconditional adds keep
                    # the hot path branch-free and the number honest.
                    self.stats.add(time.perf_counter() - t0)
                if item is self._DONE:
                    if self.error is not None:
                        raise self.error
                    return
                yield item
        finally:
            self.stop()


def device_prefetch(host_batches: Iterator[dict], mesh, batch_axes=("data", "fsdp"),
                    depth: int = 2) -> Iterator[dict]:
    """Assemble global jax.Arrays from host-local shards and keep `depth`
    batches in flight to HBM (BASELINE.json:5 'device-side prefetch to HBM').

    device_put is async — enqueueing the transfer returns immediately, so the
    DMA for batch N+1 overlaps step N's compute.
    """
    sharding = NamedSharding(mesh, PartitionSpec(tuple(batch_axes)))

    def to_device(b: dict) -> dict:
        # h2d stage (obs/perf.py): global-array assembly + the transfer
        # enqueue. device_put is async, so this times dispatch, not the
        # DMA itself — a SYNCHRONOUS h2d bottleneck (transfer backlog
        # applying back-pressure here) still shows up as this stage
        # dominating the split.
        with perf_lib.stage("h2d"):
            return {
                k: jax.make_array_from_process_local_data(sharding, v)
                for k, v in b.items()
            }

    buf: deque = deque()
    try:
        for b in host_batches:
            buf.append(to_device(b))
            if len(buf) >= depth:
                yield buf.popleft()
        while buf:
            yield buf.popleft()
    finally:
        close = getattr(host_batches, "close", None)
        if close is not None:
            close()


def build_input_pipeline(dataset, data_cfg, mesh, *, train: bool,
                         batch_axes=("data", "fsdp"), sync_check_every: int = 0,
                         num_hosts: int | None = None,
                         host_id: int | None = None):
    """Convenience: loader + producer thread + device prefetch.

    Returns (loader, epoch_fn) where epoch_fn(epoch) yields device-resident
    global batches. ``sync_check_every`` enables the cross-host input
    divergence check (SURVEY §5.2) on HOST-LOCAL batches, before global
    array assembly — after assembly all hosts see identical global shapes by
    construction, so checking there would be vacuous. The check runs on the
    consumer thread (collectives must not race the step's collectives).
    ``num_hosts``/``host_id`` override the jax process world for the
    loader's sharding — the elastic-reshard path (``data.elastic_shards``)
    passes the LAUNCHER world here, recomputed per restart generation.
    """
    if getattr(data_cfg, "loader", "threads") == "grain":
        from pytorch_distributed_train_tpu.data.grain_pipeline import (
            GrainHostDataLoader,
        )

        loader = GrainHostDataLoader(dataset, data_cfg, train=train,
                                     num_hosts=num_hosts, host_id=host_id)
    else:
        loader = HostDataLoader(dataset, data_cfg, train=train,
                                num_hosts=num_hosts, host_id=host_id)
    # read by the trainer's log window; mirrored to /metrics by split
    loader.stall_stats = StallStats(split="train" if train else "eval")

    def epoch_fn(epoch: int, start_batch: int = 0) -> Iterator[dict]:
        host_iter = iter(_Producer(loader.epoch(epoch, start_batch),
                                   depth=max(2, data_cfg.prefetch),
                                   stats=loader.stall_stats))
        if sync_check_every:
            from pytorch_distributed_train_tpu.utils.debug import check_input_sync

            def checked(it):
                for i, b in enumerate(it):
                    if i % sync_check_every == 0:
                        check_input_sync(b)
                    yield b

            host_iter = checked(host_iter)
        return device_prefetch(
            host_iter, mesh, batch_axes=batch_axes, depth=data_cfg.prefetch
        )

    return loader, epoch_fn
