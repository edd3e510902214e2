"""Grain-backed host loader — the multiprocess alternative to the threaded
HostDataLoader (SURVEY C17: torch's DataLoader runs worker *processes*,
torch:utils/data/_utils/worker.py:244; Grain is the JAX-ecosystem loader
with the same process-pool design).

Selected via ``DataConfig.loader = "grain"``. Duck-types HostDataLoader
(``steps_per_epoch``, ``epoch(epoch, start_batch)``) so the rest of the
input pipeline — producer thread, HBM prefetch, sync checks — is shared.

Reuses the datasets unchanged. The element grain moves is a whole HOST
BATCH of record indices (round-5 restructure of the "grain gap"):
batching lives in the SOURCE, before grain's worker sharding,
so batch composition is invariant to worker_count and a mid-epoch
resume slices the epoch order at exact batch boundaries (see
_BatchIndexSource for why operation-level gp.Batch cannot give
either). One map call per batch also amortizes grain's per-element
machinery by the batch size and hands the native batch decoder
(native/jpegdec.cpp) real batches. Augment randomness does NOT use
Grain's sampler-position rng: item-style records key their rng on
(seed, epoch, record index) — bit-exact under ANY regrouping — and
``get_batch`` loads on (seed, epoch, the batch's full index tuple),
which the source's batch-boundary invariant makes resume-exact.

Sharding/shuffle semantics mirror DistributedSampler (C16): per-epoch
reseeded shuffle, host-sharded with drop_remainder — though the shuffle
permutation itself is Grain's, not byte-identical to data/sampler.py.
"""

from __future__ import annotations

import os
from typing import Iterator

import jax
import numpy as np

from pytorch_distributed_train_tpu.obs.spans import span as _span

# Process-local decode pool for per-record get_item calls inside the
# batched map (see _make_load_transform). A module global, NOT transform
# state: MapTransform instances pickle into grain worker processes and a
# ThreadPoolExecutor does not — each worker process (or the in-process
# worker_count=0 path) lazily builds its own. Pid-guarded: the shared-
# memory decode pool (data/workers.py) FORKS its workers, and executor
# threads never survive a fork.
_DECODE_POOL = None


def _decode_pool():
    global _DECODE_POOL
    if _DECODE_POOL is None or _DECODE_POOL[0] != os.getpid():
        from concurrent.futures import ThreadPoolExecutor

        from pytorch_distributed_train_tpu.data import workers as workers_lib

        # python_thread_budget, NOT process_thread_budget: this pool
        # runs PIL item decode (GIL-holding Python framing), and the
        # native budget's x2 I/O allowance composed pathologically with
        # data.mp_workers — N forked workers x 2x-their-core-share PIL
        # threads oversubscribed the host into the pil_grain_mp8
        # regression (ISSUE 14 satellite).
        _DECODE_POOL = (os.getpid(), ThreadPoolExecutor(
            max_workers=workers_lib.python_thread_budget(
                min(8, os.cpu_count() or 1)),
            thread_name_prefix="grain-decode"))
    return _DECODE_POOL[1]


# Log each distinct clamp once per process — a per-epoch warning for the
# same configured count is noise, silence is an unexplained throughput
# drop (satellite: grain clamp fix, ISSUE 12).
_CLAMP_LOGGED: set = set()


def _effective_workers_gauge(loader: str):
    from pytorch_distributed_train_tpu.obs.registry import get_registry

    return get_registry().gauge(
        "input_effective_workers", labels={"loader": loader},
        help="effective input-pipeline worker count after host/pool "
             "clamping (processes; 0 = in-process loading)")


def bounded_workers(requested: int, avail: int | None = None, *,
                    pool_budget: int = 0) -> int:
    """Cap Grain worker PROCESSES by what the host can actually run.

    Worker processes exist to escape the GIL onto OTHER cores
    (torch:utils/data/_utils/worker.py:244 — same rationale); on a host
    with no core to spare they only add spawn+IPC contention against the
    consumer. Measured on this repo's 1-core sandbox: the uncapped
    process arm produced no batch within 550 s,
    while worker_count=0 (in-process loading, Grain's supported
    degenerate mode) streams fine. Cap = cpu_count - 1 (one core stays
    with the consumer/train loop), never more than requested.

    With the shared-memory pool enabled (``pool_budget`` > 0, from
    ``data.mp_workers``) the old 1-core clamp-to-zero is WRONG: the pool
    replaces grain's worker machinery outright — its workers block on a
    queue when idle instead of spinning grain's per-element IPC — so the
    effective count clamps against the POOL's own budget (floor 1).
    Either way the decision is logged once per distinct clamp and
    exposed as the ``input_effective_workers`` gauge.
    """
    if avail is None:
        avail = os.cpu_count() or 1
    if pool_budget > 0:
        bounded = max(1, min(requested, pool_budget)) if requested > 0 \
            else pool_budget
        why = (f"shared-memory pool budget {pool_budget} "
               f"(data.mp_workers; {avail} host core(s))")
    else:
        bounded = max(0, min(requested, avail - 1))
        why = (f"{avail} host core(s); worker processes need a spare "
               "core — 0 = in-process loading")
    if bounded != requested and (requested, bounded) not in _CLAMP_LOGGED:
        _CLAMP_LOGGED.add((requested, bounded))
        import warnings

        warnings.warn(
            f"grain num_workers={requested} clamped to {bounded} ({why})")
    _effective_workers_gauge("grain").set(bounded)
    return bounded


class _BatchIndexSource:
    """Grain source over whole BATCHES of record indices.

    Batching happens HERE — in the source, BEFORE grain's worker
    sharding — which is the load-bearing design choice: grain
    stride-shards the element stream across worker processes and runs
    `gp.Batch` inside each worker, so batches formed by an operation
    are composed of worker-strided subsequences and their composition
    CHANGES with worker_count (and a mid-epoch resume that slices the
    source contiguously reproduces neither the set nor the order the
    uninterrupted run consumed). With one-element-per-batch sources,
    workers stride over batches, grain's deterministic interleave
    restores source order, and batch b is ALWAYS epoch-order slice
    [b*B:(b+1)*B] — invariant to worker_count, exactly what
    epoch(start_batch=) slicing assumes."""

    def __init__(self, order: np.ndarray, batch: int):
        self._order = order
        self._batch = batch

    def __len__(self) -> int:
        return (len(self._order) + self._batch - 1) // self._batch

    def __getitem__(self, b: int) -> np.ndarray:
        return self._order[b * self._batch:(b + 1) * self._batch]


def load_batch_payload(dataset, item_style: bool, train: bool,
                       seed: int, epoch: int, idx: np.ndarray) -> dict:
    """Load ONE host batch under the GRAIN rng-keying convention — the
    single definition shared by grain's MapTransform (in grain worker
    processes or in-process under worker_count=0) and the shared-memory
    decode pool (data/workers.py), so the two process models cannot
    drift byte-wise.

    Batched (get_batch) rng is keyed on (seed, epoch, the batch's FULL
    index tuple) — the full tuple, not idx[0], because weighted sampling
    with replacement can repeat a first element across different
    batches. Item-style records keep per-RECORD keying (seed, epoch,
    record index): each record's augment draws are bit-exact regardless
    of how batches regroup."""
    idx = np.asarray(idx, np.int64)
    # Retry/backoff + the `data.decode` fault point come from the
    # faults package (lazy import: worker processes rebuild their own
    # process-local schedule from the PDTT_FAULTS env var).
    from pytorch_distributed_train_tpu import faults as faults_lib

    # The span feeds span_seconds{name="data.grain.load_batch"} — the
    # decode wait is a scrapable histogram, so the worker_count=0
    # throughput question (ADVICE round 5) is answerable from /metrics
    # instead of re-profiling.
    with _span("data.grain.load_batch", records=int(len(idx))):
        if item_style:
            # Per-record decode fans out over a thread pool: under
            # worker_count=0 the round-5 batched-map restructure had
            # serialized what used to run on grain's read threads (PIL
            # decode releases the GIL). Per-record rng keying is
            # position-free, so thread scheduling cannot perturb
            # reproducibility. Substituted records (decode_with_retry's
            # last resort) keep the keying: record j's rng is always
            # (seed, epoch, j), wherever it lands.
            def _load(i):
                def load(j):
                    faults_lib.maybe_fire("data.decode")
                    return dataset.get_item(
                        int(j), np.random.default_rng(
                            np.random.SeedSequence(
                                (seed, epoch, int(j)))))

                return faults_lib.decode_with_retry(
                    load, int(i), len(dataset))

            items = list(_decode_pool().map(_load, idx))
            return {k: np.stack([it[k] for it in items])
                    for k in items[0]}

        def _load_batch():
            faults_lib.maybe_fire("data.decode")
            rng = np.random.default_rng(np.random.SeedSequence(
                (seed, epoch) + tuple(int(t) for t in idx)))
            return dataset.get_batch(idx, rng, train)

        return faults_lib.retry_call(_load_batch, point="data.decode")


def _make_load_transform(dataset, item_style: bool, train: bool,
                         seed: int, epoch: int):
    """One MapTransform per host BATCH (an index array element).

    get_batch datasets get ONE dataset call per batch — round-5
    profiling (tools/grain_profile.py) measured
    ~1.1 ms/record of pure grain machinery in the per-record
    formulation, and batch-of-1 calls starved the native batch decoder
    (native/jpegdec.cpp); whole-batch elements amortize the machinery
    by the batch size and hand the decoder real batches. Load + rng
    semantics live in :func:`load_batch_payload`."""
    import grain.python as gp

    class _LoadBatch(gp.MapTransform):
        def map(self, idx):
            return load_batch_payload(dataset, item_style, train, seed,
                                      epoch, idx)

    return _LoadBatch()


class GrainHostDataLoader:
    """Per-host loader over Grain worker processes."""

    def __init__(self, dataset, data_cfg, *, train: bool,
                 num_hosts: int | None = None, host_id: int | None = None):
        self.dataset = dataset
        self.train = train
        # NOTE: the defaults initialize the device backend (process_count
        # → jax.devices()); host-only callers (benches, tools) must pass
        # num_hosts/host_id explicitly so a pure-host data pipeline
        # never touches the device.
        self.num_hosts = (num_hosts if num_hosts is not None
                          else jax.process_count())
        self.host_id = host_id if host_id is not None else jax.process_index()
        global_batch = data_cfg.batch_size if train else (
            data_cfg.eval_batch_size or data_cfg.batch_size
        )
        if global_batch % self.num_hosts != 0:
            raise ValueError(
                f"global batch {global_batch} not divisible by "
                f"{self.num_hosts} hosts"
            )
        self.host_batch = global_batch // self.num_hosts
        self.seed = data_cfg.seed
        self.shuffle = train and data_cfg.shuffle
        # Shared-memory decode pool (data/workers.py): when enabled it
        # REPLACES grain's worker machinery — the in-process
        # worker_count=0 degenerate mode this loader was clamped into on
        # core-starved hosts — so the effective worker count clamps
        # against the pool's own budget, not cpu_count-1 (ISSUE 12
        # satellite: the grain bounded_workers fix).
        from pytorch_distributed_train_tpu.data import workers as workers_lib

        self._pool_budget = (
            workers_lib.pool_budget(getattr(data_cfg, "mp_workers", 0))
            if workers_lib.available() else 0)
        self.num_workers = bounded_workers(
            data_cfg.num_workers, pool_budget=self._pool_budget)
        self.decode_threads_per_worker = 0
        if self._pool_budget > 0 and getattr(dataset, "is_item_style",
                                             False):
            # mp pool + grain ITEM-style decode: each forked worker also
            # fans out a PIL decode thread pool. Uncapped that composed
            # pathologically (pil_grain_mp8 ran slower than plain
            # threads) — workers.python_thread_budget now clamps
            # each worker to its core share; surface the decision once
            # (log + gauge) so the throughput math is inspectable.
            avail = os.cpu_count() or 1
            per = workers_lib.pool_decode_threads(self.num_workers)
            self.decode_threads_per_worker = per
            total = per * self.num_workers
            from pytorch_distributed_train_tpu.obs.registry import (
                get_registry,
            )

            get_registry().gauge(
                "input_decode_threads", labels={"loader": "grain"},
                help="PIL decode threads per forked mp pool worker "
                     "after the core-share clamp").set(per)
            key = ("decode-threads", self.num_workers, per)
            if key not in _CLAMP_LOGGED:
                _CLAMP_LOGGED.add(key)
                import warnings

                warnings.warn(
                    f"grain + data.mp_workers item decode: "
                    f"{self.num_workers} worker(s) x {per} PIL decode "
                    f"thread(s) = {total} on {avail} host core(s) "
                    "(per-worker pool clamped to the core share — the "
                    "pil_grain_mp8 oversubscription fix)")
        self.mp_slots = getattr(data_cfg, "mp_slots", 0)
        self._mp_pool = None
        self.read_buffer = max(2, data_cfg.prefetch)
        self.weighted = None
        if train and getattr(data_cfg, "weighted_sampling", ""):
            # torch WeightedRandomSampler parity under the PROCESS loader
            # too: the weighted draw replaces Grain's uniform IndexSampler
            # by using the epoch's record order (host-sharded, seed+epoch
            # deterministic — data/sampler.py) as an explicit array
            # source, the same mechanism the mid-epoch resume path uses.
            # Augment-rng nuance vs the threads loader, per transform
            # shape: ITEM-style records drawn twice in an epoch (with
            # replacement) reuse the same per-record rng where the
            # threads loader draws fresh; BATCHED get_batch loads key
            # on the batch's full index tuple, so only an entirely
            # repeated batch repeats its draws. Construction/validation
            # shared with HostDataLoader (sampler.make_weighted_sampler).
            from pytorch_distributed_train_tpu.data.sampler import (
                make_weighted_sampler,
            )

            self.weighted = make_weighted_sampler(
                dataset, data_cfg, self.num_hosts, self.host_id)

    @property
    def steps_per_epoch(self) -> int:
        per_host = len(self.dataset) // self.num_hosts
        if self.train:
            return per_host // self.host_batch
        return (per_host + self.host_batch - 1) // self.host_batch

    def _sampler(self, epoch: int):
        import grain.python as gp

        # UNSHARDED on purpose (elastic resharding, docs/elastic.md):
        # grain's ShardOptions splits the record range into CONTIGUOUS
        # blocks and shuffles within each, so the set of records behind
        # global batch b would change with shard_count — a gang that
        # shrinks mid-epoch could then replay or skip records. One
        # GLOBAL shuffle (seed+epoch) with hosts taking strided
        # positions keeps the union of all hosts' batch b equal to the
        # same global slice at ANY world size, which is exactly the
        # invariant the mid-epoch start_batch fast-forward assumes.
        return gp.IndexSampler(
            num_records=len(self.dataset),
            shard_options=gp.NoSharding(),
            shuffle=self.shuffle,
            # per-epoch reshuffle ≡ DistributedSampler.set_epoch (C16)
            seed=self.seed + epoch,
            num_epochs=1,
        )

    def _epoch_order(self, epoch: int) -> np.ndarray:
        """This host's record order for the epoch, as one int64 array.

        Weighted sampling has it materialized already; otherwise it is
        enumerated from grain's IndexSampler (pure index math, no IO —
        ~O(n) python at iterator construction, overlapped with compile
        by the producer thread). An explicit order array is what lets
        batching live in the SOURCE (see _BatchIndexSource) and resume
        slice at exact batch boundaries. Host h takes positions
        h, h+world, ... of the GLOBAL shuffled stream (the
        DistributedSampler stride, C16), so the per-host order is a
        pure function of (seed, epoch, world, host) — shard_count
        changes reshard the SAME epoch-global order."""
        if self.weighted is not None:
            self.weighted.set_epoch(epoch)
            n = self.steps_per_epoch * self.host_batch
            return np.asarray(self.weighted.indices()[:n], np.int64)
        sampler = self._sampler(epoch)
        n = min(self.steps_per_epoch * self.host_batch,
                len(self.dataset) // self.num_hosts)
        return np.fromiter(
            (sampler[self.host_id + k * self.num_hosts].record_key
             for k in range(n)), np.int64, count=n)

    def close(self) -> None:
        """Release the shared-memory pool (bench/tests)."""
        if self._mp_pool is not None:
            self._mp_pool.close()
            self._mp_pool = None

    def _pad_tail(self, out: dict) -> dict:
        short = self.host_batch - len(next(iter(out.values())))
        if short > 0:
            # Pad the tail batch by wrapping — SPMD needs static shapes
            # (same invariant as HostDataLoader's eval-tail wrap).
            out = {
                k: np.concatenate(
                    [v, np.tile(v, (short // len(v) + 1,)
                                + (1,) * (v.ndim - 1))[:short]]
                )
                for k, v in out.items()
            }
        return out

    def _pool_load(self, task) -> dict:
        """One (epoch, idx-array) pool task → batch dict, under grain's
        rng-keying convention (load_batch_payload) — runs inside a
        forked decode worker; byte-identical to the grain path."""
        epoch, idx = task
        return load_batch_payload(
            self.dataset, getattr(self.dataset, "is_item_style", False),
            self.train, self.seed, epoch, idx)

    def _epoch_via_pool(self, epoch: int,
                        order: np.ndarray) -> Iterator[dict]:
        """Shared-memory pool path: same epoch-order batch slices as the
        grain source (_BatchIndexSource semantics), decoded in N forked
        workers. Batch b is ALWAYS epoch-order slice [b*B:(b+1)*B] —
        invariant to the worker count, resume-exact."""
        if self._mp_pool is None:
            from pytorch_distributed_train_tpu.data import (
                workers as workers_lib,
            )

            self._mp_pool = workers_lib.SharedMemoryWorkerPool(
                self._pool_load, self.num_workers, slots=self.mp_slots,
                post_fork=lambda: workers_lib.reset_thread_local_state(
                    self.dataset))
        n_batches = (len(order) + self.host_batch - 1) // self.host_batch
        tasks = ((epoch, order[b * self.host_batch:
                               (b + 1) * self.host_batch])
                 for b in range(n_batches))
        for out in self._mp_pool.run(tasks):
            yield self._pad_tail(
                {k: np.asarray(v) for k, v in out.items()})

    def epoch(self, epoch: int, start_batch: int = 0) -> Iterator[dict]:
        order = self._epoch_order(epoch)[start_batch * self.host_batch:]
        if self._pool_budget > 0:
            return self._epoch_via_pool(epoch, order)
        return self._epoch_grain(epoch, order)

    def _epoch_grain(self, epoch: int, order: np.ndarray) -> Iterator[dict]:
        import grain.python as gp

        source = _BatchIndexSource(order, self.host_batch)
        order_sampler = gp.IndexSampler(
            num_records=len(source), shuffle=False,
            seed=self.seed + epoch, num_epochs=1,
            shard_options=gp.NoSharding(),
        )
        ops = [_make_load_transform(
            self.dataset, getattr(self.dataset, "is_item_style", False),
            self.train, self.seed, epoch)]
        read = gp.ReadOptions(
            num_threads=max(1, min(16, self.read_buffer)),
            prefetch_buffer_size=self.read_buffer)
        loader = gp.DataLoader(
            data_source=source,
            sampler=order_sampler,
            operations=ops,
            worker_count=self.num_workers,
            read_options=read,
        )
        # Stage attribution (obs/perf.py): with worker PROCESSES the
        # decode/augment stage timers fire inside the workers where this
        # process can't see them, so the host-side wait on the IPC
        # stream is attributed to `read` (fetching finished records).
        # With worker_count=0 the map runs inline in next() and the
        # dataset's own read/decode/augment timers already cover it —
        # timing the wait too would double-count every stage.
        from pytorch_distributed_train_tpu.obs import perf as perf_lib

        it = iter(loader)
        _done = object()
        while True:
            if self.num_workers > 0:
                with perf_lib.stage("read"):
                    batch = next(it, _done)
            else:
                batch = next(it, _done)
            if batch is _done:
                break
            yield self._pad_tail({k: np.asarray(v) for k, v in batch.items()})
