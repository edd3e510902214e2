#!/usr/bin/env python
"""bench.py — training-throughput benchmarks on the local TPU chip(s).

Default (the north-star, BASELINE.json:2): ResNet-50 ImageNet-shape
training, images/sec/chip. Prints ONE JSON line:
  {"metric": "resnet50_images_per_sec_per_chip", "value": N,
   "unit": "images/sec/chip", "vs_baseline": R}

vs_baseline compares against the first measured value recorded in
BENCH_BASELINE.json (the reference publishes no numbers: the first
instrumented run IS the baseline, ratio 1.0 that round).
Only the default configuration seeds/reads the baseline ratio; other
models/shapes report vs_baseline against their own recorded key when
present, else 1.0.

Secondary modes: ``--model llama`` / ``--model bert_base`` measure
tokens/sec/chip on a ~1B-param Llama (or BERT-base MLM) with the same
machinery.

Methodology: synthetic data (isolates device throughput from disk),
bf16 compute policy, full train step (fwd+bwd+optimizer) on all local
devices. Timing enqueues `--steps` steps back-to-back and ends the window
in `jax.block_until_ready` on the last step's outputs: they depend on the
(donated) state chain, so every enqueued step has executed by then. This
measures pipelined steady-state throughput the way a real training loop
runs. Every printed record names the device it ran on (`platform`,
`device_kind`, `device_count`); a device mode that finds no TPU exits
non-zero unless JAX_PLATFORMS=cpu was asked for explicitly.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

VISION = ("resnet18", "resnet50", "vit_b16")


def _ledger_append(record: dict) -> None:
    """Mirror a measured record into the perf ledger (obs/perf.py;
    docs/performance.md) — the append-only trajectory the regression
    gate (tools/perf_ledger --check) compares across rounds. Best-effort
    by contract: a read-only checkout still prints the record."""
    try:
        from pytorch_distributed_train_tpu.obs.perf import (
            PerfLedger,
            default_ledger_path,
        )

        PerfLedger(default_ledger_path(os.path.dirname(
            os.path.abspath(__file__)))).append_record(record,
                                                       source="bench")
    except Exception as e:
        print(f"bench.py: perf-ledger append failed "
              f"({type(e).__name__}: {e})", file=sys.stderr, flush=True)


def _device_stamp() -> dict:
    """The device a record was measured on, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _emit(record: dict, device_metric: bool = True) -> None:
    """Print the one-line JSON record, stamped with the device, and
    append it to the program's perf ledger when it is a real hardware
    measurement (TPU backend; host-pipeline benches pass False and are
    recorded unconditionally)."""
    record = {**record, **_device_stamp()}
    print(json.dumps(record), flush=True)
    if device_metric and record["platform"] != "tpu":
        return  # CPU smoke numbers must never pose as measurements
    _ledger_append(record)


def _require_tpu() -> None:
    """Device modes measure the chip: with no TPU, say so in one line and
    exit non-zero — unless the caller asked for the CPU explicitly
    (JAX_PLATFORMS=cpu: smoke runs and tests, whose records carry
    platform "cpu")."""
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return
    import jax

    try:
        platform = jax.devices()[0].platform
    except RuntimeError as e:
        raise SystemExit(f"bench.py: no TPU: {str(e).splitlines()[0]}")
    if platform != "tpu":
        raise SystemExit(
            f"bench.py: no TPU (JAX found platform {platform!r}); set "
            "JAX_PLATFORMS=cpu for a CPU smoke run")


# Process-start anchor for the bench goodput_pct denominator (module
# import ≈ process start; monotonic so NTP can't skew the split).
_T_MAIN0 = [time.monotonic()]


def pipeline_bench(args) -> None:
    """Host input-pipeline throughput (SURVEY hard part #1): sampler →
    batch augment/normalize → numpy batches, NO device involved. The
    augment is the fused C++ pass (native/imgops, internally multithreaded)
    on u8 storage; with the native build absent it falls back to the
    single-threaded numpy path — the metric name records which one ran so
    the numbers aren't conflated. (The per-item thread pool and the
    producer/prefetch stages don't apply to array-style datasets; what's
    measured here is the per-batch collate cost the train loop overlaps
    with device steps.) Deliberately does NOT seed/read BENCH_BASELINE.json:
    host throughput scales with whatever else shares the host cores, so a
    cross-run ratio would gate CI on machine load, not on code.

    ISSUE 12 arms (each its own metric name → fresh ledger trajectory):
    ``--packed-cache`` stores the dataset as packed shards and reads
    them through the mmap path (data/packed_cache.py);
    ``--device-augment`` ships raw u8 (host augment collapses to the
    read — the stall_split records the shift; the device-side cost is
    measured by the training benches, not here); ``--mp-workers N``
    collates in the shared-memory decode pool (data/workers.py)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # never touch the TPU here
    import shutil
    import tempfile

    import numpy as np

    from pytorch_distributed_train_tpu.config import DataConfig
    from pytorch_distributed_train_tpu.data.datasets import U8ImageDataset
    from pytorch_distributed_train_tpu.data.pipeline import HostDataLoader
    from pytorch_distributed_train_tpu.native import imgops

    size = args.image_size
    n = 4096
    batch = args.batch_per_chip or 256
    if batch * 2 > n:
        raise SystemExit(
            f"--batch-per-chip {batch} too large for the {n}-sample "
            "synthetic dataset (need >= 2 batches: 1 warmup + 1 timed)")
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (n, size, size, 3), dtype=np.uint8)
    labels = rng.integers(0, 1000, n).astype(np.int32)
    mean = np.array([0.485, 0.456, 0.406], np.float32)
    std = np.array([0.229, 0.224, 0.225], np.float32)
    tmp = None
    try:
        if args.packed_cache:
            from tools.pack_dataset import pack_arrays

            from pytorch_distributed_train_tpu.data.packed_cache import (
                PackedImageDataset,
            )

            tmp = tempfile.mkdtemp(prefix="bench-packed-")
            pack_arrays(images, labels, tmp, split="train",
                        shard_records=max(batch, n // 4),
                        meta={"mean": mean.tolist(), "std": std.tolist(),
                              "pad": 4})
            del images  # the mmap is the storage under test, not RAM
            ds = PackedImageDataset(tmp, augment=True, split="train",
                                    raw_u8=args.device_augment)
        else:
            ds = U8ImageDataset(images, labels, mean=mean, std=std,
                                augment=True, raw_u8=args.device_augment)
        cfg = DataConfig(batch_size=batch, mp_workers=args.mp_workers)
        loader = HostDataLoader(ds, cfg, train=True, num_hosts=1, host_id=0)

        it = loader.epoch(0)
        next(it)  # warm caches (and fork+prime the worker pool)
        t0 = time.perf_counter()
        seen = 0
        for b in it:
            seen += len(b["label"])
        wall = time.perf_counter() - t0
        loader.close()
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    native = "native" if imgops.available() else "numpy"
    parts = ["input_pipeline"]
    if args.packed_cache:
        parts.append("packed")
    parts.append("rawu8" if args.device_augment else native)
    if loader.mp_workers > 0:
        parts.append(f"mp{loader.mp_workers}")
    record = {
        "metric": "_".join(parts) + "_images_per_sec",
        "value": round(seen / wall, 2),
        "unit": "images/sec (host)",
        "vs_baseline": 1.0,
    }
    if loader.mp_workers > 0:
        record["mp_workers"] = loader.mp_workers
    from pytorch_distributed_train_tpu.obs import perf as perf_lib

    split = perf_lib.get_input_stats().split()
    if split:
        record["stall_split"] = split
    _emit(record, device_metric=False)


def pipeline_decode_bench(args) -> None:
    """JPEG-decode input pipeline throughput (SURVEY §7.4.1 — the part
    `--model pipeline` deliberately excludes): synthetic photo-like JPEGs
    in a WebDataset tar shard → TarShardImageDataset → the configured
    loader, full decode + RandomResizedCrop + flip + normalize per image.
    ``--decoder native`` routes through native/jpegdec.cpp (libjpeg batch
    decode in C++ threads); ``pil`` is the per-item PIL path. The metric
    name records decoder AND loader actually used. Never touches a device
    and never seeds a baseline key (host-load-dependent, like the collate
    bench)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # never touch the TPU here
    import shutil
    import tempfile

    import numpy as np

    from pytorch_distributed_train_tpu.config import DataConfig
    from pytorch_distributed_train_tpu.data.datasets import (
        TarShardImageDataset,
        write_jpeg_tar_shard,
    )

    n = 2048
    batch = args.batch_per_chip or 256
    if batch * 2 > n:
        raise SystemExit(
            f"--batch-per-chip {batch} too large for the {n}-sample "
            "synthetic shard (need >= 2 batches: 1 warmup + 1 timed)")
    tmp = tempfile.mkdtemp(prefix="bench-decode-")
    try:
        rng = np.random.default_rng(0)
        shard = os.path.join(tmp, "bench-000000.tar")
        write_jpeg_tar_shard(shard, n, rng)
        workers = args.workers or (os.cpu_count() or 1)
        ds = TarShardImageDataset(shard, args.image_size, train=True,
                                  native_decode=args.decoder == "native",
                                  decode_threads=workers)
        decoder = "native" if ds.native_decode else "pil"
        if args.decoder == "native" and decoder != "native":
            raise SystemExit("--decoder native requested but the jpegdec "
                             "library is unavailable")
        cfg = DataConfig(batch_size=batch, loader=args.loader,
                         num_workers=workers, mp_workers=args.mp_workers)
        if args.loader == "grain":
            from pytorch_distributed_train_tpu.data.grain_pipeline import (
                GrainHostDataLoader,
            )

            # num_hosts/host_id EXPLICIT: the defaults call
            # jax.process_count(), which initializes the device backend —
            # a host-only bench must never touch the device.
            loader = GrainHostDataLoader(ds, cfg, train=True,
                                         num_hosts=1, host_id=0)
        else:
            from pytorch_distributed_train_tpu.data.pipeline import (
                HostDataLoader,
            )

            loader = HostDataLoader(ds, cfg, train=True, num_hosts=1,
                                    host_id=0)
        it = loader.epoch(0)
        next(it)  # warm caches / spin up workers
        t0 = time.perf_counter()
        seen = 0
        for b in it:
            seen += len(b["label"])
        wall = time.perf_counter() - t0
        close = getattr(loader, "close", None)
        if close is not None:
            close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if args.loader == "grain":
        # grain + pool: effective count is the pool-clamped num_workers
        mp_used = loader.num_workers if loader._pool_budget > 0 else 0
    else:
        mp_used = loader.mp_workers
    mp_sfx = f"_mp{mp_used}" if mp_used else ""
    record = {
        "metric": f"input_pipeline_decode_{decoder}_{args.loader}"
                  f"{mp_sfx}_images_per_sec",
        "value": round(seen / wall, 2),
        "unit": "images/sec (host)",
        "vs_baseline": 1.0,
    }
    if mp_used:
        record["mp_workers"] = mp_used
    per_worker = getattr(loader, "decode_threads_per_worker", 0)
    if per_worker:
        # Ledger note for the pil_grain_mp8 regression fix (ISSUE 14
        # satellite): the per-worker PIL decode-thread clamp is part of
        # this row's identity — rows before/after the clamp must be
        # tellable apart in the trajectory.
        record["decode_threads_per_worker"] = per_worker
        record["note"] = ("mp+grain item decode: per-worker PIL pool "
                          "clamped to the host core share "
                          "(workers.python_thread_budget)")
    # Staged attribution (obs/perf.py): which stage of the decode
    # pipeline the wall went to — the per-stage view of the host wall.
    from pytorch_distributed_train_tpu.obs import perf as perf_lib

    split = perf_lib.get_input_stats().split()
    if split:
        record["stall_split"] = split
    if args.loader == "grain":
        # The process-worker count actually used (host-core bounded —
        # grain_pipeline.bounded_workers): 0 = in-process mode on
        # core-starved hosts. Recorded so grain numbers from different
        # host shapes are never conflated.
        record["grain_workers"] = loader.num_workers
    _emit(record, device_metric=False)


def decode_bench(args) -> None:
    """KV-cache decode throughput (tokens/sec/chip) on the ~1B llama —
    the serving-side counterpart of the training bench. Prefills once
    (untimed), warms the single-token executable, then times N-1 pure
    decode steps driven directly. Never seeds a training baseline key."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_train_tpu import quant
    from pytorch_distributed_train_tpu.config import (
        ModelConfig,
        PrecisionConfig,
    )
    from pytorch_distributed_train_tpu.generate import build_decode_model
    from pytorch_distributed_train_tpu.models.registry import build_model

    if args.model != "llama":
        raise SystemExit("--decode-tokens supports --model llama")
    if args.decode_tokens < 2:
        raise SystemExit("--decode-tokens must be >= 2 (timing needs at "
                         "least one pure decode step after the warmup one)")
    bpc = args.batch_per_chip or 8
    new_tokens = args.decode_tokens
    prompt_len = 16 if args.tiny else 128
    if prompt_len + new_tokens + 1 > args.seq_len:
        # generate()'s length guard doesn't run on this direct-step path;
        # overflowing the cache would silently clamp writes into the last
        # slot and time a semantically broken decode.
        raise SystemExit(
            f"prompt ({prompt_len}) + decode tokens ({new_tokens}) + 1 "
            f"exceeds --seq-len {args.seq_len}; raise --seq-len")
    dims = _llama_dims(args.tiny)
    model_cfg = ModelConfig(
        name="llama", **dims,
        max_seq_len=min(args.seq_len, prompt_len + new_tokens + 1),
        attention_impl="xla",  # decode steps are single-token; dense is right
        kv_cache_dtype=args.kv_cache_dtype,
    )
    precision = PrecisionConfig(compute_dtype="bfloat16")
    train_model = build_model(model_cfg, precision)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, dims["vocab_size"],
                                          (bpc, prompt_len)), jnp.int32)
    params = jax.jit(
        lambda r: train_model.init({"params": r}, ids[:1, :8],
                                   train=False)["params"]
    )(jax.random.PRNGKey(0))
    if args.quantize:
        params = jax.jit(lambda p: quant.quantize_tree_named(
            p, args.quantize))(params)
    model = build_decode_model(model_cfg, precision)

    # Drive the single-token step loop directly: prefill once (untimed),
    # warm the decode executable, then time N pure decode steps — no
    # noisy two-run subtraction.
    from pytorch_distributed_train_tpu.generate import (
        _decode_step,
        init_cache,
    )

    cache = init_cache(model, bpc)
    logits, cache = _decode_step(model, params, cache, ids)  # prefill
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    logits, cache = _decode_step(model, params, cache, nxt)  # compile step
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    for _ in range(new_tokens - 1):
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        logits, cache = _decode_step(model, params, cache, nxt)
    jax.block_until_ready(logits)  # end of the donated-cache chain
    wall = time.perf_counter() - t0
    # Single-device generation (no mesh) — per-chip IS the run's rate.
    per_chip = bpc * (new_tokens - 1) / wall
    suffix = (f"_{args.quantize}" if args.quantize else "") + (
        "_tiny" if args.tiny else "")
    record = {
        "metric": f"llama_decode{suffix}_tokens_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": 1.0,
    }
    # MBU — decode's utilization measure (bandwidth-bound, so MFU would
    # mislead): bytes moved per token (weights/B + KV read at the run's
    # average fill) over the chip's HBM peak. The quantization levers
    # change the numerator exactly as documented (utils/flops.py).
    from pytorch_distributed_train_tpu.utils import flops as flops_lib

    wbytes = {"int8": 1.0, "int4": 0.5}.get(args.quantize, 2.0)
    kvbytes = 1.0 if args.kv_cache_dtype.startswith("float8") else 2.0
    bpt = flops_lib.decode_bytes_per_token(
        model_cfg, batch=bpc, avg_position=prompt_len + new_tokens / 2,
        weight_bytes_per_param=wbytes, kv_bytes_per_elt=kvbytes)
    mbu = flops_lib.mbu_pct(per_chip, bpt,
                            flops_lib.device_hbm_bandwidth())
    record["model_mb_per_token"] = round(bpt / 1e6, 3)
    if mbu is not None:
        record["mbu_pct"] = round(mbu, 2)
    _emit(record)


def _llama_dims(tiny: bool) -> dict:
    """The ~1.1B llama shape the decode/spec/serve benches share (tiny:
    CI-smoke sizes — never comparable to real numbers)."""
    return (dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                 num_kv_heads=4, mlp_dim=128) if tiny else
            dict(vocab_size=32000, hidden_size=2048, num_layers=16,
                 num_heads=16, num_kv_heads=16, mlp_dim=5504))


def serve_bench(args) -> None:
    """Continuous-batching serving throughput (serving.ContinuousBatcher):
    ``--serve N`` requests with MIXED prompt lengths and budgets drain
    through ``--batch-per-chip`` slots (default 8). The aggregate
    generated-tokens/sec is the serving rate a lockstep generate() cannot
    reach on this workload — lockstep pads every request to the longest
    prompt and keeps finished rows in the batch until the longest budget
    drains. ``occupancy`` (live-slot fraction per step) reports how full
    the batch stayed. Never seeds a training baseline key."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_train_tpu.config import (
        ModelConfig,
        PrecisionConfig,
    )
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.serving import ContinuousBatcher

    from pytorch_distributed_train_tpu import quant

    if args.model != "llama":
        raise SystemExit("--serve supports --model llama")
    n_req = args.serve
    slots = args.batch_per_chip or 8
    dims = _llama_dims(args.tiny)
    p_lo, p_hi = (4, 12) if args.tiny else (32, 256)
    b_lo, b_hi = (2, 6) if args.tiny else (16, 96)
    turns = max(args.serve_turns, 1)
    if turns > 1 and args.serve_prefix:
        raise SystemExit("--serve-turns and --serve-prefix are separate "
                         "workloads; pick one")
    # chat workload: later turns are shorter than openers
    t_lo, t_hi = (2, 6) if args.tiny else (16, 64)
    prefix_len = args.serve_prefix
    if prefix_len and slots < 2:
        raise SystemExit(
            "--serve-prefix needs --batch-per-chip >= 2: the template "
            "occupies one slot for the whole run")
    # headroom = the longest request the workload can draw (opener/user
    # turn + budget); the cap guards HBM, not correctness — refuse
    # prefixes that would eat the headroom rather than truncate silently
    max_len = (32 * turns + prefix_len if args.tiny
               else min(4096, 512 * turns) + prefix_len)
    if not args.tiny and max_len > 8192:
        raise SystemExit(
            f"--serve-prefix {prefix_len} pushes max_seq_len to "
            f"{max_len} (> 8192); lower the prefix length")
    model_cfg = ModelConfig(name="llama", **dims, max_seq_len=max_len,
                            attention_impl="xla",
                            kv_cache_dtype=args.kv_cache_dtype)
    precision = PrecisionConfig(compute_dtype="bfloat16")
    train_model = build_model(model_cfg, precision)
    params = jax.jit(
        lambda r: train_model.init({"params": r},
                                   jnp.zeros((1, 8), jnp.int32),
                                   train=False)["params"]
    )(jax.random.PRNGKey(0))
    if args.quantize:
        params = jax.jit(lambda p: quant.quantize_tree_named(
            p, args.quantize))(params)

    rng = np.random.default_rng(0)
    V = dims["vocab_size"]
    reqs = [(rng.integers(p_lo, p_hi + 1), rng.integers(b_lo, b_hi + 1))
            for _ in range(n_req)]
    extra_turns = [[(rng.integers(t_lo, t_hi + 1),
                     rng.integers(b_lo, b_hi + 1))
                    for _ in range(turns - 1)] for _ in range(n_req)]

    def make_batcher():
        if args.serve_paged:
            from pytorch_distributed_train_tpu.serving import (
                PagedContinuousBatcher,
            )

            return PagedContinuousBatcher(
                model_cfg, precision, params, slots=slots,
                page_size=args.serve_paged, spec_k=args.serve_spec)
        return ContinuousBatcher(model_cfg, precision, params, slots=slots,
                                 spec_k=args.serve_spec)

    def run_prefix_workload(b) -> int:
        """Shared-system-prompt workload: every request = prefix_len
        system tokens + its own user turn. Fork arm: ONE preload serves
        all requests; resend arm: each request re-prefills
        system+user."""
        system = list(rng.integers(0, V, prefix_len))
        sid = None if args.serve_resend else b.preload(system)
        for i in range(n_req):
            user = list(rng.integers(0, V, int(reqs[i][0])))
            if args.serve_resend:
                b.submit(system + user, int(reqs[i][1]))
            else:
                b.submit(user, int(reqs[i][1]), prefix=sid)
        n = 0
        for c in b.run():
            assert c.finish_reason == "length", c.finish_reason
            n += 1
        assert n == n_req
        return b.stats["generated_tokens"]

    def run_workload(b) -> int:
        """Drive the full (possibly multi-turn) workload; returns total
        generated tokens. Multi-turn: sessions resume by default; with
        --serve-resend each turn re-prefills the FULL history instead
        (the no-session baseline the session arm is measured against)."""
        conv_of_uid: dict[int, int] = {}
        turn_of_conv = [0] * n_req
        history = [list(rng.integers(0, V, int(reqs[i][0])))
                   for i in range(n_req)]
        for i in range(n_req):
            uid = b.submit(history[i], int(reqs[i][1]),
                           keep=turns > 1 and not args.serve_resend)
            conv_of_uid[uid] = i
        remaining = n_req * turns
        while remaining:
            for c in b.step():
                i = conv_of_uid.pop(c.uid)
                remaining -= 1
                t = turn_of_conv[i] = turn_of_conv[i] + 1
                if t >= turns:
                    continue
                n_turn, budget = extra_turns[i][t - 1]
                turn_toks = list(rng.integers(0, V, int(n_turn)))
                last = t >= turns - 1
                if args.serve_resend:
                    history[i] += c.tokens + turn_toks
                    uid = b.submit(history[i], int(budget))
                else:
                    uid = b.submit(turn_toks, int(budget),
                                   keep=not last, session=c.session)
                conv_of_uid[uid] = i
        return b.stats["generated_tokens"]

    # Warm EXACTLY the executables the timed run will hit. The workload's
    # submit lengths are deterministic a priori — every request
    # length-finishes (no eos), so turn t's history is opener +
    # sum(budgets + turn lengths so far) — which makes the prefill and
    # resume bucket sets computable before running anything. Executables
    # cache across batchers (structurally equal static module args), so
    # compiles land here, not inside the timed A/B (which would skew the
    # session-vs-resend comparison by unequal compile time).
    prefill_lens, resume_lens, fork_lens = set(), set(), set()
    if prefix_len:
        if args.serve_resend:
            prefill_lens = {prefix_len + int(n) for n, _ in reqs}
        else:
            prefill_lens = {prefix_len}
            fork_lens = {int(n) for n, _ in reqs}  # forked turn ingests
    else:
        for i in range(n_req):
            hist, budget = int(reqs[i][0]), int(reqs[i][1])
            prefill_lens.add(hist)
            for n_turn, next_budget in extra_turns[i]:
                if args.serve_resend:
                    hist += budget + int(n_turn)
                    prefill_lens.add(hist)
                    budget = int(next_budget)
                else:
                    resume_lens.add(1 + int(n_turn))
    warm = make_batcher()
    for bucket in sorted({warm._bucket(n) for n in prefill_lens}):
        warm.submit(rng.integers(0, V, bucket), 2)
    list(warm.run())
    if resume_lens:
        # chain resumes on one parked session, one per DISTINCT resume
        # bucket (turn length bucket-1 → ingest 1+len fills it exactly)
        uid = warm.submit(rng.integers(0, V, 4), 2, keep=True)
        for bucket in sorted({warm._bucket(n) for n in resume_lens}):
            done = {c.uid: c for c in warm.run()}
            uid = warm.submit(rng.integers(0, V, bucket - 1), 2,
                              keep=True, session=done[uid].session)
        list(warm.run())
    if fork_lens:
        # warm the fork-continuation buckets off one throwaway template
        # (fork ingest is the turn alone: templates carry no unconsumed
        # token, so bucket(len) == the timed executable's shape)
        wsid = warm.preload(rng.integers(0, V, 4))
        for bucket in sorted({warm._bucket(n) for n in fork_lens}):
            warm.submit(rng.integers(0, V, bucket), 2, prefix=wsid)
        list(warm.run())

    b = make_batcher()
    t0 = time.perf_counter()
    total = run_prefix_workload(b) if prefix_len else run_workload(b)
    wall = time.perf_counter() - t0
    # admission tokens: every REQUEST prefill/resume/fork samples one
    # token outside a batched step; preloads prefill but admit nothing
    admissions = (b.stats["prefills"] - b.stats["preloads"]
                  + b.stats["resumes"] + b.stats["forks"])
    occupancy = (b.stats["generated_tokens"] - admissions
                 ) / max(b.stats["slot_token_slots"], 1)
    suffix = (f"_{args.quantize}" if args.quantize else "") + (
        "_tiny" if args.tiny else "")
    arm = ""
    if turns > 1:
        arm = "_chat_resend" if args.serve_resend else "_chat"
    elif prefix_len:
        arm = "_prefix_resend" if args.serve_resend else "_prefix"
    if args.serve_spec:
        arm += f"_spec{args.serve_spec}"
    if args.serve_paged:
        arm += f"_paged{args.serve_paged}"
    _emit({
        "metric": f"llama_serve{arm}{suffix}_tokens_per_sec_per_chip",
        "value": round(total / wall, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": 1.0,
        "requests": n_req,
        "turns": turns,
        "prefix_len": prefix_len,
        "slots": slots,
        "prefills": b.stats["prefills"],
        "resumes": b.stats["resumes"],
        "forks": b.stats["forks"],
        "occupancy": round(occupancy, 3),
    })


def spec_bench(args) -> None:
    """Speculative-decoding throughput (B=1, latency regime). Two arms:

    - default: a quarter-ish-size RANDOM draft — acceptance ~0, so this is
      the overhead FLOOR (worst case: all speculation wasted);
    - ``--spec-self``: draft == target — acceptance 1, the machinery
      CEILING (k+1 committed tokens per verify at full draft cost).

    A trained/distilled draft lands between the two; compare against the
    ``llama_decode`` metric (note that one is B=8). Never seeds a
    baseline key."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_train_tpu.config import (
        ModelConfig,
        PrecisionConfig,
    )
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.speculative import (
        speculative_generate,
    )

    if args.model != "llama":
        raise SystemExit("--speculative supports --model llama")
    if args.prompt_lookup and args.spec_self:
        raise SystemExit("--prompt-lookup has no draft model to self-pair")
    k = args.speculative
    new_tokens = args.decode_tokens or 64
    prompt_len = 16 if args.tiny else 128
    dims = _llama_dims(args.tiny)
    d_dims = (dict(vocab_size=512, hidden_size=32, num_layers=1,
                   num_heads=2, num_kv_heads=2, mlp_dim=64) if args.tiny
              else dict(vocab_size=32000, hidden_size=512, num_layers=4,
                        num_heads=8, num_kv_heads=8, mlp_dim=1376))
    max_len = prompt_len + new_tokens + k + 2
    cfg = ModelConfig(name="llama", **dims, max_seq_len=max_len,
                      kv_cache_dtype=args.kv_cache_dtype,
                      attention_impl="xla")
    precision = PrecisionConfig(compute_dtype="bfloat16")

    def init_params(c, seed):
        m = build_model(c, precision)
        return jax.jit(lambda r: m.init(
            {"params": r}, jnp.zeros((1, 8), jnp.int32),
            train=False)["params"])(jax.random.PRNGKey(seed))

    params = init_params(cfg, 0)
    if args.prompt_lookup:
        draft_cfg = draft_params = None
        arm = f"plookup_n{args.prompt_lookup}"
    elif args.spec_self:
        draft_cfg, draft_params, arm = cfg, params, "self"
    else:
        draft_cfg = ModelConfig(name="llama", **d_dims, max_seq_len=max_len,
                                kv_cache_dtype=args.kv_cache_dtype,
                                attention_impl="xla")
        draft_params, arm = init_params(draft_cfg, 1), "randdraft"
    rng0 = np.random.default_rng(0)
    if args.prompt_lookup and args.plookup_periodic:
        # repetition-heavy prompt: the regime prompt lookup exists for
        # (summarization/edit/RAG workloads echo their context) — a
        # periodic pattern gives matches every round; acceptance is then
        # up to the model
        pat = rng0.integers(0, dims["vocab_size"], 8)
        prompt = jnp.asarray(
            np.tile(pat, prompt_len // 8 + 1)[None, :prompt_len], jnp.int32)
        arm += "_periodic"
    else:
        prompt = jnp.asarray(
            rng0.integers(0, dims["vocab_size"], (1, prompt_len)),
            jnp.int32)
    # warm every executable (prefills, draft steps, verify, accept);
    # capped at new_tokens so the warmup horizon fits the cache the
    # timed run sized (max_len above)
    warm_tokens = min(max(2 * k, 4), new_tokens)

    def run(n_toks, with_stats=False):
        if args.prompt_lookup:
            from pytorch_distributed_train_tpu.speculative import (
                prompt_lookup_generate,
            )

            return prompt_lookup_generate(
                cfg, precision, params, prompt, n_toks, k=k,
                ngram=args.prompt_lookup, temperature=0.0,
                return_stats=with_stats)
        return speculative_generate(
            cfg, precision, params, draft_cfg, draft_params, prompt,
            n_toks, k=k, temperature=0.0, return_stats=with_stats)

    run(warm_tokens)
    t0 = time.perf_counter()
    out, stats = run(new_tokens, with_stats=True)
    wall = time.perf_counter() - t0
    suffix = "_tiny" if args.tiny else ""
    record = {
        "metric": f"llama_spec_{arm}_k{k}{suffix}_tokens_per_sec",
        "value": round((out.shape[1] - prompt_len) / wall, 2),
        "unit": "tokens/sec (B=1)",
        "vs_baseline": 1.0,
        "accept_rate": round(stats["accept_rate"], 4),
        "tokens_per_round": round(stats["tokens_per_round"], 3),
    }
    if "match_rate" in stats:
        record["match_rate"] = round(stats["match_rate"], 3)
    _emit(record)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   help="resnet18|resnet50|vit_b16|llama|bert_base|pipeline")
    p.add_argument("--batch-per-chip", type=int, default=0,
                   help="0 → model default (128 vision, 8 llama, 32 bert)")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--remat-policy", default="full",
                   choices=["full", "dots", "dots_no_batch"],
                   help="llama only: what block remat keeps resident "
                        "(models/remat.py)")
    p.add_argument("--fused-head", action="store_true",
                   help="llama only: fused chunked LM-head loss "
                        "(model.fused_lm_loss) — (B,S,V) logits never "
                        "materialize.")
    p.add_argument("--optimizer", default="",
                   help="override the model's default optimizer (llama: "
                        "adamw; bert: lamb; vision: momentum) — e.g. "
                        "adafactor to probe optimizer-state HBM headroom")
    p.add_argument("--moment-dtype", default="",
                   help="optimizer moment storage dtype ('' = fp32; "
                        "bfloat16 halves adam/adamw/lamb first-moment HBM)")
    p.add_argument("--decode-tokens", type=int, default=0,
                   help="llama only: measure KV-cache DECODE throughput "
                        "instead of training — generate this many tokens "
                        "per sequence (timed after a warmup generation)")
    p.add_argument("--speculative", type=int, default=0, metavar="K",
                   help="llama only: speculative-decoding bench with "
                        "speculation depth K (B=1; see spec_bench)")
    p.add_argument("--serve", type=int, default=0, metavar="N_REQUESTS",
                   help="llama only: continuous-batching serving bench — "
                        "drain N mixed-length requests through "
                        "--batch-per-chip slots (see serve_bench)")
    p.add_argument("--serve-turns", type=int, default=1, metavar="T",
                   help="with --serve: chat workload — each request is a "
                        "T-turn conversation resumed via KV sessions")
    p.add_argument("--serve-resend", action="store_true",
                   help="with --serve-turns/--serve-prefix: re-prefill "
                        "instead of resuming/forking (the no-cache "
                        "baseline the session/prefix arms beat)")
    p.add_argument("--serve-spec", type=int, default=0, metavar="K",
                   help="with --serve: prompt-lookup speculative serving "
                        "(K proposals per row per step; random-token "
                        "workloads measure the overhead floor — real "
                        "text with repetition measures the win)")
    p.add_argument("--serve-paged", type=int, default=0, metavar="PAGE",
                   help="with --serve: PAGED KV cache with PAGE-token "
                        "blocks (dense-equivalent pool; measures the "
                        "paging overhead/win vs the per-slot "
                        "reservation at identical workload)")
    p.add_argument("--serve-prefix", type=int, default=0, metavar="LEN",
                   help="with --serve: all requests share a LEN-token "
                        "system prompt, served via ONE preloaded "
                        "template forked per request (--serve-resend: "
                        "re-prefill system+user each time instead). The "
                        "template occupies one slot for the whole run — "
                        "the fork arm pays 1/slots occupancy to save "
                        "LEN-token prefills, so it wins when LEN is "
                        "large relative to user turns and slots")
    p.add_argument("--spec-self", action="store_true",
                   help="with --speculative: draft == target (acceptance-1 "
                        "machinery ceiling instead of the random-draft "
                        "floor)")
    p.add_argument("--prompt-lookup", type=int, default=0, metavar="NGRAM",
                   help="with --speculative K: draft-FREE n-gram prompt "
                        "lookup instead of a draft model "
                        "(speculative.prompt_lookup_generate)")
    p.add_argument("--plookup-periodic", action="store_true",
                   help="with --prompt-lookup: repetition-heavy prompt "
                        "(the workload regime the technique targets) "
                        "instead of the random floor")
    p.add_argument("--kv-cache-dtype", default="",
                   choices=["", "bfloat16", "float8_e4m3fn", "float8_e5m2"],
                   help="decode/serve benches: KV-cache STORAGE dtype "
                        "(fp8 halves the per-step cache read)")
    p.add_argument("--quantize", default="", choices=["", "int8", "int4"],
                   help="decode bench: weight-only int8 (per-channel) or "
                        "int4 (group-wise) params (quant.py)")
    p.add_argument("--quant-training", default="", choices=["", "int8"],
                   help="llama training bench: AQT-style int8 QAT matmuls "
                        "(quant.int8_dot_general — int8 MXU path)")
    p.add_argument("--tiny", action="store_true",
                   help="decode bench: toy model sizes for CI smoke on CPU "
                        "(never comparable to real numbers)")
    p.add_argument("--pipeline-decode", action="store_true",
                   help="with --model pipeline: measure the JPEG-DECODE "
                        "pipeline (synthetic tar shard) instead of the "
                        "pre-decoded collate path")
    p.add_argument("--decoder", default="pil", choices=["pil", "native"],
                   help="decode bench: per-item PIL vs native libjpeg "
                        "batch decode (native/jpegdec.cpp)")
    p.add_argument("--loader", default="threads", choices=["threads", "grain"],
                   help="decode bench: host loader backend (SURVEY C17)")
    p.add_argument("--workers", type=int, default=0,
                   help="decode bench: loader workers (0 → cpu count)")
    p.add_argument("--mp-workers", type=int, default=0,
                   help="pipeline benches: shared-memory decode worker "
                        "PROCESSES (data/workers.py; 0 = in-process). "
                        "Clamped to cpu_count-1; metric name records the "
                        "effective count")
    p.add_argument("--packed-cache", action="store_true",
                   help="with --model pipeline: store the synthetic "
                        "dataset as packed pre-decoded shards "
                        "(tools/pack_dataset.py format) and read through "
                        "the mmap path (data/packed_cache.py)")
    p.add_argument("--device-augment", action="store_true",
                   help="with --model pipeline: host ships raw uint8 "
                        "(data.device_augment mode) — measures the host "
                        "side with the augment share collapsed into "
                        "device compute")
    p.add_argument("--stem", default="conv", choices=["conv", "space_to_depth"],
                   help="resnet ImageNet stem: space_to_depth is the exact "
                        "MXU-friendly 4x4/s1 rewrite (models/resnet.py)")
    p.add_argument("--offload-opt", action="store_true",
                   help="keep optimizer state in pinned HOST memory between "
                        "steps (ZeRO-Offload analogue; TPU backends only)")
    p.add_argument("--attention-impl", default="auto",
                   choices=["auto", "xla", "pallas", "chunked"],
                   help="LM attention backend. 'auto' picks the Pallas flash "
                        "kernel on a TPU where the shapes qualify "
                        "(ops/attention.py). 'chunked' is the pure-XLA "
                        "flash-style path: O(S*chunk) memory, compiles "
                        "everywhere.")
    # ---- ISSUE 14 compute-graph arms (each encodes into the metric
    # name -> fresh ledger trajectory; never seeds a canonical baseline)
    p.add_argument("--grad-accum", type=int, default=0, metavar="N",
                   help="microbatched train step: lax.scan over N "
                        "microbatches with accumulated grads "
                        "(train.grad_accum_steps; metric gains _gaN)")
    p.add_argument("--overlap-collectives", action="store_true",
                   help="shard_map DP step with per-bucket grad pmeans "
                        "inside the accumulation scan + the latency-"
                        "hiding XLA flag preset (metric gains _overlap)")
    p.add_argument("--grad-bucket-mb", type=int, default=25,
                   help="bucket cap for --overlap-collectives (DDP "
                        "bucket_cap_mb analogue)")
    p.add_argument("--fused-epilogue", action="store_true",
                   help="one-pass fused clip+update+gate epilogue "
                        "(ops/fused_update.py; metric gains _fusedep). "
                        "Needs an adamw/adam/sgd/momentum optimizer — "
                        "combine with --optimizer for lamb/adafactor "
                        "presets")
    args = p.parse_args()

    if args.overlap_collectives:
        # Scheduler preset must be in XLA_FLAGS before the FIRST jax
        # import in this process (config.py is jax-free). TPU backends
        # only — XLA:CPU/GPU reject unknown --xla_tpu_* flags FATALLY —
        # so gate on the platform actually resolving to TPU: an
        # explicit JAX_PLATFORMS naming tpu, or no request at all on a
        # host with libtpu installed (jax's default pick). A CPU smoke
        # of this arm still runs; it measures collective PLACEMENT,
        # not overlap.
        import importlib.util

        plat = os.environ.get("JAX_PLATFORMS", "")
        tpu_backend = "tpu" in plat or (
            plat == "" and importlib.util.find_spec("libtpu") is not None)
        if tpu_backend:
            from pytorch_distributed_train_tpu.config import (
                ensure_latency_hiding_flags,
            )

            ensure_latency_hiding_flags()

    if args.quant_training and (args.model != "llama" or args.decode_tokens):
        # Same convention as the Trainer guard: a silently-ignored knob
        # records fp numbers as an int8 measurement.
        raise SystemExit("--quant-training supports llama TRAINING only "
                         "(decode-side int8 is --quantize)")
    if args.model == "pipeline":
        if args.pipeline_decode:
            return pipeline_decode_bench(args)
        return pipeline_bench(args)
    # Every remaining mode measures the device.
    from pytorch_distributed_train_tpu.utils import compile_cache

    compile_cache.enable()
    _require_tpu()
    if args.serve:
        return serve_bench(args)
    if args.speculative:
        return spec_bench(args)
    if args.decode_tokens:
        return decode_bench(args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.config import (
        MeshConfig,
        ModelConfig,
        OptimConfig,
        PrecisionConfig,
    )
    from pytorch_distributed_train_tpu.losses import get_loss_fn
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.optim import make_optimizer
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
    from pytorch_distributed_train_tpu.parallel.partition import rules_for_model
    from pytorch_distributed_train_tpu.train_state import TrainState

    n_chips = jax.device_count()
    mesh = build_mesh(MeshConfig(data=-1))
    vision = args.model in VISION

    if vision:
        model_cfg = ModelConfig(name=args.model, num_classes=1000,
                                image_size=args.image_size, stem=args.stem,
                                attention_impl=args.attention_impl)
        loss_name = "softmax_xent"
        opt = OptimConfig(name="momentum", learning_rate=0.1,
                          schedule="constant", warmup_steps=0)
        bpc = args.batch_per_chip or 128
    elif args.model == "llama":
        # ~1.1B params: the largest shape that trains comfortably on one
        # v5e chip's HBM with remat; scales out via mesh config in train.py.
        model_cfg = ModelConfig(
            name="llama", vocab_size=32000, hidden_size=2048, num_layers=16,
            num_heads=16, num_kv_heads=16, mlp_dim=5504,
            max_seq_len=args.seq_len, remat=True,
            remat_policy=args.remat_policy,
            attention_impl=args.attention_impl,
            fused_lm_loss=args.fused_head,
            quant_training=args.quant_training,
        )
        loss_name = "fused_causal_lm_xent" if args.fused_head else "causal_lm_xent"
        opt = OptimConfig(name="adamw", learning_rate=3e-4,
                          schedule="constant", warmup_steps=0)
        bpc = args.batch_per_chip or 8
    elif args.model == "t5":
        # t5-small shapes (the t5_small preset): seq2seq throughput —
        # tokens counted as encoder source + decoder target per example.
        model_cfg = ModelConfig(
            name="t5", vocab_size=32128, hidden_size=512, num_layers=6,
            decoder_layers=6, num_heads=8, mlp_dim=2048,
            max_seq_len=min(args.seq_len, 512),
        )
        loss_name = "seq2seq_xent"
        opt = OptimConfig(name="adafactor", learning_rate=1e-2,
                          schedule="constant", warmup_steps=0)
        bpc = args.batch_per_chip or 64
    elif args.model == "bert_base":
        model_cfg = ModelConfig(
            name="bert_base", vocab_size=30522, hidden_size=768,
            num_layers=12, num_heads=12, mlp_dim=3072,
            max_seq_len=min(args.seq_len, 512),
            attention_impl=args.attention_impl,
        )
        # True masked-LM objective (BASELINE.json:10): 15% dynamic masking
        # with the 80/10/10 recipe via data.datasets.synthetic_mlm — the
        # measured workload now matches the spec (round 1 trained plain
        # next-token xent here).
        loss_name = "mlm_xent"
        opt = OptimConfig(name="lamb", learning_rate=1e-3,
                          schedule="constant", warmup_steps=0)
        bpc = args.batch_per_chip or 32
    else:
        raise SystemExit(f"unknown bench model {args.model!r}")

    if args.optimizer:
        opt = OptimConfig(name=args.optimizer, learning_rate=opt.learning_rate,
                          schedule="constant", warmup_steps=0)
    if args.moment_dtype:
        opt = dataclasses.replace(opt, moment_dtype=args.moment_dtype)

    model = build_model(model_cfg, PrecisionConfig(compute_dtype="bfloat16"))
    tx, lr_sched = make_optimizer(opt, total_steps=1000)
    rules = rules_for_model(args.model)
    seq = model_cfg.max_seq_len

    if args.overlap_collectives and args.offload_opt:
        # Same refusal as the trainer's: the shard_map step cannot
        # stage pinned-host opt state (an obscure sharding error — or a
        # meaningless measurement — otherwise).
        raise SystemExit("--overlap-collectives + --offload-opt is "
                         "unsupported (shard_map cannot stage host-"
                         "memory opt state)")

    fused_update = None
    if args.fused_epilogue:
        from pytorch_distributed_train_tpu.optim import make_fused_update

        # Raises with the reason for inexpressible optimizers (lamb/
        # adafactor presets) — same loud-knob convention as
        # --quant-training; pair with --optimizer to fuse those benches.
        fused_update = make_fused_update(opt, lr_sched)

    tgt_seq = seq // 4 if args.model == "t5" else 0  # t5_small's 512/128

    def init_state(rng):
        if vision:
            dummy = (jnp.zeros((2, args.image_size, args.image_size, 3)),)
        elif args.model == "t5":
            dummy = (jnp.zeros((2, seq), jnp.int32),
                     jnp.zeros((2, tgt_seq), jnp.int32))
        else:
            dummy = (jnp.zeros((2, seq), jnp.int32),)
        variables = model.init({"params": rng}, *dummy, train=False)
        return TrainState.create(params=variables["params"], tx=tx,
                                 batch_stats=variables.get("batch_stats", {}))

    rng = jax.random.PRNGKey(0)
    shape = jax.eval_shape(init_state, rng)
    sharding = steps_lib.state_shardings(mesh, rules, shape)
    opt_dev_sharding = sharding.opt_state
    if args.offload_opt:
        if jax.devices()[0].platform == "cpu":
            raise SystemExit(
                "--offload-opt needs a TPU backend — the CPU backend "
                "cannot execute host-memory placement "
                "(annotate_device_placement)")
        sharding = steps_lib.offload_state_shardings(sharding)
    state = jax.jit(init_state, out_shardings=sharding)(rng)
    accum = max(args.grad_accum, 1)
    reduce_grads = reduce_metrics = None
    n_buckets = 0
    if args.overlap_collectives:
        reduce_grads, buckets = steps_lib.overlap_grad_reducer(
            shape.params, max(args.grad_bucket_mb, 1), ("data", "fsdp"))
        reduce_metrics = steps_lib.metrics_reducer(("data", "fsdp"))
        n_buckets = len(buckets)
    train_step = steps_lib.make_train_step(
        model, get_loss_fn(loss_name), tx, grad_accum_steps=accum,
        fused_update=fused_update, reduce_grads=reduce_grads,
        reduce_metrics=reduce_metrics)
    if args.offload_opt:
        train_step = steps_lib.offload_opt_state(
            train_step, opt_dev_sharding, sharding.opt_state)
    if args.overlap_collectives:
        step = steps_lib.jit_overlap_train_step(train_step, mesh, sharding)
    else:
        step = steps_lib.jit_train_step(train_step, mesh, sharding)

    global_batch = bpc * n_chips
    # Under --overlap-collectives the scan splits each SHARD's batch
    # (batch axes data x fsdp = n_chips here), not the global one.
    accum_unit = bpc if args.overlap_collectives else global_batch
    if accum_unit % accum:
        raise SystemExit(
            f"--grad-accum {accum} does not divide the "
            f"{'per-shard' if args.overlap_collectives else 'global'} "
            f"batch {accum_unit}")
    rng_np = np.random.default_rng(0)
    if vision:
        batch = {
            "image": jnp.asarray(
                rng_np.standard_normal(
                    (global_batch, args.image_size, args.image_size, 3)
                ),
                jnp.float32,
            ),
            "label": jnp.asarray(rng_np.integers(0, 1000, global_batch),
                                 jnp.int32),
        }
        items_per_step, unit_noun = global_batch, "images"
    elif args.model == "bert_base":
        from pytorch_distributed_train_tpu.data.datasets import synthetic_mlm

        ds = synthetic_mlm(global_batch, seq, model_cfg.vocab_size,
                           mlm_prob=0.15)
        mlm_batch = ds.get_batch(np.arange(global_batch), rng_np, train=True)
        batch = {k: jnp.asarray(v) for k, v in mlm_batch.items()}
        items_per_step, unit_noun = global_batch * seq, "tokens"
    elif args.model == "t5":
        labels = rng_np.integers(0, model_cfg.vocab_size,
                                 (global_batch, tgt_seq))
        batch = {
            "input_ids": jnp.asarray(
                rng_np.integers(0, model_cfg.vocab_size,
                                (global_batch, seq)), jnp.int32),
            "decoder_input_ids": jnp.asarray(
                np.concatenate([np.zeros((global_batch, 1), np.int64),
                                labels[:, :-1]], 1), jnp.int32),
            "labels": jnp.asarray(labels, jnp.int32),
        }
        items_per_step = global_batch * (seq + tgt_seq)
        unit_noun = "tokens"
    else:
        batch = {"input_ids": jnp.asarray(
            rng_np.integers(0, model_cfg.vocab_size, (global_batch, seq)),
            jnp.int32)}
        items_per_step, unit_noun = global_batch * seq, "tokens"

    # Timing always excludes compile: at least one warmup step runs.
    t_warm0 = time.monotonic()
    for _ in range(max(args.warmup, 1)):
        state, metrics = step(state, batch, rng)
    jax.block_until_ready((state, metrics))
    compile_s = time.monotonic() - t_warm0

    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, metrics = step(state, batch, rng)
    jax.block_until_ready((state, metrics))  # end of the donated chain
    wall = time.perf_counter() - t0
    loss = float(metrics["loss"])
    assert np.isfinite(loss), f"non-finite loss {loss}"

    per_step = wall / args.steps
    per_chip = items_per_step / per_step / n_chips

    # bert carries an explicit _mlm tag: the round-1 key measured plain
    # next-token xent and must never be compared against the MLM workload.
    bench_name = "bert_base_mlm" if args.model == "bert_base" else args.model
    # Compute-graph arms encode into the metric name (PR 12 convention:
    # each arm owns its ledger trajectory; the gate never cross-judges).
    arm_parts = []
    if accum > 1:
        arm_parts.append(f"ga{accum}")
    if args.overlap_collectives:
        arm_parts.append("overlap")
    if args.fused_epilogue:
        arm_parts.append("fusedep")
    arm_sfx = ("_" + "_".join(arm_parts)) if arm_parts else ""
    metric = f"{bench_name}{arm_sfx}_{unit_noun}_per_sec_per_chip"
    # Only canonical shapes may seed a baseline key — smoke runs with
    # non-default shapes must not.
    default_opt = (not args.optimizer and not args.moment_dtype
                   and not args.offload_opt and not arm_parts)
    if vision:
        # resnet50 is the north-star; vit_b16 also tracks its own key so
        # regressions there are visible across rounds (resnet18 stays a
        # smoke config).
        canonical = (args.model in ("resnet50", "vit_b16")
                     and args.batch_per_chip in (0, 128)
                     and args.image_size == 224 and default_opt
                     and args.stem == "conv")
    elif args.model == "llama":
        # fused-head runs are a different program (no logits materialized) —
        # they must not share a baseline key with the dense-head config.
        canonical = (args.batch_per_chip in (0, 8) and args.seq_len == 2048
                     and args.attention_impl == "auto"
                     and not args.fused_head and not args.quant_training
                     and args.remat_policy == "full" and default_opt)
    elif args.model == "t5":
        canonical = (args.batch_per_chip in (0, 64) and args.seq_len >= 512
                     and default_opt)
    else:  # bert_base
        canonical = (args.batch_per_chip in (0, 32) and args.seq_len >= 512
                     and args.attention_impl == "auto" and default_opt)
    baseline_path = os.path.join(os.path.dirname(__file__),
                                 "BENCH_BASELINE.json")
    base = {}
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            base = json.load(f)
    vs = per_chip / base[metric] if base.get(metric) else 1.0
    if metric not in base and canonical:
        # First measured run of a canonical config seeds its baseline key.
        base[metric] = per_chip
        base.setdefault("recorded", time.strftime("%Y-%m-%d"))
        with open(baseline_path, "w") as f:
            json.dump(base, f, indent=1)

    record = {
        "metric": metric,
        "value": round(per_chip, 2),
        "unit": f"{unit_noun}/sec/chip",
        "vs_baseline": round(vs, 4),
        # Bench-local goodput split (obs/goodput.py vocabulary): wall to
        # warmup/compile vs the timed steady-state steps; goodput_pct is
        # the timed fraction of the whole bench process life — a bench
        # that spent ten minutes in backend bring-up says so.
        "goodput_s_compile": round(compile_s, 3),
        "goodput_s_step": round(wall, 3),
        "goodput_pct": round(
            100.0 * wall / max(time.monotonic() - _T_MAIN0[0], 1e-9), 2),
    }
    if accum > 1:
        record["grad_accum_steps"] = accum
    if args.overlap_collectives:
        record["grad_buckets"] = n_buckets
        record["grad_bucket_mb"] = args.grad_bucket_mb
    if args.fused_epilogue:
        record["fused_epilogue"] = True
    from pytorch_distributed_train_tpu.obs import perf as perf_lib

    # Synthetic device batches: the stall split is usually empty — a
    # nonzero split here means a real loader fed this bench.
    split = perf_lib.get_input_stats().split()
    if split:
        record["stall_split"] = split
    # MFU accounting (VERDICT r3 #2): analytic model FLOPs/item (2xMACs,
    # train = 3x fwd — utils/flops.py conventions) over the detected
    # chip's bf16 peak. None on CPU backends (no MXU peak to divide by).
    from pytorch_distributed_train_tpu.utils import flops as flops_lib

    fpi = flops_lib.train_flops_per_item(model_cfg, None if vision else seq)
    peak = flops_lib.device_peak_flops(jax.devices()[0])
    mfu = flops_lib.mfu_pct(per_chip, fpi, peak)
    if fpi is not None:
        record["model_gflops_per_item"] = round(fpi / 1e9, 3)
    if mfu is not None:
        record["mfu_pct"] = round(mfu, 2)
    _emit(record)


if __name__ == "__main__":
    main()
