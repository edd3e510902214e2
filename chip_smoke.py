#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once on ONE TPU chip, through the entry points a user
calls, at the published widths of GPT-2 small (768 wide, 12 layers, 12 heads
of 64, vocabulary 50304, sequence 1024; only the batch is sized to one chip):

  1. train        train.main: a few steps, synchronous checkpoint, then a
                  second train.main that must resume at the saved step
  2. export_serve train.main --export-safetensors, then tools/serve_http.py's
                  real backend on those weights on a loopback port: a few
                  POST /v1/completions (two concurrent), /healthz, drain
  3. resnet50     train.main for the north-star model at 224x224, batch 128
  +  attention_ab one forward+backward of the flash kernel and of the
                  chunked path at the smoke's own attention shape

    python chip_smoke.py              # one chip, every phase
    python chip_smoke.py --four-chips # ONLY the sharded path (mesh.data=2
                                      # mesh.fsdp=2) and its one-device twin
    python chip_smoke.py --tiny       # rehearsal sizes (tests, CPU): never
                                      # reports ok

Each phase prints one JSON line (information, not claims). Any failed check
raises: the script exits non-zero and prints no result line. With no TPU it
refuses before any phase (exit 2, nothing on stdout) unless --tiny asks for
the CPU rehearsal, whose last line always says "ok": false. The LAST stdout
line of a good run is exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
One process throughout: nothing here starts a child that needs the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import re
import shutil
import socket
import statistics
import sys
import threading
import time
import urllib.request
import warnings

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "tools"))

# Small records (metrics JSONL) go where the chip tool brings them back
# from; checkpoints and weights — GiBs at real widths — stay in a
# git-ignored work directory inside the checkout.
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
WORK_DIR = os.path.join(REPO, ".chip_smoke_work")

# GPT-2 small at its published widths comes from the preset; only these
# run-length knobs are set. Batch 8 x 1024 tokens: the compiler's memory
# accounting for the whole step on one v5e is in CHANGES.md (PR 21).
GPT2_BATCH = 8
GPT2_TINY = ["model.hidden_size=128", "model.num_layers=2",
             "model.num_heads=2", "model.mlp_dim=256", "model.vocab_size=512",
             "model.max_seq_len=128", "data.seq_len=128"]
# Loss parity, four chips against one: same seed, same global batch, no
# dropout; bf16 compute with a different reduction order per layout.
PARITY_ATOL = 0.01


class _Tee(io.TextIOBase):
    """A stream that also remembers — the phases read what the entry points
    print (resume line on stdout, attention resolution on stderr) without
    hiding it."""

    def __init__(self, stream):
        self.stream, self.buf = stream, io.StringIO()

    def write(self, s):
        self.buf.write(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()

    def fileno(self):  # faulthandler writes to the real descriptor
        return self.stream.fileno()


def _check(cond, msg):
    if not cond:
        raise AssertionError(f"chip_smoke: {msg}")


def _run_main(main, argv):
    """Run an entry point's main(argv) in-process; (rc, what it printed on
    stdout and stderr)."""
    out, err = _Tee(sys.stdout), _Tee(sys.stderr)
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = main(argv)
    return rc, out.buf.getvalue() + err.buf.getvalue()


def _rows(path, tag):
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows if r.get("tag") == tag]


class _CacheCounter:
    """Persistent-compile-cache traffic, from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.requests = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def mark(self):
        return (self.hits, self.requests)

    def since(self, mark):
        return {"cache_hits": self.hits - mark[0],
                "cache_requests": self.requests - mark[1]}


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _emit(phase, t0, **fields):
    print(json.dumps({"phase": phase,
                      "seconds": round(time.monotonic() - t0, 2),
                      **fields}), flush=True)


def _train_argv(config, run_dir, steps, sets, jsonl):
    argv = ["--config", config, "--steps", str(steps)]
    for kv in [*sets, f"checkpoint.dir={run_dir}",
               "checkpoint.async_save=false", "obs.log_every_steps=1",
               f"obs.jsonl_path={jsonl}"]:
        argv += ["--set", kv]
    return argv


def _train_summary(jsonl, unit):
    train = _rows(jsonl, "train")
    summary = _rows(jsonl, "summary")[-1]
    losses = [r["loss"] for r in train]
    _check(losses and all(math.isfinite(x) for x in losses),
           f"non-finite or missing losses in {jsonl}: {losses}")
    last = train[-1]
    return train, {
        "steps": len(train), "first_step": train[0]["step"],
        "first_loss": losses[0], "last_loss": losses[-1],
        "compile_s": summary.get("goodput_s_compile"),
        "step_time_ms_p50": last.get("step_time_ms_p50"),
        f"{unit}_per_sec": last.get(f"{unit}_per_sec"),
        "mfu_pct": last.get("mfu_pct"),
    }


def _gpt2_sets(batch, tiny):
    sets = ["data.dataset=synthetic_lm", f"data.batch_size={batch}",
            f"data.synthetic_size={4 * batch}", "optim.schedule=constant",
            "optim.warmup_steps=0"]
    return sets + (GPT2_TINY if tiny else [])


# --------------------------------------------------------------- phase 1
def phase_train(train, cache, on_tpu, tiny, cache_dir):
    import jax

    t0 = time.monotonic()
    run_dir = os.path.join(WORK_DIR, "gpt2")
    batch = 4 if tiny else GPT2_BATCH
    sets = _gpt2_sets(batch, tiny) + ["checkpoint.save_every_steps=6"]
    j1, j2 = (os.path.join(OUT_DIR, f"gpt2_run{i}.jsonl") for i in (1, 2))

    mark = cache.mark()
    rc, out1 = _run_main(train.main,
                         _train_argv("gpt2_small", run_dir, 6, sets, j1))
    _check(rc == 0, f"first train.main returned {rc}")
    first_cache = cache.since(mark)
    _, s1 = _train_summary(j1, "tokens")
    _check(s1["last_loss"] < s1["first_loss"],
           f"loss did not fall: {s1['first_loss']} -> {s1['last_loss']}")

    mark = cache.mark()
    rc, out2 = _run_main(train.main,
                         _train_argv("gpt2_small", run_dir, 10, sets, j2))
    _check(rc == 0, f"second train.main returned {rc}")
    second_cache = cache.since(mark)
    m = re.search(r"\[resume\] restored step (\d+)", out2)
    _check(m and int(m.group(1)) == 6,
           f"second run did not resume at the saved step 6: {m and m.group(0)}")
    rows2, s2 = _train_summary(j2, "tokens")
    _check(rows2[0]["step"] == 7 and rows2[-1]["step"] == 10,
           f"resumed run logged steps {[r['step'] for r in rows2]}")
    _check(s2["last_loss"] < s1["first_loss"],
           f"loss after resume {s2['last_loss']} not below the first "
           f"{s1['first_loss']}")

    attention = sorted(set(re.findall(r"\[attention\] impl=(\w+)", out1)))
    info = {**s1, "resumed_at": int(m.group(1)),
            "last_loss_after_resume": s2["last_loss"],
            "attention_impl": attention,
            "peak_bytes_in_use": _peak_bytes(), "cache_dir": cache_dir,
            "first_run": first_cache, "second_run": second_cache,
            "second_run_compile_s": s2["compile_s"],
            "global_batch": batch, "platform": jax.devices()[0].platform}
    if on_tpu and not tiny:  # the tiny sequence is below the kernel's gate
        want = (f"[attention] impl=pallas q=({batch}, 1024, 12, 64) "
                "kv_heads=12 dtype=bfloat16 causal=True window=0 "
                "interpret=False")
        _check(want in out1, f"attention did not resolve to the compiled "
               f"Pallas kernel; wanted {want!r}, saw impl={attention}")
        _check(s1["mfu_pct"] is not None,
               "MFU missing: the peaks table gave no rate for this device")
        if cache_dir is not None:
            _check(second_cache["cache_hits"] > 0,
                   f"second train.main hit no cache entry: {second_cache}")
        info["tpu_custom_calls"] = _step_text(train, _train_argv(
            "gpt2_small", run_dir, 10, sets,
            os.path.join(OUT_DIR, "gpt2_inspect.jsonl"))
        ).count("tpu_custom_call")
        _check(info["tpu_custom_calls"] > 0,
               "no tpu_custom_call in the compiled train step")
    _emit("train", t0, **info)
    return run_dir, sets


def _trainer_for(train, argv, devices=None):
    """The Trainer train.main would build for argv (restoring the run's
    checkpoint when there is one); ``devices`` narrows the mesh."""
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = train.build_config(train.parse_args(argv))
    return Trainer(cfg, mesh=build_mesh(cfg.mesh, devices=devices)
                   if devices is not None else None)


def _compiled_step(trainer):
    """The trainer's own jitted step, compiled for the batch its own input
    pipeline produces (a cache hit after a run)."""
    batch = next(iter(trainer.train_epoch_fn(0)))
    compiled = trainer.train_step.lower(
        trainer.state, batch, trainer.step_rng).compile()
    return batch, compiled


def _step_text(train, argv):
    trainer = _trainer_for(train, argv)
    try:
        return _compiled_step(trainer)[1].as_text()
    finally:
        trainer.close()


# --------------------------------------------------------------- phase 2
def _post(port, path, body, timeout=600):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(port, path, timeout=60):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as r:
        return r.status, json.loads(r.read())


def phase_export_serve(train, run_dir, sets, tiny):
    import serve_http

    t0 = time.monotonic()
    weights = os.path.join(WORK_DIR, "gpt2_small.safetensors")
    rc, _ = _run_main(train.main, _train_argv(
        "gpt2_small", run_dir, 10, sets,
        os.path.join(OUT_DIR, "gpt2_export.jsonl"))
        + ["--export-safetensors", weights])
    _check(rc == 0 and os.path.getsize(weights) > 0,
           f"export returned {rc}")

    with socket.socket() as s:  # a free loopback port for the server
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    argv = ["--config", "gpt2_small", "--safetensors", weights,
            "--port", str(port), "--slots", "4", "--drain-grace", "60"]
    for kv in (GPT2_TINY if tiny else []):
        argv += ["--set", kv]
    result = {}
    server = threading.Thread(
        target=lambda: result.update(rc=serve_http.main(argv)),
        name="serve-http", daemon=True)
    server.start()
    deadline = time.monotonic() + 600
    while True:  # weights load + first compile happen before it listens
        _check(server.is_alive(), f"server exited early: {result}")
        _check(time.monotonic() < deadline, "server never came up")
        try:
            status, health = _get(port, "/healthz", timeout=5)
            break
        except OSError:
            time.sleep(0.5)
    _check(status == 200 and health.get("status") == "ok",
           f"/healthz said {health}")

    prompts = [("short", "TPU", 8), ("medium", "the quick brown fox " * 3, 12),
               ("long", "attention is all you need. " * (3 if tiny else 12),
                16)]
    answers, lat = {}, {}

    def ask(name, prompt, n):
        t = time.monotonic()
        answers[name] = _post(port, "/v1/completions", {
            "prompt": prompt, "max_tokens": n, "temperature": 0.0,
            "logprobs": True})
        lat[name] = round(time.monotonic() - t, 3)

    ask(*prompts[0])
    pair = [threading.Thread(target=ask, args=p) for p in prompts[1:]]
    for t in pair:  # two requests in flight together
        t.start()
    for t in pair:
        t.join(timeout=600)
        _check(not t.is_alive(), "a concurrent completion never returned")
    ask("short_again", *prompts[0][1:])
    for name, _, n in [*prompts, ("short_again", "", prompts[0][2])]:
        _check(name in answers, f"no answer for {name}")
        status, body = answers[name]
        _check(status == 200, f"{name}: HTTP {status}")
        _check(body["usage"]["completion_tokens"] == n,
               f"{name}: asked {n} tokens, got {body['usage']} "
               f"({body['finish_reason']})")
    a, b = answers["short"][1], answers["short_again"][1]
    _check(a["text"] == b["text"] and a["logprobs"] == b["logprobs"],
           "greedy output differs for a repeated prompt")

    status, health = _get(port, "/healthz")
    _check(status == 200 and health.get("status") == "ok",
           f"/healthz after traffic said {health}")
    status, _ = _post(port, "/admin/drain", {})
    _check(status == 202, f"/admin/drain answered {status}")
    server.join(timeout=120)
    _check(not server.is_alive() and result.get("rc") == 0,
           f"server did not shut down cleanly: {result}")
    _emit("export_serve", t0, requests=len(answers), latency_s=lat,
          weights_bytes=os.path.getsize(weights),
          batcher=health.get("stats"), peak_bytes_in_use=_peak_bytes())


# --------------------------------------------------------------- phase 3
def phase_resnet(train, tiny):
    from pytorch_distributed_train_tpu.native import imgops

    t0 = time.monotonic()
    # The input path's fused augment is built from native/imgops.cpp on
    # first use; a failed build must fail here, not fall back to numpy.
    _check(imgops.available(), "native/imgops did not build")
    batch = 8 if tiny else 128
    sets = ["data.dataset=synthetic_images", f"data.batch_size={batch}",
            f"data.synthetic_size={2 * batch}", "data.num_workers=2",
            "optim.schedule=constant", "optim.warmup_steps=0",
            "optim.learning_rate=0.02", "checkpoint.save_every_steps=0",
            "checkpoint.best_metric="]
    if tiny:
        sets += ["model.name=resnet18", "model.image_size=32"]
    jsonl = os.path.join(OUT_DIR, "resnet50.jsonl")
    rc, _ = _run_main(train.main, _train_argv(
        "resnet50_imagenet", os.path.join(WORK_DIR, "resnet50"), 8, sets,
        jsonl))
    _check(rc == 0, f"resnet train.main returned {rc}")
    _, s = _train_summary(jsonl, "images")
    _check(s["last_loss"] < s["first_loss"],
           f"resnet loss did not fall: {s['first_loss']} -> {s['last_loss']}")
    _emit("resnet50", t0, **s, global_batch=batch,
          image_size=32 if tiny else 224, peak_bytes_in_use=_peak_bytes())


# ------------------------------------------------------- attention timing
def phase_attention_ab(on_tpu, tiny):
    """One forward+backward of the flash kernel and of the chunked path at
    the smoke's attention shape, through the public dispatch."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_train_tpu.ops.attention import (
        dot_product_attention,
    )

    t0 = time.monotonic()
    shape = (2, 128, 2, 64) if tiny else (GPT2_BATCH, 1024, 12, 64)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
               for key in jax.random.split(jax.random.PRNGKey(0), 3))
    ms, grads = {}, {}
    for impl in ("pallas", "chunked"):
        def loss(q_, k_, v_, impl=impl):
            return dot_product_attention(
                q_, k_, v_, causal=True, impl=impl).astype(jnp.float32).sum()

        fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        grads[impl] = jax.block_until_ready(fn(q, k, v))  # compile + warm
        times = []
        for _ in range(5):
            t = time.perf_counter()
            jax.block_until_ready(fn(q, k, v))
            times.append((time.perf_counter() - t) * 1e3)
        ms[impl] = statistics.median(times)
    # the two implementations are each other's reference
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(grads["pallas"], grads["chunked"]))
    scale = max(float(jnp.max(jnp.abs(g.astype(jnp.float32))))
                for g in grads["chunked"])
    _check(math.isfinite(err) and err <= 0.05 * max(scale, 1.0),
           f"flash and chunked gradients disagree: max|d|={err} "
           f"against max|g|={scale}")
    _emit("attention_ab", t0, shape=list(shape), dtype="bfloat16",
          causal=True, fwd_bwd_ms_flash=ms["pallas"],
          fwd_bwd_ms_chunked=ms["chunked"], grad_max_abs_diff=err,
          interpret=not on_tpu)


# ------------------------------------------------------------ four chips
def phase_four_chips(train, tiny):
    import jax

    t0 = time.monotonic()
    devices = jax.devices()
    _check(len(devices) == 4, f"--four-chips needs 4 devices, found "
           f"{len(devices)}")
    batch = 8 if tiny else 2 * GPT2_BATCH  # must also fit ONE device
    sets = _gpt2_sets(batch, tiny) + ["model.dropout_rate=0.0",
                                      "checkpoint.save_every_steps=6"]
    steps = 6
    run4 = os.path.join(WORK_DIR, "gpt2_4chip")
    j4, j1 = (os.path.join(OUT_DIR, f"gpt2_{n}.jsonl")
              for n in ("4chip", "1device"))
    argv4 = _train_argv("gpt2_small", run4, steps,
                        sets + ["mesh.data=2", "mesh.fsdp=2"], j4)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        rc, out4 = _run_main(train.main, argv4)
    _check(rc == 0, f"four-chip train.main returned {rc}")
    fallback = [str(w.message) for w in caught
                if "falling back to enumeration order" in str(w.message)]
    rows4, s4 = _train_summary(j4, "tokens")

    # what the run left: its own Trainer, rebuilt on the saved state
    trainer = _trainer_for(train, argv4)
    try:
        mesh_shape = {k: v for k, v in trainer.mesh.shape.items() if v > 1}
        _check(mesh_shape == {"data": 2, "fsdp": 2}, f"mesh is {mesh_shape}")
        leaves = jax.tree_util.tree_leaves_with_path(
            {"params": trainer.state.params,
             "opt_state": trainer.state.opt_state})
        big = sorted((x for x in leaves if hasattr(x[1], "sharding")),
                     key=lambda x: -x[1].size)[:6]
        spread = {}
        for path, leaf in big:
            shard = leaf.addressable_shards[0].data
            spread[jax.tree_util.keystr(path)] = {
                "shape": list(leaf.shape), "shard_shape": list(shard.shape),
                "devices": len(leaf.sharding.device_set)}
            _check(len(leaf.sharding.device_set) == 4
                   and shard.size < leaf.size,
                   f"{jax.tree_util.keystr(path)} is not spread: "
                   f"{leaf.sharding}")
        batch_arr, compiled = _compiled_step(trainer)
        ids = batch_arr["input_ids"]
        _check(len(ids.sharding.device_set) == 4
               and ids.addressable_shards[0].data.shape[0] == batch // 4,
               f"batch is not sharded on its batch axes: {ids.sharding}")
        text = compiled.as_text()
        collectives = {op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
                       for op in ("all-reduce", "reduce-scatter",
                                  "all-gather")}
        _check(collectives["all-gather"] > 0
               and collectives["all-reduce"] + collectives["reduce-scatter"]
               > 0, f"expected collectives missing: {collectives}")
        del batch_arr, ids
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        state_bytes = sum(x[1].size * x[1].dtype.itemsize for x in leaves
                          if hasattr(x[1], "sharding"))
        if all(b is not None for b in in_use):
            _check(max(in_use) <= 2 * min(in_use),
                   f"devices hold unequal shares: {in_use}")
            _check(in_use[0] < state_bytes,
                   f"device 0 holds {in_use[0]} bytes, the whole state is "
                   f"{state_bytes}")
    finally:
        trainer.close()
        del trainer

    # the comparison: same seed and global batch, one device
    argv1 = _train_argv("gpt2_small", os.path.join(WORK_DIR, "gpt2_1device"),
                        steps, sets, j1)
    one = _trainer_for(train, argv1, devices=devices[:1])
    try:
        one.fit()
    finally:
        one.close()
    rows1, s1 = _train_summary(j1, "tokens")
    l4, l1 = ([r["loss"] for r in rows] for rows in (rows4, rows1))
    _check(len(l4) == len(l1) == steps, f"step counts differ: {l4} / {l1}")
    diffs = [abs(a - b) for a, b in zip(l4, l1)]
    _check(max(diffs) <= PARITY_ATOL,
           f"per-step losses disagree beyond {PARITY_ATOL}: {l4} vs {l1}")
    attention = sorted(set(re.findall(r"\[attention\] impl=(\w+)", out4)))
    _emit("four_chips", t0, mesh=mesh_shape, global_batch=batch,
          losses_4chip=l4, losses_1device=l1, max_abs_diff=max(diffs),
          parity_atol=PARITY_ATOL, largest_leaves=spread,
          collectives=collectives, bytes_in_use_per_device=in_use,
          state_bytes=state_bytes, mesh_fallback_warning=fallback,
          attention_impl=attention,
          step_time_ms_p50_4chip=s4["step_time_ms_p50"],
          step_time_ms_p50_1device=s1["step_time_ms_p50"],
          tokens_per_sec_4chip=s4["tokens_per_sec"],
          tokens_per_sec_1device=s1["tokens_per_sec"],
          compile_s_4chip=s4["compile_s"])


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run ONLY the sharded path on four chips and its "
                        "one-device comparison")
    p.add_argument("--tiny", action="store_true",
                   help="test-only rehearsal sizes; never reports ok")
    args = p.parse_args(argv)

    import train  # fails here in a directory that is not the repo

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no accelerator: {str(e).splitlines()[0]}",
              file=sys.stderr)
        return 2
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: no TPU (JAX found platform "
              f"{devices[0].platform!r}); --tiny rehearses on the CPU",
              file=sys.stderr)
        return 2

    from pytorch_distributed_train_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    cache = _CacheCounter()
    for d in (OUT_DIR, WORK_DIR):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    t0 = time.monotonic()
    if args.four_chips:
        phase_four_chips(train, args.tiny)
    else:
        run_dir, sets = phase_train(train, cache, on_tpu, args.tiny,
                                    cache_dir)
        phase_export_serve(train, run_dir, sets, args.tiny)
        phase_resnet(train, args.tiny)
        phase_attention_ab(on_tpu, args.tiny)
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    _emit("total", t0, cache_dir=cache_dir, **cache.since((0, 0)))
    print(json.dumps({
        "ok": on_tpu and not args.tiny,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)}}),
        flush=True)
    return 0 if on_tpu and not args.tiny else 3


if __name__ == "__main__":
    sys.exit(main())
