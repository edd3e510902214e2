#!/usr/bin/env python
"""Compiler-side A/B evidence with no chip attached.

This tool grounds A/B arms in the COMPILER'S OWN ACCOUNTING:
``jit(...).lower().compile()`` runs
the full XLA pipeline (SPMD partitioner, fusion, buffer assignment)
without touching a device, and ``compiled.cost_analysis()`` /
``memory_analysis()`` report FLOPs, bytes accessed, and temp sizes.
These are a COMPILER MODEL, not a measurement — rows are labeled so —
but ratios between two arms of an A/B (same compiler, same shapes) are
exactly the quantity the queued hardware runs would estimate.

Strategy: an AOT compile for a described TPU topology
(`jax.experimental.topologies`), which the locally installed libtpu
serves with no chip attached, so the arms compile with the real v5e
cost model and the real 15.75G HBM budget enforced at buffer
assignment; if the topology cannot be described, a structured record
lands in the output and the arms fall back to XLA:CPU (the
memfit_7b.py-validated fallback).

Arms:
  stem     — ResNet-50 train step: conv 7x7/s2 stem vs space_to_depth
  attn     — llama train step: attention_impl xla vs chunked
  quant    — llama decode step: int8 vs int4 weight-only params (bytes)
  epilogue — train-step optimizer epilogue: optax chain + gate select
             vs the one-pass fused epilogue (ops/fused_update.py) —
             bytes-accessed is the decision metric
  overlap  — shard_map DP train step: monolithic post-backward pmean
             vs per-bucket in-scan pmeans (collective count + bytes)

Run:  JAX_PLATFORMS=cpu python tools/aot_ab.py \
      [--arms stem attn quant epilogue overlap] [--small]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _probe_tpu_topology():
    """Can this host compile for a described TPU topology? Returns
    (record, topology-or-None) — the record lands in the output either
    way, so a failure is recorded, not silently swallowed. Where the
    local libtpu describes v5e, the arms below compile with the real TPU
    cost model, Mosaic included."""
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            topology_name="v5e:2x2x1", platform="tpu")
        return {"available": True,
                "topology": "v5e:2x2x1",
                "devices": len(topo.devices)}, topo
    except Exception as e:  # noqa: BLE001 — any failure = unavailable
        return {"available": False,
                "error": f"{type(e).__name__}: {str(e)[:200]}"}, None


def _guarded(fn, *a, **kw) -> dict:
    """Per-arm fault isolation. A v5e RESOURCE_EXHAUSTED at buffer
    assignment is EVIDENCE, not a tool failure — the TPU AOT pipeline
    enforces the real 15.75G HBM budget (discovered on the full-shape
    llama/adamw arm), so 'this config does not fit a single v5e' comes
    straight from the compiler and is recorded as such."""
    import re

    try:
        return fn(*a, **kw)
    except Exception as e:  # noqa: BLE001
        msg = str(e)
        m = re.search(r"Used ([\d.]+[GMK]) of ([\d.]+[GMK]) hbm", msg)
        rec = {"ok": False,
               "error": f"{type(e).__name__}: {msg[:250]}"}
        if m:
            rec["oom"] = {"needs": m.group(1), "hbm": m.group(2)}
        return rec


def _analyze(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ma = compiled.memory_analysis()
    out = {
        "gflops": round(float(ca.get("flops", 0.0)) / 1e9, 3),
        "gbytes_accessed": round(
            float(ca.get("bytes accessed", 0.0)) / 1e9, 3),
        "temp_mib": round(
            getattr(ma, "temp_size_in_bytes", 0) / 2**20, 1),
        "arg_mib": round(
            getattr(ma, "argument_size_in_bytes", 0) / 2**20, 1),
    }
    return out


def _attach(tree, sh):
    """Pin every ShapeDtypeStruct leaf to ``sh`` (the AOT target device);
    None = current-backend default (CPU fallback)."""
    import jax

    if sh is None:
        return tree
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree)


def _compile_train(model_cfg, loss_name: str, batch_n: int,
                   seq_or_img, sh=None) -> dict:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.config import (
        OptimConfig,
        PrecisionConfig,
    )
    from pytorch_distributed_train_tpu.losses import get_loss_fn
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.optim import make_optimizer
    from pytorch_distributed_train_tpu.train_state import TrainState

    precision = PrecisionConfig(compute_dtype="bfloat16")
    model = build_model(model_cfg, precision)
    tx, _ = make_optimizer(
        OptimConfig(name="adamw", learning_rate=1e-3, schedule="constant",
                    warmup_steps=0), total_steps=10)

    is_img = model_cfg.name.startswith(("resnet", "vit"))
    if is_img:
        x = jax.ShapeDtypeStruct(
            (batch_n, seq_or_img, seq_or_img, 3), jnp.bfloat16)
        batch = {"image": x,
                 "label": jax.ShapeDtypeStruct((batch_n,), jnp.int32)}
        init_inputs = (jnp.zeros((1, seq_or_img, seq_or_img, 3),
                                 jnp.bfloat16),)
    else:
        ids = jax.ShapeDtypeStruct((batch_n, seq_or_img), jnp.int32)
        batch = {"input_ids": ids}
        init_inputs = (jnp.zeros((1, seq_or_img), jnp.int32),)

    def init_state(rng):
        variables = model.init({"params": rng}, *init_inputs, train=False)
        return TrainState.create(params=variables["params"], tx=tx,
                                 batch_stats=variables.get("batch_stats"))

    state_shape = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    step = steps_lib.make_train_step(model, get_loss_fn(loss_name), tx)
    rng_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
    t0 = time.time()
    compiled = jax.jit(step).lower(
        _attach(state_shape, sh), _attach(batch, sh),
        _attach(rng_s, sh)).compile()
    out = _analyze(compiled)
    out["compile_s"] = round(time.time() - t0, 1)
    return out


def _compile_decode(model_cfg, quantize: str, sh=None) -> dict:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_train_tpu import quant
    from pytorch_distributed_train_tpu.config import PrecisionConfig
    from pytorch_distributed_train_tpu.generate import (
        _cache_shapes,
        build_decode_model,
    )
    from pytorch_distributed_train_tpu.models.registry import build_model

    precision = PrecisionConfig(compute_dtype="bfloat16")
    dm = build_decode_model(model_cfg, precision)
    base = jax.eval_shape(
        lambda: build_model(model_cfg, precision).init(
            {"params": jax.random.PRNGKey(0)},
            jnp.zeros((1, 2), jnp.int32), train=False))["params"]
    params = (jax.eval_shape(
        lambda p: quant.quantize_tree_named(p, quantize), base)
        if quantize else base)
    cache = _cache_shapes(dm, 1)
    ids = jax.ShapeDtypeStruct((1, 1), jnp.int32)

    def decode_step(p, c, i):
        p = quant.dequantize_tree(p, dm.dtype)
        logits, updated = dm.apply({"params": p, "cache": c}, i,
                                   train=False, mutable=["cache"])
        return logits[:, -1], updated["cache"]

    t0 = time.time()
    compiled = jax.jit(decode_step, donate_argnums=(1,)).lower(
        _attach(params, sh), _attach(cache, sh),
        _attach(ids, sh)).compile()
    out = _analyze(compiled)
    out["compile_s"] = round(time.time() - t0, 1)
    out["param_bytes_mib"] = round(sum(
        x.size * x.dtype.itemsize
        for x in jax.tree.leaves(params)) / 2**20, 1)
    return out


def _count_collectives(hlo_text: str) -> dict:
    """All-reduce placement in a compiled HLO dump — the evidence of
    the overlap A/B. Post-optimization XLA may COMBINE adjacent
    all-reduces, so the raw count can coincide between arms; what
    cannot coincide is WHERE they live: the bucketed arm issues its
    reductions inside the accumulation scan (a while-body computation,
    i.e. any non-ENTRY computation), the monolithic arm reduces the
    accumulated tree in the entry computation after the loop."""
    import re

    entry = nested = 0
    in_entry = False
    for line in hlo_text.splitlines():
        if not line.startswith(" ") and "{" in line:
            in_entry = line.lstrip().startswith("ENTRY")
        if re.search(r" all-reduce(?:-start)?\(", line):
            if in_entry:
                entry += 1
            else:
                nested += 1
    return {"all_reduce": entry + nested,
            "all_reduce_entry": entry,
            "all_reduce_in_loop": nested}


def _compile_epilogue_arm(small: bool, fused: bool, sh=None) -> dict:
    """ViT train step, adamw + clip + numeric guard: the optax-chain
    epilogue (three tree passes + whole-state gate select) vs the
    one-pass fused epilogue. Same model, same shapes — bytes-accessed
    is the decision metric."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.config import (
        ModelConfig,
        OptimConfig,
        PrecisionConfig,
    )
    from pytorch_distributed_train_tpu.losses import get_loss_fn
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.optim import (
        make_fused_update,
        make_optimizer,
    )
    from pytorch_distributed_train_tpu.train_state import TrainState

    if small:
        mc = ModelConfig(name="vit_b16", num_classes=10, image_size=16,
                         patch_size=4, hidden_size=64, num_layers=2,
                         num_heads=4, mlp_dim=128)
        bs = 8
    else:
        mc = ModelConfig(name="vit_b16", num_classes=1000, image_size=224,
                         patch_size=16, hidden_size=768, num_layers=12,
                         num_heads=12, mlp_dim=3072)
        bs = 64
    opt = OptimConfig(name="adamw", learning_rate=1e-3,
                      schedule="constant", warmup_steps=0,
                      weight_decay=0.01, grad_clip_norm=1.0)
    model = build_model(mc, PrecisionConfig(compute_dtype="bfloat16"))
    tx, sched = make_optimizer(opt, total_steps=100)
    fe = make_fused_update(opt, sched) if fused else None

    def init_state(rng):
        variables = model.init(
            {"params": rng},
            jnp.zeros((1, mc.image_size, mc.image_size, 3)), train=False)
        return TrainState.create(params=variables["params"], tx=tx)

    state_shape = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    step = steps_lib.make_train_step(
        model, get_loss_fn("softmax_xent"), tx, numeric_guard=True,
        fused_update=fe)
    batch = {
        "image": jax.ShapeDtypeStruct(
            (bs, mc.image_size, mc.image_size, 3), jnp.float32),
        "label": jax.ShapeDtypeStruct((bs,), jnp.int32),
    }
    rng_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
    t0 = time.time()
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        _attach(state_shape, sh), _attach(batch, sh),
        _attach(rng_s, sh)).compile()
    out = _analyze(compiled)
    out["compile_s"] = round(time.time() - t0, 1)
    return out


def _compile_overlap_arm(small: bool, bucketed: bool) -> dict:
    """shard_map DP train step over the local device mesh: monolithic
    post-backward pmean of the whole accumulated grad tree vs
    per-bucket pmeans inside the accumulation scan. Collective counts
    from the compiled HLO are the placement evidence; always compiles
    on the LOCAL devices (the CPU fake-device mesh in tests/CI) — a
    deviceless topology has no executable collective lowering to
    count."""
    import jax
    import jax.numpy as jnp

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
    from pytorch_distributed_train_tpu.parallel.partition import (
        rules_for_model,
    )
    from pytorch_distributed_train_tpu.train_state import TrainState

    devs = jax.devices()
    n = 8 if len(devs) >= 8 else len(devs)
    mesh = build_mesh(MeshConfig(data=n, fsdp=1), devs[:n])
    if small:
        mc = ModelConfig(name="vit_b16", num_classes=10, image_size=16,
                         patch_size=4, hidden_size=64, num_layers=2,
                         num_heads=4, mlp_dim=128)
        bs, accum, bucket_mb = 2 * n, 2, 1
    else:
        mc = ModelConfig(name="vit_b16", num_classes=1000, image_size=224,
                         patch_size=16, hidden_size=768, num_layers=12,
                         num_heads=12, mlp_dim=3072)
        bs, accum, bucket_mb = 8 * n, 4, 25
    opt = OptimConfig(name="momentum", learning_rate=0.1,
                      schedule="constant", warmup_steps=0)
    model = build_model(mc, PrecisionConfig(compute_dtype="bfloat16"))
    tx, _ = make_optimizer(opt, total_steps=100)
    rules = rules_for_model(mc.name)

    def init_state(rng):
        variables = model.init(
            {"params": rng},
            jnp.zeros((1, mc.image_size, mc.image_size, 3)), train=False)
        return TrainState.create(params=variables["params"], tx=tx)

    state_shape = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    sharding = steps_lib.state_shardings(mesh, rules, state_shape)
    axes = ("data", "fsdp")
    n_buckets = 0
    if bucketed:
        reduce_grads, buckets = steps_lib.overlap_grad_reducer(
            state_shape.params, bucket_mb, axes)
        reduce_accum = None
        n_buckets = len(buckets)
    else:
        reduce_grads = None
        reduce_accum = steps_lib.monolithic_grad_reducer(axes)
    step = steps_lib.make_train_step(
        model, get_loss_fn("softmax_xent"), tx, grad_accum_steps=accum,
        reduce_grads=reduce_grads, reduce_grads_accum=reduce_accum,
        reduce_metrics=steps_lib.metrics_reducer(axes))
    jitted = steps_lib.jit_overlap_train_step(step, mesh, sharding)
    batch = {
        "image": jax.ShapeDtypeStruct(
            (bs, mc.image_size, mc.image_size, 3), jnp.float32),
        "label": jax.ShapeDtypeStruct((bs,), jnp.int32),
    }
    rng_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
    t0 = time.time()
    compiled = jitted.lower(state_shape, batch, rng_s).compile()
    out = _analyze(compiled)
    out["compile_s"] = round(time.time() - t0, 1)
    out.update(_count_collectives(compiled.as_text()))
    out["devices"] = n
    out["grad_accum_steps"] = accum
    if bucketed:
        out["grad_buckets"] = n_buckets
    return out


def main(argv=None) -> int:
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    p = argparse.ArgumentParser()
    p.add_argument("--arms", nargs="+",
                   default=["stem", "attn", "quant"],
                   choices=["stem", "attn", "quant", "epilogue",
                            "overlap"])
    p.add_argument("--small", action="store_true",
                   help="tiny shapes (smoke/test mode, minutes -> seconds)")
    args = p.parse_args(argv)

    from pytorch_distributed_train_tpu.config import ModelConfig

    out = {"tool": "aot_ab",
           "backend": "tpu-topology", "date": time.strftime("%Y-%m-%d"),
           "note": ("compiler model (cost_analysis/memory_analysis), "
                    "NOT a hardware measurement; ratios between arms "
                    "are the decision signal")}
    rec, topo = _probe_tpu_topology()
    out["tpu_topology_probe"] = rec
    sh = None
    if topo is not None:
        sh = jax.sharding.SingleDeviceSharding(topo.devices[0])
    else:
        out["backend"] = f"xla:{jax.devices()[0].platform}"

    if "stem" in args.arms:
        img = 64 if args.small else 224
        bs = 8 if args.small else 128
        name = "resnet18" if args.small else "resnet50"
        arms = {}
        for stem in ("conv", "space_to_depth"):
            arms[stem] = _guarded(
                _compile_train,
                ModelConfig(name=name, num_classes=1000, stem=stem),
                "softmax_xent", bs, img, sh=sh)
        out["stem_ab"] = {"config": f"{name} bs{bs} {img}px", **arms}

    if "attn" in args.arms:
        mc = dict(vocab_size=32000, hidden_size=2048, num_layers=16,
                  num_heads=16, num_kv_heads=16, mlp_dim=5504,
                  max_seq_len=2048, fused_lm_loss=True)
        bs, seq = 4, 2048
        if args.small:
            mc.update(vocab_size=512, hidden_size=128, num_layers=2,
                      num_heads=4, num_kv_heads=4, mlp_dim=256,
                      max_seq_len=256)
            bs, seq = 2, 256
        arms = {}
        for impl in ("xla", "chunked"):
            arms[impl] = _guarded(
                _compile_train,
                ModelConfig(name="llama", attention_impl=impl, **mc),
                "fused_causal_lm_xent", bs, seq, sh=sh)
        out["attn_ab"] = {"config": f"llama h{mc['hidden_size']} "
                                    f"L{mc['num_layers']} bs{bs} s{seq}",
                          **arms}

    if "epilogue" in args.arms:
        arms = {}
        for fused in (False, True):
            arms["fused" if fused else "chain"] = _guarded(
                _compile_epilogue_arm, args.small, fused, sh=sh)
        out["epilogue_ab"] = {
            "config": ("vit train step, adamw+clip+numeric-guard, "
                       + ("small" if args.small else "b16 bs64")),
            "decision": "fused gbytes_accessed <= chain (one-pass "
                        "epilogue reads/writes the grad tree once)",
            **arms}

    if "overlap" in args.arms:
        arms = {}
        for bucketed in (False, True):
            arms["bucketed" if bucketed else "monolithic"] = _guarded(
                _compile_overlap_arm, args.small, bucketed)
        out["overlap_ab"] = {
            "config": ("shard_map DP vit train step over local devices "
                       + ("(small)" if args.small else "(b16)")),
            "decision": "bucketed arm emits per-bucket all-reduces "
                        "inside the accumulation scan (count changes "
                        "vs the monolithic post-backward reduction)",
            **arms}

    if "quant" in args.arms:
        mc = dict(vocab_size=32000, hidden_size=2048, num_layers=16,
                  num_heads=16, num_kv_heads=16, mlp_dim=5504,
                  max_seq_len=512)
        if args.small:
            mc.update(vocab_size=512, hidden_size=128, num_layers=2,
                      num_heads=4, num_kv_heads=4, mlp_dim=256,
                      max_seq_len=128)
        arms = {}
        for q in ("int8", "int4"):
            arms[q] = _guarded(
                _compile_decode, ModelConfig(name="llama", **mc),
                q, sh=sh)
        out["quant_ab"] = {"config": f"llama h{mc['hidden_size']} "
                                     f"L{mc['num_layers']} decode bs1",
                           **arms}

    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
