"""Trainer: builds everything from a TrainConfig and runs the epoch/step loop.

The structural twin of the reference's train.py main() (SURVEY H1, §3.3):
build mesh ← (init_process_group) · model ← config · data · optimizer ·
restore ← checkpoint · loop{step, log, ckpt} · validate. Every phase maps to
its TPU-native mechanism per SURVEY §7.2.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_train_tpu import faults as faults_lib
from pytorch_distributed_train_tpu import lora as lora_lib
from pytorch_distributed_train_tpu import losses as losses_lib
from pytorch_distributed_train_tpu import steps as steps_lib
from pytorch_distributed_train_tpu.checkpoint import (
    BestCheckpointTracker,
    CheckpointManager,
)
from pytorch_distributed_train_tpu.ckpt import build_checkpoint_manager
from pytorch_distributed_train_tpu.config import TrainConfig
from pytorch_distributed_train_tpu.data.datasets import build_dataset
from pytorch_distributed_train_tpu.data.pipeline import build_input_pipeline
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.obs import cluster as cluster_lib
from pytorch_distributed_train_tpu.obs import events as events_lib
from pytorch_distributed_train_tpu.obs import memory as memory_lib
from pytorch_distributed_train_tpu.obs import perf as perf_lib
from pytorch_distributed_train_tpu.obs import profiler as profiler_lib
from pytorch_distributed_train_tpu.obs import spans as spans_lib
from pytorch_distributed_train_tpu.obs import step_program
from pytorch_distributed_train_tpu.obs import tracing
from pytorch_distributed_train_tpu.obs.goodput import GoodputTracker
from pytorch_distributed_train_tpu.obs.registry import get_registry
from pytorch_distributed_train_tpu.optim import make_optimizer, plateau_scale
from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
from pytorch_distributed_train_tpu.parallel.partition import rules_for_model
from pytorch_distributed_train_tpu.sentinel import numeric as sentinel_numeric
from pytorch_distributed_train_tpu.train_state import DynamicScale, TrainState
from pytorch_distributed_train_tpu.utils import compile_cache
from pytorch_distributed_train_tpu.utils import debug as debug_lib
from pytorch_distributed_train_tpu.utils import flops as flops_lib
from pytorch_distributed_train_tpu.utils.metrics import Meter, MetricLogger
from pytorch_distributed_train_tpu.utils.watchdog import FlightRecorder, Heartbeat


def _compile_cache_preference(configured: str) -> str:
    """The cache directory this process would like when
    JAX_COMPILATION_CACHE_DIR does not decide: ``obs.compile_cache_dir``,
    else the launcher's per-worker PDTT_COMPILE_CACHE_DIR, else the
    helper's default ("") — with a per-worker subdirectory, keyed by the
    stable rank, whenever a launcher runs several workers."""
    if not configured and os.environ.get("PDTT_COMPILE_CACHE_DIR"):
        return os.environ["PDTT_COMPILE_CACHE_DIR"]
    wid = os.environ.get("PROCESS_ID")
    if wid is None:
        return configured
    from pytorch_distributed_train_tpu.elastic import worker_cache_dir

    return worker_cache_dir(configured or compile_cache.DEFAULT_DIR, wid)


class Trainer:
    def __init__(self, cfg: TrainConfig, mesh=None):
        # ``train.init`` with one child a large part (obs/spans.py): where
        # set-up goes, on the clock a profiler trace can be joined with.
        self.spans = spans_lib.get_recorder()
        spans_lib.install_compile_listener()
        with self.spans.span("train.init"), \
                contextlib.ExitStack() as part:
            def phase(name: str) -> None:
                part.close()  # the part that was open ends where this starts
                part.enter_context(self.spans.span(name))

            self._build(cfg, mesh, phase)

    def _build(self, cfg: TrainConfig, mesh, phase) -> None:
        # Goodput clock starts at construction: mesh/model/data/restore
        # time is the init bucket (obs/goodput.py) — a job that spends
        # minutes rebuilding state per restart should see it in the
        # summary, not have it vanish into pre-fit limbo.
        _t_init0 = time.perf_counter()
        self.goodput = GoodputTracker(t0=_t_init0)
        self.cfg = cfg
        # ---- event journal (obs/events.py): configured FIRST so every
        # later construction phase (fault schedule, data, restore) can
        # journal. PDTT_EVENTS_DIR (tpurun --events-dir) beats the
        # per-run default so agent + all hosts share one directory.
        self.journal = events_lib.configure(
            (cfg.obs.events_dir or os.environ.get(events_lib.ENV_VAR)
             or os.path.join(cfg.checkpoint.dir, "events"))
            if cfg.obs.events else None)
        # ---- distributed tracing (obs/tracing.py): spill beside the
        # journal, and stamp (gen, step) correlation tags on every span
        # so serving traces on a co-resident host line up against what
        # this trainer was doing — the ROADMAP-4 weight-sync debugging
        # contract. Step updates at the loop (cheap dict write).
        tracing.configure(
            cfg.obs.trace_dir or os.environ.get(tracing.ENV_DIR)
            or os.path.join(cfg.checkpoint.dir, "traces"),
            sample_pct=cfg.obs.trace_sample_pct,
            keep_slow_ms=cfg.obs.trace_keep_slow_ms)
        spans_lib.set_correlation_tags(
            gen=os.environ.get("RESTART_GENERATION", "0"))
        # ---- fault schedule + recovery policies (faults/): configured
        # before data/checkpoint construction so every fault point those
        # layers traverse is already armed. obs.fault_inject_at_step is
        # the deprecated single-kill hook, routed through the registry.
        self.faults = faults_lib.configure(
            tuple(cfg.faults.inject), seed=cfg.faults.seed,
            legacy_crash_step=cfg.obs.fault_inject_at_step)
        faults_lib.set_default_policy(faults_lib.RetryPolicy(
            max_attempts=cfg.faults.retry_max_attempts,
            base_delay_s=cfg.faults.retry_base_delay_s,
            max_delay_s=cfg.faults.retry_max_delay_s))
        if cfg.obs.debug_nans:
            debug_lib.enable_nan_debugging()
        # Persistent XLA compile cache (utils/compile_cache.py):
        # restart-and-resume (the SPMD elasticity model, SURVEY §5.3)
        # skips the minutes-scale GSPMD recompiles of large models.
        # Per-worker subdir under tpurun: jax loads truncated cache
        # entries without validation, so a worker killed mid-cache-write
        # (crash drill, SIGKILL escalation) would poison every sibling
        # and later generation sharing the dir. Worker id is stable
        # across restart generations, so each worker still reuses ITS
        # cache. tpurun --compile-cache-dir hands over a per-worker dir
        # already (PDTT_COMPILE_CACHE_DIR).
        self.compile_cache_dir = compile_cache.enable(
            _compile_cache_preference(cfg.obs.compile_cache_dir))
        if (getattr(cfg.optim, "swa_update_bn_batches", 0) > 0
                and cfg.optim.ema_decay == 0.0
                and getattr(cfg.optim, "swa_start_step", 0) == 0):
            raise ValueError(
                "optim.swa_update_bn_batches requires weight averaging "
                "(set optim.swa_start_step or optim.ema_decay) — "
                "silently ignoring the knob would ship stale-stats "
                "results the user believes were re-estimated")
        phase("train.init.mesh")
        self.mesh = mesh if mesh is not None else build_mesh(cfg.mesh)
        self.batch_axes = tuple(cfg.mesh.batch_axes)
        self.model = build_model(cfg.model, cfg.precision,
                                 mesh=self.mesh, mesh_cfg=cfg.mesh)
        fused_model = getattr(cfg.model, "fused_lm_loss", False)
        if fused_model != (cfg.loss == "fused_causal_lm_xent"):
            raise ValueError(
                "model.fused_lm_loss and loss='fused_causal_lm_xent' must be "
                f"set together (got fused_lm_loss={fused_model}, "
                f"loss={cfg.loss!r}): the fused model returns CE sums, not "
                "logits, so no other loss can consume its output")
        # the model object says whether it loops (models/llama.py), as it
        # says whether it has a tied leaf (tied_shards, below)
        looped_model = getattr(self.model, "loop_steps", 1) > 1
        if looped_model != (cfg.loss == "looped_lm_xent"):
            raise ValueError(
                "a model with loop_steps > 1 and loss='looped_lm_xent' "
                "must be set together (got model "
                f"{type(self.model).__name__} with loop_steps="
                f"{getattr(self.model, 'loop_steps', 1)}, "
                f"loss={cfg.loss!r}): the looped decoder returns its exits, "
                "not logits, and no other loss reads them")
        if fused_model and cfg.model.name not in ("llama", "gpt2"):
            raise ValueError(
                f"fused_lm_loss is implemented for llama/gpt2, not "
                f"{cfg.model.name!r}")
        if (getattr(cfg.model, "quant_training", "")
                and cfg.model.name not in ("llama", "llama_pp", "gpt2")):
            raise ValueError(
                f"quant_training is implemented for llama/llama_pp/gpt2, "
                f"not {cfg.model.name!r} (other models would silently "
                "ignore the knob)")
        if (cfg.model.num_experts > 1
                and cfg.model.moe_router == "expert_choice"
                and cfg.loss in ("causal_lm_xent", "fused_causal_lm_xent")
                and not cfg.model.moe_router_allow_noncausal):
            raise ValueError(
                "moe_router='expert_choice' with a causal-LM loss leaks "
                "future tokens into routing (selection ranks over the whole "
                "flattened batch — ops/moe.py::expert_choice_dispatch). Use "
                "moe_router='topk', or set "
                "model.moe_router_allow_noncausal=true to accept the "
                "Zhou et al. 2022 caveat explicitly")
        if cfg.lora.rank > 0 and cfg.optim.name == "schedule_free_adamw":
            raise ValueError(
                "lora + schedule_free_adamw is unsupported: the eval-time "
                "x/y unwrap (optim.schedule_free_eval) cannot locate the "
                "ScheduleFreeState through the lora optimizer mask "
                "(optax.multi_transform nests per-label inner states)")
        self.teacher_fn = None
        if cfg.loss == "dpo":
            # Preference fine-tuning: distill.teacher_checkpoint names the
            # frozen REFERENCE policy (the pre-DPO model) — loaded through
            # the same teacher machinery, consumed by a different loss.
            if not cfg.distill.teacher_checkpoint:
                raise ValueError(
                    "loss='dpo' needs distill.teacher_checkpoint pointing "
                    "at the frozen reference policy's run directory")
            if getattr(cfg.model, "fused_lm_loss", False):
                raise ValueError(
                    "loss='dpo' needs per-position logits — set "
                    "model.fused_lm_loss=false")
            self.loss_fn = losses_lib.make_dpo_loss(cfg.dpo_beta)
            # DPO eval scores the same preference objective (the eval
            # step injects the reference logits too)
            self.eval_loss_fn = self.loss_fn
        else:
            self.loss_fn = losses_lib.get_loss_fn(
                cfg.loss, label_smoothing=cfg.label_smoothing)
            # Eval always scores the plain objective; the KD wrap below
            # only applies to training.
            self.eval_loss_fn = self.loss_fn
        if cfg.distill.teacher_checkpoint:
            from pytorch_distributed_train_tpu import distill as distill_lib

            t_model, t_vars, t_cfg = distill_lib.load_teacher(
                cfg.distill, cfg.precision, self.mesh,
                "causal_lm_xent" if cfg.loss == "dpo" else cfg.loss)
            t_dim = (t_cfg.num_classes if cfg.loss == "softmax_xent"
                     else t_cfg.vocab_size)
            s_dim = (cfg.model.num_classes if cfg.loss == "softmax_xent"
                     else cfg.model.vocab_size)
            if t_dim != s_dim:
                raise ValueError(
                    f"teacher output dim ({t_dim}) != student ({s_dim}) — "
                    "the teacher/reference and student distributions must "
                    "live on the same classes/vocabulary")
            self.teacher_fn = distill_lib.make_teacher_fn(t_model, t_vars)
            if cfg.loss != "dpo":
                self.loss_fn = losses_lib.make_distill_loss(
                    self.loss_fn, cfg.loss, cfg.distill.alpha,
                    cfg.distill.temperature)
        self.rules = rules_for_model(cfg.model.name)

        # ---- elastic world (docs/elastic.md): with data.elastic_shards
        # the LAUNCHER env (NUM_PROCESSES / PROCESS_ID) — not the jax
        # process world — decides the data sharding, so a degraded
        # tpurun generation reshards the input stream to the surviving
        # hosts. The GLOBAL batch stays fixed (per-host batch rescales);
        # the loaders reject a world the batch cannot divide by.
        self.data_world: tuple[int, int] | None = None
        if cfg.data.elastic_shards:
            from pytorch_distributed_train_tpu.elastic import elastic_world

            self.data_world = elastic_world()
            if self.data_world[0] < jax.process_count():
                # elastic_world() == (1, 0) here means the env contract
                # is ABSENT (valid alone, catastrophic combined with a
                # multi-process jax world: every host would load the
                # full global batch — silent record duplication).
                raise RuntimeError(
                    f"data.elastic_shards: launcher world "
                    f"{self.data_world[0]} < jax process world "
                    f"{jax.process_count()} — the NUM_PROCESSES/"
                    "PROCESS_ID env contract is missing or stale; "
                    "sharding by it would duplicate records across "
                    "hosts")
        self.world = (self.data_world[0] if self.data_world is not None
                      else jax.process_count())

        # ---- data
        dw = self.data_world or (None, None)
        phase("train.init.data")
        self.train_ds = build_dataset(cfg.data, cfg.model, train=True)
        self.train_loader, self.train_epoch_fn = build_input_pipeline(
            self.train_ds, cfg.data, self.mesh, train=True,
            batch_axes=self.batch_axes,
            sync_check_every=cfg.obs.check_input_sync_every,
            num_hosts=dw[0], host_id=dw[1],
        )
        phase("train.init.eval_data")
        self.eval_ds = build_dataset(cfg.data, cfg.model, train=False)
        self.eval_loader, self.eval_epoch_fn = build_input_pipeline(
            self.eval_ds, cfg.data, self.mesh, train=False,
            batch_axes=self.batch_axes,
            num_hosts=dw[0], host_id=dw[1],
        )

        # ---- horizon
        phase("train.init.state")
        self.steps_per_epoch = self.train_loader.steps_per_epoch
        if cfg.epochs > 0:
            self.total_steps = cfg.epochs * self.steps_per_epoch
        else:
            self.total_steps = cfg.total_steps

        # ---- optimizer (adapter-only masking, when LoRA is on, happens
        # inside make_optimizer so MultiSteps stays the outermost wrapper)
        self.tx, self.lr_schedule = make_optimizer(
            cfg.optim, self.total_steps, self.steps_per_epoch,
            param_mask=(lambda tx: lora_lib.mask_optimizer(tx, cfg.lora))
            if cfg.lora.rank > 0 else None,
            sentinel_cooldown=cfg.sentinel.enabled,
        )

        # ---- compute-graph optimization layer (train.* knobs; steps.py
        # + ops/fused_update.py; docs/performance.md "Compute side").
        # Every invalid combination is refused loudly at construction —
        # a knob that silently does nothing records wrong measurements.
        tcfg = cfg.train
        if tcfg.grad_accum_steps > 1:
            if cfg.optim.accum_steps > 1:
                raise ValueError(
                    "train.grad_accum_steps and optim.accum_steps both "
                    "accumulate gradients — they would compound; use one "
                    "(grad_accum_steps scans microbatches in-graph, "
                    "accum_steps runs MultiSteps micro-steps)")
            # The scan splits what the step SEES: the global batch under
            # GSPMD jit, but the PER-SHARD batch under shard_map
            # (overlap_collectives) — validate the right unit here, not
            # at trace time with a misleading size in the message.
            shards = 1
            if tcfg.overlap_collectives:
                for ax in self.batch_axes:
                    shards *= max(self.mesh.shape.get(ax, 1), 1)
            if cfg.data.batch_size % shards:
                raise ValueError(
                    f"global batch {cfg.data.batch_size} not divisible "
                    f"by the {shards}-way batch sharding "
                    f"({'x'.join(self.batch_axes)})")
            unit = cfg.data.batch_size // shards
            if unit % tcfg.grad_accum_steps:
                raise ValueError(
                    f"train.grad_accum_steps={tcfg.grad_accum_steps} must "
                    f"divide the "
                    f"{'per-shard' if shards > 1 else 'global'} batch "
                    f"{unit}"
                    + (f" (global {cfg.data.batch_size} over {shards} "
                       f"shards)" if shards > 1 else ""))
        self.fused_update = None
        if tcfg.fused_epilogue:
            from pytorch_distributed_train_tpu.optim import (
                fused_update_unsupported_reason,
                make_fused_update,
            )

            reason = fused_update_unsupported_reason(
                cfg.optim, has_param_mask=cfg.lora.rank > 0)
            if reason is not None:
                raise ValueError(f"train.fused_epilogue: {reason}")
            if cfg.optim.ema_decay > 0.0 or \
                    getattr(cfg.optim, "swa_start_step", 0) > 0:
                raise ValueError(
                    "train.fused_epilogue does not maintain the EMA/SWA "
                    "mirror — disable optim.ema_decay/swa_start_step")
            self.fused_update = make_fused_update(
                cfg.optim, self.lr_schedule,
                sentinel_cooldown=cfg.sentinel.enabled)
        if tcfg.overlap_collectives:
            if cfg.optim.offload_state:
                raise ValueError(
                    "train.overlap_collectives + optim.offload_state: the "
                    "shard_map step cannot stage host-memory opt state")
            for ax in ("stage", "tensor", "context", "expert"):
                if self.mesh.shape.get(ax, 1) != 1:
                    raise ValueError(
                        "train.overlap_collectives is the DDP analogue — "
                        "pure data parallelism over the batch axes; mesh "
                        f"axis {ax!r}={self.mesh.shape[ax]} shards the "
                        "model (GSPMD already overlaps those collectives)")

        # ---- state (sharded init: params materialize directly into their
        # mesh layout — no host-RAM staging of 7B params; SURVEY C13)
        self.rng = jax.random.PRNGKey(cfg.seed)
        init_rng, self.step_rng = jax.random.split(self.rng)
        state_shape = jax.eval_shape(self._init_state, init_rng)
        self.state_sharding = steps_lib.state_shardings(
            self.mesh, self.rules, state_shape,
            zero_stage=cfg.mesh.zero_stage,
        )
        opt_dev_sharding = self.state_sharding.opt_state
        if cfg.optim.offload_state:
            if jax.devices()[0].platform == "cpu":
                raise ValueError(
                    "optim.offload_state needs a TPU backend — the CPU "
                    "backend cannot execute host-memory placement "
                    "(annotate_device_placement)")
            self.state_sharding = steps_lib.offload_state_shardings(
                self.state_sharding)
        with self.mesh:
            # runs once: the compiler's least effort (the hybrid preset's
            # init compiled in 12 s against 20: sandbox compile, PR 26)
            self.state: TrainState = jax.jit(
                self._init_state, out_shardings=self.state_sharding,
                compiler_options={"exec_time_optimization_effort": -1.0},
            )(init_rng)

        # ---- jitted steps
        phase("train.init.steps")
        # How often a leaf's gradient is all-reduced (steps.grad_reduce_plan
        # reads it off the layout; the train.compile span carries it): in
        # pure data parallelism a model with a tied leaf is told how many
        # ways the batch is split, and reduces that leaf once.
        self.grad_reduce = steps_lib.grad_reduce_plan(
            self.mesh, self.state_sharding, self.batch_axes)
        if cfg.train.overlap_collectives:  # the shard_map step reduces itself
            self.grad_reduce = self.grad_reduce._replace(
                mode="bucketed_in_scan", why="train.overlap_collectives")
        elif (self.grad_reduce.mode == "per_leaf"
              and hasattr(self.model, "tied_shards")):
            self.model = self.model.clone(
                tied_shards=self.grad_reduce.batch_devices)
        # A looped decoder uses every weight loop_steps times, and the
        # partitioner would reduce each use. In pure data parallelism its
        # step runs a replica's own program inside shard_map (the replica's
        # kernels as on one chip) and reduces the whole gradient tree once.
        # (The batch splits evenly over the replicas or no step runs: the
        # loader's device_put refuses it first.)
        replica_step = looped_model and self.grad_reduce.mode == "per_leaf"
        if replica_step:
            self.grad_reduce = self.grad_reduce._replace(
                why="a looped decoder: a replica's step inside shard_map, "
                    "the gradient tree reduced once")
        if jax.process_index() == 0:
            print(f"[parallel] grad all-reduce: {self.grad_reduce.mode} "
                  f"({self.grad_reduce.batch_devices} device(s) on "
                  f"{'x'.join(self.batch_axes)}; {self.grad_reduce.why})",
                  flush=True)
        from pytorch_distributed_train_tpu.ops.device_augment import (
            build_device_augment,
        )
        from pytorch_distributed_train_tpu.ops.mixup import build_mixup

        mixup = build_mixup(cfg.data, cfg.model, cfg.label_smoothing,
                            loss=cfg.loss)
        # Device-side augmentation (ops/device_augment.py): the dataset
        # decides applicability — only raw-u8 shippers get the transform
        # (host path byte-unchanged when data.device_augment is off).
        device_augment = build_device_augment(cfg.data, self.train_ds)
        param_transform = None
        if cfg.lora.rank > 0:
            param_transform = lambda p: lora_lib.merge(p, cfg.lora)  # noqa: E731
        reduce_grads = reduce_metrics = None
        self.grad_buckets = None
        if cfg.train.overlap_collectives:
            # Bucketed in-scan reduction (steps.overlap_grad_reducer):
            # buckets derived AOT from the params shape tree, reverse
            # parameter order, ~grad_bucket_mb each (DDP bucket_cap_mb).
            reduce_grads, self.grad_buckets = steps_lib.overlap_grad_reducer(
                state_shape.params, max(cfg.train.grad_bucket_mb, 1),
                self.batch_axes)
            reduce_metrics = steps_lib.metrics_reducer(self.batch_axes)
        reduce_grads_accum = None
        if replica_step:
            reduce_grads_accum = steps_lib.monolithic_grad_reducer(
                self.batch_axes)
            reduce_metrics = steps_lib.metrics_reducer(self.batch_axes)
        train_step = steps_lib.make_train_step(
            # inside shard_map a replica's model has no mesh to hand its
            # kernels: it is the one-chip model
            build_model(cfg.model, cfg.precision) if replica_step
            else self.model, self.loss_fn, self.tx,
            ema_decay=cfg.optim.ema_decay,
            swa_start=getattr(cfg.optim, "swa_start_step", 0),
            swa_every=getattr(cfg.optim, "swa_every", 1), mixup=mixup,
            device_augment=device_augment,
            module_grad_norms=cfg.obs.log_module_grad_norms,
            model_health=cfg.obs.model_health,
            param_transform=param_transform,
            teacher_fn=self.teacher_fn,
            numeric_guard=cfg.sentinel.enabled,
            grad_accum_steps=cfg.train.grad_accum_steps,
            fused_update=self.fused_update,
            reduce_grads=reduce_grads,
            reduce_grads_accum=reduce_grads_accum,
            reduce_metrics=reduce_metrics)
        self._step_resolved = train_step.resolved
        if cfg.optim.offload_state:
            train_step = steps_lib.offload_opt_state(
                train_step, opt_dev_sharding, self.state_sharding.opt_state)
        if replica_step or cfg.train.overlap_collectives:
            self.train_step = steps_lib.jit_overlap_train_step(
                train_step, self.mesh, self.state_sharding,
                self.batch_axes)
            if cfg.train.overlap_collectives and jax.process_index() == 0:
                print(f"[train] overlapped collectives: "
                      f"{len(self.grad_buckets)} grad bucket(s) x "
                      f"{cfg.train.grad_accum_steps} microbatch(es), "
                      f"bucket cap {cfg.train.grad_bucket_mb} MiB",
                      flush=True)
        else:
            self.train_step = steps_lib.jit_train_step(
                train_step, self.mesh, self.state_sharding, self.batch_axes,
            )
        # the jitted step under a name no wrapper replaces: a benchmark or
        # a test puts its own callable at ``train_step``, and the program
        # map (``_map_step_program``) needs the jit's ``.lower``
        self._jit_train_step = self.train_step
        self.eval_step = steps_lib.jit_eval_step(
            steps_lib.make_eval_step(
                self.model, self.eval_loss_fn,
                schedule_free=cfg.optim.name == "schedule_free_adamw",
                param_transform=param_transform,
                teacher_fn=self.teacher_fn if cfg.loss == "dpo" else None,
                device_augment=build_device_augment(cfg.data,
                                                    self.eval_ds)),
            self.mesh, self.state_sharding, self.batch_axes,
        )
        if cfg.lora.rank > 0 and jax.process_index() == 0:
            t, n = lora_lib.count_trainable(self.state.params, cfg.lora)
            print(f"[lora] rank={cfg.lora.rank} trainable {t:,} / "
                  f"{n:,} params ({100.0 * t / n:.2f}%)", flush=True)

        # ---- checkpoint + resume (auto is the default path, SURVEY §5.3b)
        # checkpoint.tiered selects the async tiered plane (ckpt/):
        # snapshot-only blocking at save boundaries, hot RAM/disk/peer
        # restore tiers, back-pressure drain re-attributed to the
        # ckpt.drain goodput bucket.
        # run_meta: every saved step records the world + global batch it
        # was trained under, so a resumed generation can tell a reshard
        # from a plain restart (and refuse a silently-changed global
        # batch — the one bookkeeping mistake that would corrupt LR/data
        # semantics without any error).
        phase("train.init.checkpoint")
        self.ckpt = build_checkpoint_manager(
            cfg.checkpoint, cfg.to_json(), goodput=self.goodput,
            run_meta={"world": self.world,
                      "global_batch": cfg.data.batch_size})
        self.best_ckpt = (BestCheckpointTracker(cfg.checkpoint, cfg.to_json())
                          if cfg.checkpoint.best_metric else None)
        if (cfg.lora.rank > 0 and cfg.lora.base_checkpoint
                and (cfg.checkpoint.resume == "none"
                     or self.ckpt.latest_good_step() is None)):
            # Fresh LoRA run: pull the frozen base from the pretrained
            # checkpoint. A restarted run (resume enabled + own ckpt
            # present) skips this — its resume below restores
            # base+adapters together, and re-reading the (potentially
            # 7B-scale) source checkpoint only to overwrite it would
            # waste minutes of IO per gang restart. With resume='none'
            # the own ckpt is never restored, so warm-start must run.
            self._warm_start_lora_base()
        self.start_epoch = 0
        self.resumed = False  # did construction restore a checkpoint?
        resume_mode = cfg.checkpoint.resume
        if resume_mode != "none":
            if resume_mode in ("auto", cfg.checkpoint.dir):
                restored = self.ckpt.restore(self.state)
            else:
                # explicit path: warm-start from a DIFFERENT run's directory
                src_cfg = dataclasses.replace(cfg.checkpoint, dir=resume_mode,
                                              resume="none")
                src = CheckpointManager(src_cfg)
                restored = src.restore(self.state)
                src.close()
            if restored is not None:
                self.state, meta = restored
                self.resumed = True
                self.start_epoch = int(meta.get("epoch", 0))
                events_lib.emit("ckpt", "restore",
                                step=int(self.state.step),
                                epoch=self.start_epoch,
                                source=resume_mode)
                if jax.process_index() == 0:
                    print(f"[resume] restored step {int(self.state.step)} "
                          f"(epoch {self.start_epoch})", flush=True)
                self._note_reshard(meta)
            elif resume_mode not in ("auto",):
                raise FileNotFoundError(
                    f"checkpoint.resume={resume_mode!r} has no checkpoint to restore"
                )

        # ---- observability
        phase("train.init.planes")
        jsonl = cfg.obs.jsonl_path or f"{cfg.checkpoint.dir}/metrics.jsonl"
        tb_dir = f"{cfg.checkpoint.dir}/tb" if cfg.obs.tensorboard else ""
        self.logger = MetricLogger(jsonl, tb_dir)
        self.meter = Meter()
        # MFU accounting (utils/flops.py): analytic train FLOPs per
        # throughput item over the chip's bf16 peak. An unlisted model or
        # the CPU backend disables the metric; a TPU kind missing from
        # the peaks table raises.
        self._flops_per_item = flops_lib.train_flops_per_item(
            cfg.model, getattr(cfg.data, "seq_len", None) or None)
        self._peak_flops = flops_lib.device_peak_flops(jax.devices()[0])
        self.recorder = FlightRecorder(dump_dir=cfg.checkpoint.dir)
        self.recorder.install_signal_dump()
        # Graceful preemption (faults/preemption.py): SIGTERM sets a
        # flag; the step loop checkpoints and exits cleanly. Composes
        # with the dump handler above in either install order — the
        # dump still happens, but the loop owns process exit.
        self.preempt = None
        self._preempted = False
        if cfg.faults.graceful_preemption:
            from pytorch_distributed_train_tpu.faults.preemption import (
                PreemptionHandler,
            )

            self.preempt = PreemptionHandler()
            self.preempt.install()
        self.heartbeat = Heartbeat(cfg.obs.heartbeat_timeout_s, self.recorder)
        # ---- managed profiler plane (obs/profiler.py): bounded capture
        # windows on cadence / on demand / on anomaly; the legacy
        # obs.profile_* fixed window rides through it as a shim.
        self.profiler = profiler_lib.ManagedProfiler(
            cfg.obs, run_dir=cfg.checkpoint.dir)
        self.profiler.start()
        # ---- unified obs layer (obs/): spans + registry + goodput.
        # One process-wide span ring — checkpoint saves, data producer
        # threads and the step loop interleave on a single exported
        # timeline; the watchdog dumps it on abort next to its events.
        self.recorder.attach_spans(self.spans)
        self.registry = get_registry()
        self._step_hist = self.registry.histogram(
            "train_step_seconds",
            help="wall seconds between consecutive train-step completions "
                 "(meter intervals; excludes compile and eval gaps)")
        self.metrics_server = None
        if cfg.obs.metrics_port:
            from pytorch_distributed_train_tpu.obs.exposition import (
                MetricsServer,
            )

            try:
                self.metrics_server = MetricsServer(cfg.obs.metrics_port)
            except OSError:
                # Port collision: obs.metrics_port is one shared config
                # value but several workers can share a host (tpurun
                # --nprocs > 1). The sidecar is a diagnostic surface —
                # crashing the trainer over it would be backwards; fall
                # back to an ephemeral port and publish the ACTUAL port
                # through the store endpoint record below.
                self.metrics_server = MetricsServer(0)
                print(f"[obs] metrics port {cfg.obs.metrics_port} in use "
                      f"(another local worker?); bound ephemeral port "
                      f"{self.metrics_server.port} instead", flush=True)
            # POST /profile on the sidecar opens a TIME-bounded capture
            # (capture_for_seconds, not a step window): the route's
            # whole point is poking a run that may be wedged, and a
            # step-windowed request would wait forever on a step loop
            # that never advances.
            from pytorch_distributed_train_tpu.obs import exposition

            self._profile_trigger = (
                lambda: self.profiler.capture_for_seconds(10.0,
                                                          reason="http"))
            exposition.set_profile_trigger(self._profile_trigger)
            if jax.process_index() == 0:
                print(f"[obs] /metrics on port {self.metrics_server.port}",
                      flush=True)
            # Self-register the scrape endpoint with the launcher store
            # (elastic.publish_obs_endpoint) so the fleet collector
            # discovers this host without static config — the ACTUAL
            # bound port, which may differ from obs.metrics_port after
            # the collision fallback above. Best-effort: no store (not
            # under tpurun) just means no fleet discovery.
            try:
                from pytorch_distributed_train_tpu import elastic, store_plane

                store = store_plane.resilient_worker_store(
                    name="trainer-advertise")
                if store is not None:
                    addr = (f"{elastic.routable_host('')}"
                            f":{self.metrics_server.port}")
                    elastic.publish_obs_endpoint(store, "trainer", addr)
                    store.close()
                    print(f"[obs] registered fleet endpoint {addr}",
                          flush=True)
            except Exception:
                pass
        self._stepped = False  # first train_step call = compile bucket
        # Eval's share of the process-global input-stage stats
        # (obs/perf.py), snapshot-deltas around evaluate(): the summary
        # stage keys and the ledger's stall_split must blame the TRAIN
        # pipeline — the thing input_stall measures — not a large eval
        # set's decode time. (Approximation: the train producer keeps
        # refilling its bounded queue during eval; the error is capped
        # by the prefetch depth in batches.)
        self._eval_stage_s = {s: 0.0 for s in perf_lib.STAGES}
        # ---- training health sentinel (sentinel/): numeric plane state
        # (the in-graph gate is already inside the jitted step; this is
        # the host-side spike window + rewind bookkeeping) and the
        # cross-host liveness plane (store heartbeats + hang monitor).
        self._sentinel_on = cfg.sentinel.enabled
        self._spike = None
        self._bad_streak = 0
        self._rewinds = 0
        self._sentinel_skipped = 0
        self._sentinel_aborted = False
        if self._sentinel_on:
            self._spike = sentinel_numeric.SpikeDetector(
                window=cfg.sentinel.spike_window,
                sigma=cfg.sentinel.spike_sigma,
                min_samples=cfg.sentinel.spike_min_samples,
                min_rel=cfg.sentinel.spike_min_rel)
        # ---- model-health monitor (obs/model_health.py): per-series
        # spike detection over the host metrics record at log cadence —
        # divergence early warning on the training-dynamics telemetry
        # the in-graph pass (ops/model_health.py) lands in the step
        # metrics. Arms the SAME rewind path as the loss sentinel, but
        # fires on the precursors (grad/update norms, reward/KL drift)
        # steps before the loss moves. Independent of sentinel.enabled:
        # the monitor reads metrics already on host, no extra sync.
        self.health = None
        if cfg.obs.model_health:
            from pytorch_distributed_train_tpu.obs import (
                model_health as model_health_lib,
            )

            self.health = model_health_lib.ModelHealthMonitor(
                profiler=self.profiler)
        self.liveness = None
        if cfg.sentinel.hang_timeout_s > 0:
            from pytorch_distributed_train_tpu.sentinel.liveness import (
                LivenessPlane,
            )

            plane = LivenessPlane(
                hang_timeout_s=cfg.sentinel.hang_timeout_s,
                poll_s=cfg.sentinel.hang_poll_s,
                exit_code=cfg.sentinel.hang_exit_code,
                every_steps=cfg.sentinel.heartbeat_every_steps,
                recorder=self.recorder, spans=self.spans)
            if plane.start():
                self.liveness = plane
                print(f"[sentinel] liveness plane up (host {plane.rank}/"
                      f"{plane.world}, timeout "
                      f"{cfg.sentinel.hang_timeout_s}s)", flush=True)
        events_lib.emit("lifecycle", "trainer_init",
                        step=int(self.state.step), resumed=self.resumed,
                        world=self.world,
                        init_s=round(time.perf_counter() - _t_init0, 3))
        self.goodput.account("init", time.perf_counter() - _t_init0)

    # ------------------------------------------------------------------ init
    def _note_reshard(self, meta: dict) -> None:
        """Elastic reshard bookkeeping at restore time (docs/elastic.md).

        The checkpoint's run_meta says what world/global-batch it was
        written under. A changed WORLD is the supported reshard: the
        restore above already re-derived shardings for the new mesh and
        the loaders already recomputed per-host shards — journal it
        (the event the acceptance drill and timeline_report look for)
        and carry on. A changed GLOBAL BATCH under elastic_shards is
        refused loudly: the documented policy keeps the global batch
        fixed across generations (per-host batch rescales), because a
        silently different global batch shifts the LR schedule's
        step<->data mapping and every union-of-shards guarantee."""
        saved_world = meta.get("world")
        saved_gb = meta.get("global_batch")
        if (self.cfg.data.elastic_shards and saved_gb is not None
                and int(saved_gb) != int(self.cfg.data.batch_size)):
            raise ValueError(
                f"elastic resume with a different GLOBAL batch "
                f"(checkpoint: {saved_gb}, config: "
                f"{self.cfg.data.batch_size}): the reshard policy keeps "
                "the global batch fixed and rescales the per-host batch "
                "— change data.batch_size back, or start a fresh run")
        if saved_world is None or int(saved_world) == int(self.world):
            return
        detail = dict(from_world=int(saved_world), to_world=int(self.world),
                      global_batch=int(self.cfg.data.batch_size),
                      devices=jax.device_count())
        events_lib.emit("elastic", "reshard", step=int(self.state.step),
                        **detail)
        if getattr(self, "recorder", None) is not None:
            self.recorder.record("reshard", int(self.state.step), **detail)
        print(f"[elastic] resharded restore: checkpoint written on world "
              f"{saved_world}, resuming on world {self.world} "
              f"(global batch {self.cfg.data.batch_size} fixed; per-host "
              f"batch {self.cfg.data.batch_size // max(self.world, 1)})",
              flush=True)

    def _warm_start_lora_base(self):
        """lora.base_checkpoint: restore the BASE params subtree from a
        pretrained run's latest checkpoint into this run's (adapter-
        injected) state. Adapters keep their fresh identity init, so the
        warm-started model is exactly the pretrained model at step 0."""
        cfg = self.cfg
        src_cfg = dataclasses.replace(
            cfg.checkpoint, dir=cfg.lora.base_checkpoint, resume="none")
        src = CheckpointManager(src_cfg)
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            self.state.params)
        base = src.restore_params_only(lora_lib.strip_abstract(abstract))
        src.close()
        if base is None:
            raise FileNotFoundError(
                f"lora.base_checkpoint={cfg.lora.base_checkpoint!r} has no "
                "checkpoint to warm-start from")
        self.state = self.state.replace(
            params=lora_lib.transplant_base(self.state.params, base))
        if jax.process_index() == 0:
            print(f"[lora] warm-started base params from "
                  f"{cfg.lora.base_checkpoint}", flush=True)

    def _init_state(self, rng):
        dummy = self._dummy_inputs()
        variables = self.model.init({"params": rng}, *dummy, train=False)
        params = variables["params"]
        if self.cfg.lora.rank > 0:
            params = lora_lib.inject(
                jax.random.fold_in(rng, 0x10FA), params, self.cfg.lora)
        batch_stats = variables.get("batch_stats", {})
        ds = None
        ls = self.cfg.precision.loss_scale
        if ls == "dynamic":
            ds = DynamicScale.create(
                self.cfg.precision.loss_scale_init,
                self.cfg.precision.loss_scale_growth_interval,
            )
        elif ls != "none":
            # static scale: fixed value, never grows (still halves on
            # overflow as a safety net, like GradScaler with growth off)
            ds = DynamicScale.create(float(ls), growth_interval=2**31 - 1)
        return TrainState.create(
            params=params, tx=self.tx, batch_stats=batch_stats,
            dynamic_scale=ds, ema=self.cfg.optim.ema_decay > 0.0,
            swa=getattr(self.cfg.optim, "swa_start_step", 0) > 0,
        )

    def _dummy_inputs(self) -> tuple:
        return steps_lib.dummy_inputs(self.cfg.loss, self.cfg.model,
                                      self.cfg.data)

    @property
    def items_per_step(self) -> int:
        if self.cfg.loss == "softmax_xent":
            return self.cfg.data.batch_size  # images/step
        if self.cfg.loss == "dpo":  # each row is a (chosen, rejected) pair
            return 2 * self.cfg.data.batch_size * self.cfg.data.seq_len
        return self.cfg.data.batch_size * self.cfg.data.seq_len  # tokens/step

    # ------------------------------------------------------------------ loop
    def compile_report(self, batch_size: int | None = None) -> dict:
        """AOT-compile the train step (no step runs) and return the
        compiler's per-device memory accounting — the `--compile-only`
        "will this config fit" probe (the torch-world analogue is running
        a step and reading torch.cuda.memory_summary; XLA can answer
        before any step executes). Args/outputs alias through donation,
        so resident ≈ args + temps. ``batch_size`` overrides the config's
        GLOBAL batch for this lowering only (the state and step function
        are batch-shape-agnostic — find_batch_size re-lowers at many
        sizes off one Trainer). Backend caveat: XLA:CPU gives remat
        regions distinct temp allocations (see tools/memfit_7b.py) — on
        CPU treat temps as an upper bound."""
        first = next(iter(self.train_loader.epoch(0)))
        gb = batch_size or self.cfg.data.batch_size
        batch = {
            k: jax.ShapeDtypeStruct((gb,) + np.asarray(v).shape[1:],
                                    np.asarray(v).dtype)
            for k, v in first.items()
        }
        t0 = time.time()
        compiled = self.train_step.lower(
            self.state, batch, self.step_rng).compile()
        out = {"compile_s": round(time.time() - t0, 1),
               "n_devices": jax.device_count(),
               "global_batch": gb}
        try:
            ma = compiled.memory_analysis()
            out.update(
                arg_bytes=int(ma.argument_size_in_bytes),
                out_bytes=int(ma.output_size_in_bytes),
                temp_bytes=int(ma.temp_size_in_bytes),
                resident_bytes=int(ma.argument_size_in_bytes
                                   + ma.temp_size_in_bytes),
            )
        except Exception as e:  # pragma: no cover - backend-dependent
            out["memory_analysis_error"] = f"{type(e).__name__}: {e}"
        return out

    def find_batch_size(self, budget_bytes: int | None = None,
                        max_global: int = 1 << 20) -> dict:
        """Largest fitting GLOBAL batch by AOT memory accounting (the
        torch-world auto_scale_batch_size, but from the compiler instead
        of OOM-probing real steps — no device memory is ever touched).

        Doubles from the configured batch while the compiled step's
        per-device resident bytes fit ``budget_bytes`` (default: the
        device's reported memory limit), then bisects. Candidates stay
        multiples of the mesh's batch-axis extent (data x fsdp) so every
        probe is a shardable shape. Returns {fits: [...probes...],
        best_global, best_per_chip, budget_bytes}; a config whose
        CONFIGURED batch already exceeds the budget reports best 0."""
        if budget_bytes is None:
            stats = jax.local_devices()[0].memory_stats() or {}
            budget_bytes = stats.get("bytes_limit")
            if not budget_bytes:
                raise ValueError(
                    "device reports no memory limit (CPU backend?) — "
                    "pass an explicit budget (--hbm-gb)")
        # Batch-axis extent from the BUILT mesh (config axes may be -1 =
        # fill-with-remaining-devices).
        unit = 1
        for ax in ("data", "fsdp"):
            unit *= max(self.mesh.shape.get(ax, 1), 1)

        probes: list[dict] = []

        def fits(gb: int) -> bool:
            try:
                rep = self.compile_report(batch_size=gb)
            except jax.errors.JaxRuntimeError as e:
                # The TPU compiler enforces the device's memory itself: a
                # batch past it is refused at buffer assignment
                # ("RESOURCE_EXHAUSTED ... Ran out of memory in memory
                # space hbm"), which IS the answer here. Anything else
                # the compiler raises is a fault and propagates.
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                rep = {"global_batch": gb,
                       "compiler_refused": str(e).splitlines()[0][:300]}
            rep["fits"] = (rep.get("resident_bytes", budget_bytes + 1)
                           <= budget_bytes)
            probes.append(rep)
            if jax.process_index() == 0:
                print(f"[find-batch-size] global={gb} resident="
                      f"{rep.get('resident_bytes', -1) / 1024**3:.2f} GiB "
                      f"budget={budget_bytes / 1024**3:.2f} GiB "
                      f"fits={rep['fits']}", flush=True)
            return rep["fits"]

        base = max(self.cfg.data.batch_size // unit, 1) * unit
        lo = 0
        gb = base
        while gb <= max_global and fits(gb):
            lo, gb = gb, gb * 2
        if lo == 0:  # configured batch itself does not fit
            return {"budget_bytes": budget_bytes, "best_global": 0,
                    "best_per_chip": 0, "probes": probes}
        hi = gb  # known not to fit (or beyond max_global)
        # bisect on multiples of `unit` in (lo, hi)
        while hi - lo > unit:
            mid = ((lo + hi) // 2) // unit * unit
            if mid in (lo, hi):
                break
            if fits(mid):
                lo = mid
            else:
                hi = mid
        return {"budget_bytes": budget_bytes, "best_global": lo,
                "best_per_chip": lo // max(jax.device_count(), 1),
                "probes": probes}

    def fit(self, max_steps: int | None = None) -> TrainState:
        cfg = self.cfg
        limit = min(self.total_steps, max_steps or self.total_steps)
        step = int(self.state.step)
        epoch = self.start_epoch
        t_start = time.time()
        events_lib.emit("lifecycle", "fit_start", step=step, limit=limit,
                        epoch=epoch)
        try:
            while step < limit:
                self.recorder.record("epoch_start", step, epoch=epoch)
                # Mid-epoch resume: continue the epoch's batch stream at the
                # restored step's offset instead of replaying it (the
                # per-batch rng seeding makes this exact — data/pipeline.py).
                start_b = max(0, step - epoch * self.steps_per_epoch)
                if start_b >= self.steps_per_epoch:
                    start_b = 0  # stale epoch meta; just run a fresh epoch
                rewound = False
                # One turn of this loop is one ``train.iteration`` span,
                # opened BEFORE the loader's next() so that the wait for
                # the batch (``train.input_wait``) lies inside it; what the
                # turn's children do not cover is this loop's own
                # bookkeeping. StepTraceAnnotation puts the same turn in a
                # profiler capture's host trace (an atomic load otherwise).
                batches = self._timed_batches(
                    self.train_epoch_fn(epoch, start_b))
                while step < limit:
                    with self.spans.span("train.iteration",
                                         step=step) as turn, \
                            jax.profiler.StepTraceAnnotation(
                                "train", step_num=step):
                        batch = next(batches, None)
                        if batch is None:
                            # the epoch is exhausted: a turn that only
                            # waited, and no iteration to a reader
                            turn.args["epoch_end"] = True
                            break
                        self.profiler.on_step(step)
                        # Sentinel drill points (flag-kind: firing only
                        # reports a match; the corruption is ours to stage).
                        # step.nan@step=N poisons the batch of the step that
                        # completes as N+1 — the in-graph guard must then
                        # skip exactly that update. step.loss_spike inflates
                        # only the OBSERVED loss (detection drill; params
                        # untouched).
                        inflate_loss = self.faults.maybe_fire(
                            "step.loss_spike", step=step)
                        # step.grad_spike inflates only the OBSERVED grad/
                        # update telemetry (post-backward, pre-anything the
                        # monitor reads) — the early-warning drill: the
                        # model-health plane must fire on it while the loss
                        # stays healthy, so the sentinel never trips.
                        inflate_grads = self.faults.maybe_fire(
                            "step.grad_spike", step=step)
                        if self.faults.maybe_fire("step.nan", step=step):
                            batch = _poison_batch_nan(batch)
                        # First execution per process = jit trace + compile
                        # (+ one step); goodput attributes it to the compile
                        # bucket — recompile cost on restart-heavy jobs is
                        # precisely what goodput accounting exists to show.
                        is_first = not self._stepped
                        # (gen, step) correlation tag: every span completed
                        # from here on — step-loop, ckpt, producer threads —
                        # carries the trainer's position, the id serving
                        # traces correlate against (obs/tracing.py).
                        spans_lib.set_correlation_tags(step=step)
                        with self.spans.span(
                                "train.compile" if is_first else "train.step",
                                step=step, **(
                                    {"grad_reduce": self.grad_reduce.mode,
                                     "batch_devices":
                                         self.grad_reduce.batch_devices}
                                    if is_first else {})) as dispatch:
                            self.state, metrics = self.train_step(
                                self.state, batch, self.step_rng
                            )
                            if is_first:  # what the trace resolved to
                                dispatch.args.update(self._step_resolved)
                        self._stepped = True
                        if is_first:
                            self._map_step_program(batch, step)
                        if inflate_loss:
                            # step.loss_spike drill: corrupt the OBSERVED
                            # loss everywhere one observation is read —
                            # the log record, the scrape mirror the fleet
                            # collector reads, and the sentinel below all
                            # see the same spike; params stay healthy.
                            # (Lazy jnp multiply: no device sync here.)
                            metrics = dict(metrics,
                                           loss=metrics["loss"] * 1e6)
                        if inflate_grads:
                            # step.grad_spike drill: same observation-only
                            # stance — every grad/update telemetry reader
                            # (log record, scrape mirror, fleet collector,
                            # model-health monitor) sees the spike; params
                            # and the loss stay healthy. (Lazy jnp multiply:
                            # no device sync here.)
                            metrics = {
                                k: (v * 1e3 if k.startswith(
                                    ("grad_norm", "update_norm",
                                     "update_ratio")) else v)
                                for k, v in metrics.items()}
                        # Host-side step counter: int(state.step) every step
                        # would sync the device and serialize async dispatch
                        # (the jitted step increments state.step identically,
                        # including loss-scale skip steps).
                        step += 1
                        self._maybe_inject_fault(step)
                        self._maybe_inject_stall(step)
                        dt_tick = self.meter.tick()
                        if dt_tick is not None:
                            self._step_hist.observe(dt_tick)
                            # step-time regression detector (anomaly plane):
                            # a meter tick that spikes off the rolling
                            # median+MAD baseline journals an anomaly and
                            # (opt-in) opens a capture window
                            self.profiler.observe_step_time(dt_tick, step)
                        if dt_tick is None:
                            # Priming tick (first step after a clock reset —
                            # epoch boundary or mid-epoch eval): its interval
                            # is excluded from meter.total_s, so drop the
                            # matching stall seconds (the producer cold-start
                            # wait) from the numerator too. Numerator and
                            # denominator must cover the SAME intervals or
                            # input_stall_pct can exceed 100% and spuriously
                            # fail the sustained drill's <5% gate.
                            stats = getattr(self.train_loader, "stall_stats",
                                            None)
                            if stats is not None:
                                self._stall_prev = (stats.wait_s,
                                                    self.meter.total_s)
                        self.heartbeat.beat()
                        if self.liveness is not None:
                            self.liveness.beat(step)
                        self.recorder.record("step", step)
                        if (step % cfg.obs.log_every_steps == 0
                                or step == limit):
                            with self.spans.span("train.log", step=step):
                                host_rec = self._log_train(step, metrics)
                            if (self.health is not None
                                    and self.health.observe(step, host_rec)):
                                # Early-warning rewind: the model-health
                                # monitor armed on divergence PRECURSORS
                                # (grad/update norms, reward/KL) — same
                                # restore+cooldown path as the loss
                                # sentinel, steps earlier.
                                step = self._sentinel_rewind(step)
                                epoch = step // max(self.steps_per_epoch, 1)
                                self.meter.reset_clock()
                                rewound = True
                                break
                        # The step bucket closes AFTER the (cadenced) log:
                        # _log_train's device sync is where async-dispatched
                        # compute gets waited on host-side, and that wait is
                        # step time, not idle. The bucket opened with the
                        # dispatch span: one clock read for both.
                        self.goodput.account(
                            "compile" if is_first else "step",
                            time.perf_counter() - dispatch.start_s)
                        if self._sentinel_on and self._sentinel_observe(
                                step, metrics):
                            # Auto-rewind: BEFORE the cadence save below, so
                            # the diverged state is never checkpointed on
                            # the way out. The while loop re-enters with the
                            # rewound step and the exact mid-epoch
                            # start_batch fast-forward.
                            step = self._sentinel_rewind(step)
                            epoch = step // max(self.steps_per_epoch, 1)
                            self.meter.reset_clock()
                            rewound = True
                            break
                        with self.goodput.measure("ckpt"):
                            # A state under suspicion (mid bad-streak: spiking
                            # but finite, so updates DID apply) must not be
                            # checkpointed — the coming rewind would otherwise
                            # restore the very divergence it escapes.
                            if self._bad_streak == 0 and self.ckpt.maybe_save(
                                    self.state, epoch=epoch, step=step):
                                self.recorder.record("ckpt", step)
                                events_lib.emit("ckpt", "save", step=step,
                                                epoch=epoch)
                                if self.liveness is not None:
                                    # A synchronous cadence save (or a tiered
                                    # back-pressure drain) can outlast
                                    # hang_timeout_s on a loaded host; saving
                                    # is progress, not a wedge.
                                    self.liveness.pulse()
                        if (cfg.eval_every_steps and
                                step % cfg.eval_every_steps == 0):
                            with self.goodput.measure("eval"):
                                self.evaluate(step)
                            # Mid-epoch eval: keep its wall time out of the
                            # step-time percentiles AND the input-stall
                            # denominator (meter.total_s).
                            self.meter.reset_clock()
                        if self.preempt is not None and self.preempt.requested:
                            # Graceful preemption: stop at this step boundary;
                            # fit()'s finally force-saves the synchronized
                            # checkpoint and the summary carries the marker.
                            self._preempted = True
                            self.recorder.record("preempt", step)
                            events_lib.emit("preempt", "sigterm", step=step)
                            if jax.process_index() == 0:
                                print(f"[preempt] stopping at step {step}; "
                                      "checkpointing and exiting cleanly",
                                      flush=True)
                            break
                # Early exits (step cap, rewind, preemption) stop the
                # loader's producer NOW; after an exception the frame's
                # end does, as it did for the for-loop this replaces.
                batches.close()
                if self._preempted:
                    break
                if rewound:
                    continue  # re-enter at the restored step, not a new epoch
                epoch += 1
                if not cfg.eval_every_steps:
                    # every epoch boundary INCLUDING the last: the final
                    # validation metric is the acceptance-matrix number
                    with self.goodput.measure("eval"):
                        self.evaluate(step)
                self.meter.reset_clock()  # epoch boundary: don't count eval time
            if (not self._preempted
                    and getattr(cfg.optim, "swa_update_bn_batches", 0) > 0
                    and self.state.ema_params is not None
                    and self.state.batch_stats
                    and (self.state.swa_count is None
                         or int(self.state.swa_count) > 0)):
                # torch swa_utils recipe: averaged weights need freshly
                # estimated BN stats. Guards: an SWA run that never
                # reached swa_start has an INIT-weights mirror — stats
                # estimated under it would poison the checkpoint. The
                # fresh stats exist for the MIRROR; the eval (logged
                # under eval_swa, the deliverable metric — also what the
                # best-checkpoint tracker sees) runs on them, then the
                # trajectory stats come back so the cadence checkpoint
                # stays consistent with state.params for resume (torch
                # keeps swa_model's BN stats separate for the same
                # reason).
                trajectory_stats = self.state.batch_stats
                self.update_bn(cfg.optim.swa_update_bn_batches)
                self.evaluate(step, prefix="eval_swa")
                self.state = self.state.replace(
                    batch_stats=trajectory_stats)
        finally:
            self.heartbeat.stop()
            # A capture window still open at the horizon (or on an
            # abort) must stop + summarize NOW — an unterminated
            # profiler session would leak into teardown.
            self.profiler.finish(step)
            # NOTE: the liveness plane deliberately OUTLIVES fit() (it
            # stops in close()): a multi-host job that finished its loop
            # can still wedge in the final synchronized save or in a
            # peer's teardown barrier, and the hang monitor must keep
            # watching exactly through that window.
            if self.liveness is not None:
                self.liveness.pulse()  # the final save can be minutes-long
            with self.goodput.measure("ckpt"):
                # A sentinel abort (rewind budget exhausted) means the
                # live state is known-diverged: force-saving it would
                # make it the newest verified checkpoint and trap every
                # later generation in a restore/diverge loop.
                if not self._sentinel_aborted:
                    if self.ckpt.save(self.state, epoch=epoch, force=True,
                                      step=step):
                        events_lib.emit("ckpt", "save", step=step,
                                        epoch=epoch, final=True)
                self.ckpt.wait()
            if self.best_ckpt is not None:
                self.best_ckpt.close()
            stage_s = self._train_stage_seconds()
            self.logger.log(
                step,
                {"wall_time_s": time.time() - t_start,
                 "preempted": int(self._preempted),
                 "rewinds": self._rewinds,
                 "sentinel_skipped_steps": self._sentinel_skipped,
                 # staged input breakdown (obs/perf.py): the per-stage
                 # split of the TRAIN host-pipeline work behind
                 # input_stall (eval's share subtracted)
                 **{f"input_stage_s_{k}": round(v, 4)
                    for k, v in stage_s.items() if v > 0},
                 **self._input_plane_metrics(),
                 **self.meter.percentiles(), **self.goodput.snapshot()},
                prefix="summary",
            )
            self._append_perf_ledger(step)
            self.logger.close()
            self._dump_trace()
            events_lib.emit("lifecycle", "fit_end", step=step,
                            preempted=self._preempted,
                            rewinds=self._rewinds,
                            wall_s=round(time.time() - t_start, 3))
        return self.state

    def _input_plane_metrics(self) -> dict:
        """Input-plane counters for the summary record (ISSUE 12):
        shared-memory pool occupancy/batches and packed-cache hit
        activity, read back from the registry the pool/cache write
        into. Zero-activity keys are omitted — a run without the pool
        or cache keeps its summary line unchanged."""
        from pytorch_distributed_train_tpu.obs.registry import get_registry

        reg = get_registry()
        out = {}
        for key, name, kind in (
                ("input_worker_occupancy", "input_worker_occupancy", "g"),
                ("input_worker_batches", "input_worker_batches_total", "f"),
                ("input_effective_workers", "input_effective_workers", "f"),
                ("packed_cache_hits", "packed_cache_hits_total", "f"),
                ("packed_cache_misses", "packed_cache_misses_total", "f"),
                ("packed_cache_records_read",
                 "packed_cache_records_read_total", "f"),
        ):
            v = (reg.get_value(name) if kind == "g"
                 else reg.family_total(name))
            if v:
                out[key] = round(float(v), 4)
        return out

    def _train_stage_seconds(self) -> dict:
        """The TRAIN pipeline's share of the process-global input-stage
        seconds: global totals minus the eval deltas accumulated around
        evaluate() (obs/perf.py stage vocabulary, floored at 0)."""
        out = {}
        for k, v in perf_lib.get_input_stats().snapshot().items():
            out[k] = max(0.0, v - self._eval_stage_s.get(k, 0.0))
        return out

    def _append_perf_ledger(self, step: int) -> None:
        """One perf-ledger row per fit() (rank 0): throughput, MFU,
        goodput and the stall-stage split — the trainer-side feed of the
        bench-history regression gate (obs/perf.py, docs/performance.md).
        Best-effort: the ledger must never fail the run."""
        cfg = self.cfg
        if not cfg.obs.perf_ledger or jax.process_index() != 0:
            return
        try:
            tput = self.meter.throughput(self.items_per_step)
            if tput is None:
                return  # no timed steps (smoke construction, 0-step fit)
            unit = "images" if cfg.loss == "softmax_xent" else "tokens"
            per_chip = tput / jax.device_count()
            mfu = flops_lib.mfu_pct(per_chip, self._flops_per_item,
                                    self._peak_flops)
            goodput = self.goodput.snapshot()
            path = (cfg.obs.perf_ledger_path
                    or os.environ.get(perf_lib.ENV_LEDGER)
                    or os.path.join(cfg.checkpoint.dir,
                                    "perf_ledger.jsonl"))
            perf_lib.PerfLedger(path).append(
                f"{cfg.model.name}_train_{unit}_per_sec_per_chip",
                round(per_chip, 2), unit=f"{unit}/sec/chip",
                source="trainer", config=cfg.to_json(),
                mfu_pct=None if mfu is None else round(mfu, 2),
                goodput_pct=goodput.get("goodput_pct"),
                stall_split=perf_lib.normalize_split(
                    self._train_stage_seconds()) or None,
                step=step)
        except Exception as e:
            print(f"[perf-ledger] trainer append failed "
                  f"({type(e).__name__}: {e})", flush=True)

    def _map_step_program(self, batch, step: int) -> None:
        """After the first step: which scope made each instruction of the
        compiled step (obs/step_program.py), inside a ``train.program_map``
        span beside ``train.compile``. The jitted step, lowered again for
        the live state and this batch on this thread, comes from JAX's
        in-process caches (no trace, no lowering, no backend compile), so
        the span costs one text dump and one pass over it. Best effort,
        never an exception: a step that was not the jit's (a plain function
        put at ``train_step``), or one whose lowering does compile (JAX's
        own compile event lands inside the span: the program described
        would not be the one that ran), leaves no map and a
        ``program_map=none: <reason>`` attribute."""
        with self.spans.span("train.program_map", step=step) as sp:
            step_program.clear()
            try:
                compiled = self._jit_train_step.lower(
                    self.state, batch, self.step_rng).compile()
                if any(s.name == "jax.compile" and s.parent_seq == sp.seq
                       for s in self.spans.events()):
                    raise RuntimeError(
                        "lowering the step again compiled it: the program "
                        "described would not be the one that ran")
                built = step_program.record(compiled, step)
            except Exception as e:  # noqa: BLE001 - observability only
                sp.args["program_map"] = f"none: {type(e).__name__}: {e}"
                return
            sp.args.update(instructions=built.instructions,
                           fusions=built.fusions,
                           mixed_fusions=len(built.mixed),
                           text_mb=round(built.text_bytes / 1e6, 3))

    def _timed_batches(self, it):
        """Yield from the epoch iterator, accounting time blocked in its
        next() to the goodput input_stall bucket — the host-pipeline wait
        as the STEP LOOP experiences it (device_put assembly included),
        complementing StallStats' producer-queue view."""
        it = iter(it)
        _done = object()
        try:
            while True:
                # the span and the bucket share one pair of clock reads
                with self.spans.span("train.input_wait") as wait:
                    batch = next(it, _done)
                self.goodput.account("input_stall", wait.dur_s)
                if batch is _done:
                    return
                yield batch
        finally:
            # Propagate early exits (step cap break) to the underlying
            # generator NOW — device_prefetch's finally stops the
            # producer thread; leaving that to GC would leak it until
            # collection.
            close = getattr(it, "close", None)
            if close is not None:
                close()

    def _dump_trace(self) -> None:
        """Write the host span ring as Chrome trace.json (process 0).
        Best-effort: observability must never fail the run."""
        if jax.process_index() != 0:
            return
        path = self.cfg.obs.trace_path or os.path.join(
            self.cfg.checkpoint.dir, "trace.json")
        try:
            self.spans.dump_chrome_trace(path)
        except Exception:
            pass  # incl. unserializable span args — never fail the run

    def _log_train(self, step: int, metrics: dict) -> dict:
        """Build + emit the host-side train record; returns it so the
        fit loop can feed the model-health monitor without a second
        device transfer."""
        with self.spans.span("train.log.sync", step=step):
            # the device fetch: waits until every step dispatched so far
            # has run, so the device idles from here to the next dispatch
            host = {k: float(np.asarray(v)) for k, v in metrics.items()}
        # the schedule counts optimizer updates, not micro-steps
        host["lr"] = float(self.lr_schedule(step // max(self.cfg.optim.accum_steps, 1)))
        if self.cfg.optim.plateau_factor > 0:
            scale = plateau_scale(self.state.opt_state)
            if scale is not None:
                host["lr_plateau_scale"] = float(np.asarray(scale))
                host["lr"] *= host["lr_plateau_scale"]
        host.update(self.meter.percentiles())
        tput = self.meter.throughput(self.items_per_step)
        if tput is not None:
            unit = "images" if self.cfg.loss == "softmax_xent" else "tokens"
            host[f"{unit}_per_sec"] = tput
            host[f"{unit}_per_sec_per_chip"] = tput / jax.device_count()
            mfu = flops_lib.mfu_pct(host[f"{unit}_per_sec_per_chip"],
                                    self._flops_per_item, self._peak_flops)
            if mfu is not None:
                host["mfu_pct"] = round(mfu, 2)
                # perf plane gauge (obs/perf.py): the scrape-visible MFU
                # the capture attribution stamps into its journal record
                perf_lib.record_mfu(host["mfu_pct"])
        host["epoch"] = step // max(self.steps_per_epoch, 1)
        stats = getattr(self.train_loader, "stall_stats", None)
        if stats is not None:
            # Per-log-window input stall fraction: what % of the window the
            # consumer spent blocked on the host pipeline (SURVEY §7.4.1;
            # sustained-drill acceptance is < 5%).
            # Denominator = in-loop stepping time (meter.total_s), NOT
            # wall time between log calls: a window spanning an eval pass
            # or checkpoint wait would otherwise dilute the stall fraction
            # the sustained-drill <5% acceptance gates on.
            loop_s = self.meter.total_s
            prev = getattr(self, "_stall_prev", None)
            if prev is not None and loop_s > prev[1]:
                host["input_stall_pct"] = round(
                    100.0 * max(0.0, stats.wait_s - prev[0])
                    / (loop_s - prev[1]), 3)
                # input-stall regression detector (anomaly plane): one
                # observation per log window
                self.profiler.observe_stall_pct(host["input_stall_pct"],
                                                step)
            self._stall_prev = (stats.wait_s, loop_s)
        if self.cfg.obs.log_memory:
            host.update(device_memory_metrics())
        # Host/device memory telemetry (obs/memory.py): refresh the
        # OOM-headroom gauges at log cadence regardless of log_memory —
        # two /proc reads plus an already-cached jax stats call, and
        # they are the fleet plane's first alert-rule inputs.
        memory_lib.sample_memory_gauges()
        if self._sentinel_on or self.health is not None:
            scale = sentinel_numeric.cooldown_scale(self.state.opt_state)
            if scale is not None and scale != 1.0:
                # post-rewind cooldown: fold into the reported lr like
                # the plateau scale above (effective lr = schedule *
                # plateau * cooldown)
                host["lr_cooldown_scale"] = scale
                host["lr"] *= scale
        host["goodput_pct"] = self.goodput.snapshot()["goodput_pct"]
        if self.cfg.obs.straggler_metrics and jax.process_count() > 1:
            # Cross-host health gather (obs/cluster.py): every host
            # calls this symmetrically (the collective is inside), only
            # the logging below is rank-0. Fixed key schema — absent
            # backends contribute 0.0, never a missing key.
            hbm = device_memory_metrics().get("hbm_gb_in_use", 0.0)
            agg = cluster_lib.summarize({
                "step_time_p50": host.get("step_time_ms_p50", 0.0),
                "input_stall_pct": host.get("input_stall_pct", 0.0),
                "hbm_used": hbm,
            })
            host.update(agg)
            # Straggler blame trigger: every host computes the same
            # aggregate at the same step, so each fires the anomaly
            # locally and the capture windows align by construction.
            blamed = profiler_lib.straggler_blame(
                agg, self.cfg.obs.profile_straggler_ratio)
            if blamed is not None:
                self.profiler.anomaly(
                    "straggler", step, host=blamed,
                    p50_max=round(agg["step_time_p50_max"], 3),
                    p50_med=round(agg["step_time_p50_med"], 3))
        self.logger.log(step, host, prefix="train")
        return host

    def update_bn(self, num_batches: int = 50) -> None:
        """Re-estimate BN running statistics for the CURRENT eval params
        (the SWA/EMA mirror when averaging is on) — torch
        swa_utils.update_bn: averaged weights shift every layer's
        activation distribution, so the stats collected along the
        trajectory are wrong for them. Mechanism: a probe model with
        bn_momentum=0 makes one train-mode apply return exactly ONE
        batch's statistics; the cumulative average over ``num_batches``
        training batches (mean of batch means/vars — torch's
        momentum=None CMA computes the same) replaces state.batch_stats.
        No-op for stat-free models."""
        if not self.state.batch_stats:
            return
        if not any(f.name == "bn_momentum"
                   for f in dataclasses.fields(self.model)):
            return
        probe = dataclasses.replace(self.model, bn_momentum=0.0)
        params = self.state.eval_params

        @jax.jit
        def batch_stats_of(stats, batch):
            _, updated = probe.apply(
                {"params": params, "batch_stats": stats},
                *steps_lib.model_inputs(batch), train=True,
                mutable=["batch_stats"])
            return updated["batch_stats"]

        total = None
        n = 0
        for batch in self.train_epoch_fn(0):
            if self.liveness is not None:
                self.liveness.pulse()  # same non-step liveness as eval
            stats = batch_stats_of(self.state.batch_stats, batch)
            total = stats if total is None else jax.tree.map(
                jnp.add, total, stats)
            n += 1
            if n >= num_batches:
                break
        if n == 0:
            return
        avg = jax.tree.map(lambda t: t / n, total)
        self.state = self.state.replace(batch_stats=avg)
        if self.state.ema_batch_stats is not None:
            # eval reads the EMA stats mirror when one exists: the freshly
            # re-estimated stats (computed under eval_params) must land
            # there too or update_bn would be invisible to EMA eval.
            self.state = self.state.replace(ema_batch_stats=avg)
        self.recorder.record("update_bn", int(self.state.step), batches=n)

    def evaluate(self, step: int, prefix: str = "eval") -> dict:
        sums: dict[str, float] = {}
        n = 0
        stage_pre = perf_lib.get_input_stats().snapshot()
        with self.spans.span("train.eval", step=step):
            for batch in self.eval_epoch_fn(0):
                if self.liveness is not None:
                    # eval runs can dwarf hang_timeout_s; a healthy host
                    # mid-eval must not read as wedged to the monitor
                    self.liveness.pulse()
                m = self.eval_step(self.state, batch)
                for k, v in m.items():
                    sums[k] = sums.get(k, 0.0) + float(np.asarray(v))
                n += 1
        for k, v in perf_lib.get_input_stats().snapshot().items():
            self._eval_stage_s[k] += max(0.0, v - stage_pre.get(k, 0.0))
        if n == 0:
            return {}
        avg = {k: v / n for k, v in sums.items()}
        self.logger.log(step, avg, prefix=prefix)
        if self.best_ckpt is not None:
            if self.best_ckpt.update(
                    avg, self.state, step=step,
                    epoch=step // max(self.steps_per_epoch, 1)):
                self.recorder.record("ckpt_best", step,
                                     value=self.best_ckpt.best_value)
        self.meter.reset_clock()
        return avg

    def _maybe_inject_fault(self, step: int) -> None:
        """Step-boundary fault points (faults/registry.py): hard-kill
        (``step.crash`` — SURVEY §5.3c, no finally-save, no flush;
        exactly what a real host loss looks like to the launcher),
        transient straggle (``step.straggle``), and self-delivered
        preemption (``preempt.sigterm``). ``obs.fault_inject_at_step``
        arrives here too, shimmed to ``step.crash@step=N``."""
        self.faults.set_step(step)
        self.faults.maybe_fire("step.crash", step=step)
        # elastic.shrink: permanent host loss (rc 45, no finally-save).
        # Same mechanics as step.crash; the distinct point + rc lets a
        # shrink drill (docs/elastic.md, tools/chaos_soak.py --shrink)
        # schedule "this host never comes back" declaratively — under a
        # min_nnodes launcher the survivors re-rendezvous DEGRADED and
        # resume resharded.
        self.faults.maybe_fire("elastic.shrink", step=step)
        self.faults.maybe_fire("step.straggle", step=step)
        self.faults.maybe_fire("preempt.sigterm", step=step)
        # host.hang wedges HERE — after the step completed but BEFORE
        # this step's heartbeat/liveness beat, so both the local monitor
        # and the cross-host liveness plane see a step that never
        # finishes (sentinel/liveness.py drives the diagnosis).
        self.faults.maybe_fire("host.hang", step=step)

    def _maybe_inject_stall(self, step: int) -> None:
        """SURVEY §5.3a: wedge (don't crash) this step, first generation
        only — BEFORE the heartbeat beat, so the monitor sees a step that
        never completes (a hung host / wedged link, not a dead process) and
        must drive the dump→abort→gang-restart→resume chain itself."""
        import os

        stall = self.cfg.obs.stall_inject_at_step
        if (stall and step >= stall
                and os.environ.get("RESTART_GENERATION", "0") == "0"):
            print(f"[stall-inject] wedging at step {step}", flush=True)
            while True:  # only the heartbeat abort ends this
                time.sleep(60)

    # ------------------------------------------------------------- sentinel
    def _sentinel_observe(self, step: int, metrics: dict,
                          inflate_loss: bool = False) -> bool:
        """Host half of the numeric guard: classify the completed step as
        healthy / nonfinite / spiking, maintain the consecutive-bad
        streak, and return True when the streak says rewind. Reads the
        loss to host — a device sync per step, the cost the
        ``sentinel.enabled`` knob opts into (documented in config.py)."""
        import math

        loss = float(np.asarray(metrics["loss"]))
        gate_skipped = ("update_skipped" in metrics
                        and float(np.asarray(metrics["update_skipped"])) > 0)
        if inflate_loss:
            # Legacy hook: the step.loss_spike drill now corrupts
            # ``metrics["loss"]`` at the injection site in fit() (so
            # the log/scrape mirror sees the spike too); this flag
            # stays for callers staging their own observation.
            loss = loss * 1e6 if math.isfinite(loss) else loss
        reason = None
        if gate_skipped or not math.isfinite(loss):
            reason = "nonfinite"
            self._sentinel_skipped += 1
        elif self._spike.is_spike(loss):
            reason = "loss_spike"
        else:
            self._spike.add(loss)
            self._bad_streak = 0
        if reason is None:
            return False
        self._bad_streak += 1
        self.registry.counter(
            "sentinel_skipped_steps_total", labels={"reason": reason},
            help="train steps judged bad by the sentinel (nonfinite "
                 "update skipped in-graph, or loss spike flagged)").inc()
        self.registry.gauge(
            "sentinel_bad_streak",
            help="current consecutive bad-step count").set(self._bad_streak)
        print(f"[sentinel] step {step}: {reason} "
              f"(loss={loss:.6g}, streak "
              f"{self._bad_streak}/{self.cfg.sentinel.max_consecutive_bad})",
              flush=True)
        self.recorder.record("sentinel_bad_step", step, reason=reason)
        events_lib.emit("sentinel", "bad_step", step=step, reason=reason,
                        loss=loss, streak=self._bad_streak)
        if reason == "loss_spike":
            # anomaly hook: journal + (opt-in) open a capture window —
            # the profile of the steps AROUND a spike is the evidence
            # the post-mortem never has
            self.profiler.anomaly("loss_spike", step, loss=loss,
                                  streak=self._bad_streak)
        return self._bad_streak >= self.cfg.sentinel.max_consecutive_bad

    def _sentinel_rewind(self, step: int) -> int:
        """Restore the newest integrity-verified checkpoint, apply the
        LR cooldown, and hand the (possibly earlier) step counter back
        to the loop — which re-enters the epoch with the exact
        ``start_batch`` fast-forward. Returns the step to resume from
        (``step`` unchanged when there is nothing to rewind to)."""
        scfg = self.cfg.sentinel
        if self._rewinds >= scfg.max_rewinds:
            # Flag BEFORE raising: fit()'s finally must not force-save
            # the known-diverged live state over the rewind target.
            self._sentinel_aborted = True
            events_lib.emit("sentinel", "abort", step=step,
                            rewinds=self._rewinds)
            raise RuntimeError(
                f"[sentinel] rewind budget exhausted "
                f"({self._rewinds}/{scfg.max_rewinds}): training keeps "
                "diverging after repeated restore+cooldown — aborting "
                "rather than looping restore/diverge forever")
        self._bad_streak = 0
        if self._spike is not None:  # health-armed rewind, sentinel off
            self._spike.reset()
        if self.health is not None:
            # post-rewind: the pre-rewind telemetry regime may contain
            # the very divergence being recovered from
            self.health.reset()
        try:
            # a mid-flight async save must commit before we pick
            self.ckpt.wait()
        except OSError as e:
            # A terminal BACKGROUND persist failure (tiered plane)
            # re-raises at the next wait — here that history must not
            # abort the rewind: letting it unwind would reach fit()'s
            # finally with _sentinel_aborted unset and force-save the
            # known-diverged live state. The failed step's sealed hot
            # snapshot is still a valid rewind source, and the failure
            # was already printed and counted when it happened.
            print(f"[sentinel] ignoring earlier checkpoint persist "
                  f"failure during rewind ({type(e).__name__}: {e})",
                  flush=True)
        good = self.ckpt.latest_good_step()
        restored = (self.ckpt.restore(self.state, step=good)
                    if good is not None else None)
        if restored is None:
            print(f"[sentinel] step {step}: rewind wanted but no verified "
                  "checkpoint exists — resetting the detector and "
                  "continuing in place", flush=True)
            return step
        self.state, _meta = restored
        self.state = self.state.replace(
            opt_state=sentinel_numeric.scale_cooldown(
                self.state.opt_state, scfg.lr_cooldown_factor))
        self._rewinds += 1
        scale = sentinel_numeric.cooldown_scale(self.state.opt_state)
        self.registry.counter(
            "sentinel_rewinds_total",
            help="auto-rewinds to the last verified checkpoint after a "
                 "bad-step streak").inc()
        self.recorder.record("sentinel_rewind", step, to=good,
                             lr_scale=scale)
        events_lib.emit("sentinel", "rewind", step=step, to=int(good),
                        lr_scale=scale, rewind=self._rewinds)
        print(f"[sentinel] rewinding from step {step} to verified step "
              f"{good} (rewind {self._rewinds}/{scfg.max_rewinds}, "
              f"lr cooldown x{scfg.lr_cooldown_factor} -> total scale "
              f"{scale})", flush=True)
        return good

    def import_params(self, path: str) -> None:
        """Warm-start params from a (torch-layout) safetensors file
        (interop.py), keeping the configured sharding."""
        from pytorch_distributed_train_tpu.interop import (
            load_flax_safetensors,
        )

        host_params = load_flax_safetensors(path, self.state.params)
        # Place into the state's ACTUAL layout (state_sharding), not a
        # re-derivation from the rules — they differ under
        # mesh.zero_stage=1, where params are replicated over 'fsdp'.
        sharded = jax.device_put(host_params, self.state_sharding.params)
        self.state = self.state.replace(params=sharded)
        if self.state.ema_params is not None:
            # re-seed the EMA mirror too, else eval would run on the stale
            # random-init mirror until the EMA horizon washes it out
            self.state = self.state.replace(ema_params=sharded)
        if jax.process_index() == 0:
            print(f"[interop] warm-started params from {path}", flush=True)

    @property
    def preempted(self) -> bool:
        """Did a graceful SIGTERM preemption end fit() early? (train.py
        maps this to ``faults.preempt_exit_code``.)"""
        return self._preempted

    def close(self) -> None:
        self.heartbeat.stop()
        self.profiler.finish()
        # shared-memory decode pools (data/workers.py): stop worker
        # processes + release the rings (daemons would die with the
        # process anyway; tests build many Trainers per process)
        for loader in (getattr(self, "train_loader", None),
                       getattr(self, "eval_loader", None)):
            close = getattr(loader, "close", None)
            if close is not None:
                close()
        if self.liveness is not None:
            self.liveness.stop()
        self.ckpt.close()
        if self.best_ckpt is not None:
            self.best_ckpt.close()
        self.logger.close()
        if self.metrics_server is not None:
            from pytorch_distributed_train_tpu.obs import exposition

            # compare-and-clear: a newer Trainer's trigger (several
            # Trainers per test process) must survive this close
            exposition.clear_profile_trigger(self._profile_trigger)
            self.metrics_server.close()
            self.metrics_server = None


def _poison_batch_nan(batch: dict) -> dict:
    """``step.nan`` drill: overwrite every float-dtype batch field with
    NaN — the loss and grads of the next step go non-finite exactly the
    way a corrupted record or overflowed activation would make them, and
    the in-graph guard must absorb it. Elementwise op on the sharded
    arrays: layout preserved, no host round-trip. Integer-only batches
    (token ids with no mask/teacher field) have nothing to poison; the
    drill warns instead of silently passing."""
    out = {}
    poisoned = False
    for k, v in batch.items():
        if jnp.issubdtype(v.dtype, jnp.floating):
            out[k] = v * jnp.asarray(jnp.nan, dtype=v.dtype)
            poisoned = True
        else:
            out[k] = v
    if not poisoned:
        print("[fault-inject] step.nan: no float field in the batch to "
              "poison (integer-only inputs) — step left healthy",
              flush=True)
    return out


def device_memory_metrics() -> dict:
    """HBM usage of local device 0, or {} where the backend reports none
    (CPU). Keys mirror the reference's torch.cuda.memory_allocated /
    max_memory_allocated logging convention."""
    stats = jax.local_devices()[0].memory_stats()
    if not stats:
        return {}
    out = {}
    if "bytes_in_use" in stats:
        out["hbm_gb_in_use"] = stats["bytes_in_use"] / 2**30
    peak = stats.get("peak_bytes_in_use")
    if peak is not None:
        out["hbm_gb_peak"] = peak / 2**30
    return out
