"""The jitted train/eval step — the whole reference hot loop as ONE program.

The reference's step (SURVEY §3.3) is five runtime phases: autocast forward,
DDP-hooked backward with bucketed NCCL all-reduce (reducer.hpp:285),
GradScaler unscale+check, optimizer step, scheduler step. Here that entire
block is a single XLA executable: forward + loss + grad + compiler-placed
collectives + optax update, with overlap done by XLA's latency-hiding
scheduler instead of autograd hooks (SURVEY C7 — "obsolete by construction").

Sharding contract: the TrainState and batch arrive as jax.Arrays already laid
out per the partition rules; `jit(in_shardings=..., donate_argnums=0)` makes
the update in-place in HBM. One PartitionRules table shards params AND
optimizer state AND batch stats — optax state mirrors the param tree
structure, and the '$'-anchored suffix regexes match either path.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Re-exports: the preset + env helper live in config.py (jax-free —
# XLA_FLAGS must be set before any backend-registering import, which
# importing THIS module may already have done).
from pytorch_distributed_train_tpu.config import (  # noqa: F401
    LATENCY_HIDING_XLA_FLAGS,
    ensure_latency_hiding_flags,
)
from pytorch_distributed_train_tpu.models import remat
from pytorch_distributed_train_tpu.models.llama import LoopExits
from pytorch_distributed_train_tpu.ops import lm_head
from pytorch_distributed_train_tpu.train_state import TrainState


def dummy_inputs(loss: str, model_cfg, data_cfg) -> tuple:
    """Tiny dummy inputs for model.init / eval_shape, dispatched the same
    way ``model_inputs`` dispatches real batches (shared by Trainer init
    and distill.py's teacher loading)."""
    if loss == "softmax_xent":
        return (jnp.zeros(
            (2, model_cfg.image_size, model_cfg.image_size, 3),
            jnp.float32),)
    if loss == "mlm_xent":
        ids = jnp.zeros((2, data_cfg.seq_len), jnp.int32)
        return (ids, jnp.ones((2, data_cfg.seq_len), jnp.int32))
    if loss == "seq2seq_xent":
        return (jnp.zeros((2, data_cfg.seq_len), jnp.int32),
                jnp.zeros((2, data_cfg.tgt_seq_len or data_cfg.seq_len),
                          jnp.int32))
    return (jnp.zeros((2, data_cfg.seq_len), jnp.int32),)


def model_inputs(batch: dict) -> tuple:
    """Dispatch batch dict → model positional args (registry-wide convention:
    vision models take images NHWC; BERT takes (input_ids, attention_mask);
    causal LMs take input_ids)."""
    if "image" in batch:
        return (batch["image"],)
    if "decoder_input_ids" in batch:  # seq2seq (t5) — before the bert key
        return (batch["input_ids"], batch["decoder_input_ids"])
    if "attention_mask" in batch:
        return (batch["input_ids"], batch["attention_mask"])
    ids = batch["input_ids"]
    if ids.ndim == 3:
        # preference pairs (B, 2, S) — DPO; the model scores the pair as
        # one flattened (2B, S) forward (losses.make_dpo_loss un-flattens)
        ids = ids.reshape(-1, ids.shape[-1])
    return (ids,)


def apply_model(model, params, batch_stats, batch, *, train: bool, dropout_rng):
    """Returns (logits, new_batch_stats, aux_loss).

    aux_loss is the sum of everything the model ``sow``ed into the 'losses'
    collection (MoE load-balance/z-loss, ops/moe.py) — 0.0 for dense models.
    """
    return apply_model_metrics(model, params, batch_stats, batch,
                               train=train, dropout_rng=dropout_rng)[:3]


def apply_model_metrics(model, params, batch_stats, batch, *, train: bool,
                        dropout_rng):
    """:func:`apply_model` plus, fourth, the scalars a training model sowed
    into its top-level 'step_metrics' collection ({name: value}; models/
    hybrid.py: the expert layers' row counts) — they join the step's
    metrics, and so the log and the registry. One name is the step's own:
    ``update_invalid`` (> 0: the model computed this step on less than its
    input, so the update must not be applied) is folded into the
    skip-select and reported as ``update_skipped``. A model's
    ``router_load`` collection (ops/moe.py: the tokens on every output of a
    router whose selection bias has a rate) rides along under that name:
    arrays, not a metric; the step takes it out and moves the bias by it
    after the optimizer."""
    variables: dict[str, Any] = {"params": params}
    # mutable must be False (not []) when there are no stats — flax returns a
    # (out, vars) tuple for ANY list, including an empty one.
    mutable: Any = False
    if train:
        mutable = ["losses", "step_metrics", "router_load"]
    if batch_stats:
        variables["batch_stats"] = batch_stats
        if train:
            mutable = ["batch_stats"] + mutable
    rngs = {"dropout": dropout_rng} if dropout_rng is not None else None
    kwargs = {}
    if "decoder_input_ids" in batch and "attention_mask" in batch:
        # seq2seq (t5): the encoder padding mask rides as a kwarg (the
        # positional slots are taken by the two id tensors).
        kwargs["attention_mask"] = batch["attention_mask"]
    if getattr(model, "fused_loss", False) and "loss_mask" in batch:
        # Fused-head models reduce CE inside the model (losses.
        # chunked_causal_ce), so the mask must travel in with the inputs.
        kwargs["loss_mask"] = batch["loss_mask"]
    out = model.apply(
        variables, *model_inputs(batch), train=train, rngs=rngs,
        mutable=mutable, **kwargs
    )
    if mutable:
        logits, updated = out
        aux = sum(
            (jnp.sum(leaf) for leaf in jax.tree_util.tree_leaves(
                updated.get("losses", {}))),
            start=jnp.float32(0.0),
        )
        metrics = dict(updated.get("step_metrics", {}))
        if "router_load" in updated:
            metrics["router_load"] = updated["router_load"]
        return logits, updated.get("batch_stats"), aux, metrics
    return out, None, jnp.float32(0.0), {}


def _tree_finite(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    finite = jnp.bool_(True)
    for leaf in leaves:
        finite &= jnp.all(jnp.isfinite(leaf))
    return finite


def make_train_step(model, loss_fn: Callable, tx,
                    ema_decay: float = 0.0, swa_start: int = 0,
                    swa_every: int = 1, mixup=None,
                    device_augment=None,
                    module_grad_norms: bool = False,
                    param_transform: Callable | None = None,
                    teacher_fn: Callable | None = None,
                    numeric_guard: bool = False,
                    grad_accum_steps: int = 1,
                    fused_update=None,
                    reduce_grads: Callable | None = None,
                    reduce_grads_accum: Callable | None = None,
                    reduce_metrics: Callable | None = None,
                    model_health: bool = False) -> Callable:
    """Returns train_step(state, batch, rng) -> (state, metrics). Pure;
    closes over the optax transform (and the static EMA decay / mixup
    transform); jit-wrapped by the caller with explicit shardings.
    ``module_grad_norms`` adds per-top-level-module grad norms to the
    metrics (grad_norm/<module> keys) — the torch-recipe debugging habit
    of watching which block's gradients explode/vanish; computed in-graph,
    so it costs a few reductions, not a host transfer per param.
    ``model_health`` (obs/model_health.py) widens that to the full
    training-dynamics pass (ops/model_health.health_stats): per-module
    grad/param/update norms and update-to-param ratios plus tree-wide
    aggregates, all reduced in-graph. It only ADDS metrics entries — the
    update path is bitwise identical with the flag off.
    ``numeric_guard`` (sentinel/) generalizes the GradScaler skip-step to
    UNSCALED training: a non-finite grad or loss skips the optimizer
    update in-graph (params/opt-state unchanged, step still advances)
    and reports ``update_skipped`` in the metrics — one NaN batch costs
    one skipped step instead of permanently poisoned params. With
    dynamic loss scaling the scaler's own finite gate already does this;
    the guard then only widens the check to include the loss value.

    Compute-graph optimization layer (train.* knobs, docs/performance.md):

    ``grad_accum_steps > 1`` microbatches the step IN-GRAPH: a
    ``lax.scan`` over N equal microbatch slices of the (donated) global
    batch accumulates grads in the carry; loss/metrics are the mean of
    the per-microbatch means and the whole epilogue below — loss-scale
    unscale, finite gate, clip, optimizer — runs ONCE on the
    accumulated grads, so skip/rewind semantics and the LR schedule's
    step count are those of the single-shot step at the same global
    batch (optax.MultiSteps instead runs N host-driven micro-steps and
    gates each one). Dropout/augment keys fold the microbatch index on
    top of the per-step fold, so each microbatch draws independently
    and deterministically under resume.

    ``fused_update`` (ops/fused_update.py via optim.make_fused_update)
    replaces the clip → optax-chain → apply_updates → gate-select
    pipeline with the one-pass fused epilogue; semantics are pinned
    bit-for-bit to the chain by tests. Mutually exclusive with EMA/SWA
    (the fused path does not maintain the mirror).

    ``reduce_grads`` / ``reduce_grads_accum`` / ``reduce_metrics`` are
    the shard_map hooks of the overlapped-collectives path
    (``jit_overlap_train_step``): per-microbatch bucketed grad
    reduction inside the scan (DDP-reducer overlap), whole-tree
    reduction of the accumulated grads (the monolithic baseline arm),
    and cross-shard averaging of loss/metrics/batch-stats. All None
    under plain GSPMD jit, where the partitioner places collectives."""
    if not 0.0 <= ema_decay < 1.0:
        raise ValueError(f"ema_decay must be in [0, 1), got {ema_decay}")
    if swa_start > 0 and ema_decay > 0.0:
        raise ValueError(
            "ema_decay and swa_start_step are mutually exclusive — both "
            "own the single averaged-params mirror")
    if swa_every < 1:
        raise ValueError(f"swa_every must be >= 1, got {swa_every}")
    if grad_accum_steps < 1:
        raise ValueError(
            f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
    if fused_update is not None and (ema_decay > 0.0 or swa_start > 0):
        raise ValueError(
            "train.fused_epilogue does not maintain the EMA/SWA params "
            "mirror — disable optim.ema_decay/swa_start_step or the "
            "fused epilogue")

    model, keeps_logits = _head_loss_plan(model, loss_fn, teacher_fn)
    # what the trace resolved: the trainer's train.compile span carries it
    resolved = {"head_loss": "none", "remat_keeps": "none"}

    def transform_batch(batch, dropout_rng):
        """Per-(micro)batch input transforms, same fold-in discipline
        in every path."""
        if device_augment is not None:
            # Device-side crop/flip/RandAugment/normalize on the raw u8
            # batch (ops/device_augment.py) — same fold-in discipline as
            # dropout (deterministic under resume: same step, same
            # crops), distinct domain tag so augment draws never collide
            # with the mixup stream below.
            batch = device_augment(
                batch, jax.random.fold_in(dropout_rng, 2), train=True)
        if mixup is not None:
            batch = mixup(batch, jax.random.fold_in(dropout_rng, 1))
        if teacher_fn is not None:
            # Distillation (distill.py): the frozen teacher scores the
            # (possibly mixup-transformed) batch in the same executable;
            # the KD loss reads batch['teacher_logits'].
            batch = {**batch, "teacher_logits": teacher_fn(batch)}
        return batch

    def grad_one_batch(params, stats, batch, dropout_rng, scale):
        """grads + aux for ONE (micro)batch — the single-shot math."""

        def loss_for_grad(p):
            # LoRA et al: fold adapter leaves into base kernels in-graph
            # (lora.merge); grads flow only through the transform's
            # non-stop_gradient outputs.
            if param_transform is not None:
                p = param_transform(p)
            # Phase names (``grad_reduce`` and ``optimizer`` below, and
            # ``lm_head`` in ops/lm_head.py): they ride every device op's
            # op_name, the backward pass as ``transpose(jvp(forward))``,
            # so a trace can be split by phase (docs/observability.md).
            with jax.named_scope("forward"):
                logits, new_stats, model_aux, model_metrics = \
                    apply_model_metrics(model, p, stats, batch, train=True,
                                        dropout_rng=dropout_rng)
            if isinstance(logits, lm_head.HeadOperands):
                # the kernels of ops/lm_head_loss.py, or for what they
                # cannot take the logits path from the same operands
                why = lm_head.unsupported(logits)
                resolved["head_loss"] = lm_head.log_resolution(logits, why)
                if why is not None:
                    with jax.named_scope("forward"):
                        logits = lm_head.logits(logits)
            elif isinstance(logits, LoopExits):
                # the looped decoder's exits: its loss takes each exit's
                # head through the same kernels, or the logits path
                exit_head = lm_head.HeadOperands(logits.x[0], logits.table,
                                                 logits.cp)
                resolved["head_loss"] = lm_head.log_resolution(
                    exit_head, lm_head.unsupported(exit_head))
                resolved["loop"] = f"scan x{logits.x.shape[0]}"
            elif (keeps_logits and getattr(logits, "ndim", 0) == 3
                  and "input_ids" in batch):
                resolved["head_loss"] = lm_head.log_resolution(
                    logits, keeps_logits)
            with jax.named_scope("loss"):
                loss, aux = loss_fn(logits, batch)
            aux = {**aux, **model_metrics}
            total = loss + model_aux  # sown losses (MoE aux) join the objective
            scaled = total * scale if scale is not None else total
            return scaled, (loss, aux, model_aux, new_stats)

        remat.kept.clear()
        grads_and_aux = jax.grad(loss_for_grad, has_aux=True)(params)
        if remat.kept:  # by name, as that backward pass was traced
            resolved["remat_keeps"] = "+".join(sorted(remat.kept))
        return grads_and_aux

    def accum_grads(state, batch, dropout_rng, scale):
        """lax.scan over grad_accum_steps microbatches: grads (still
        loss-scaled — the unscale happens once, after accumulation) sum
        in the carry, BN stats thread sequentially (microbatch i sees
        i-1's running stats — sequential-small-batch semantics, the
        same caveat as optax.MultiSteps), per-microbatch metrics stack
        in ys and average after."""
        k = grad_accum_steps

        def split(x):
            if x.shape[0] % k:
                # "step batch": the global batch under GSPMD jit, the
                # per-shard batch inside shard_map (the trainer
                # validates both cases at construction with the right
                # denomination — this is the trace-time backstop).
                raise ValueError(
                    f"train.grad_accum_steps={k} does not divide the "
                    f"step batch {x.shape[0]}")
            return x.reshape((k, x.shape[0] // k) + x.shape[1:])

        micro = jax.tree.map(split, batch)

        def body(carry, xs):
            grad_acc, stats = carry
            mb, idx = xs
            # Per-microbatch key: the step fold already happened; the
            # microbatch index folds on top, so draws are independent
            # across microbatches and deterministic under resume.
            mb_rng = jax.random.fold_in(dropout_rng, idx)
            mb = transform_batch(mb, mb_rng)
            grads, (loss, aux, model_aux, new_stats) = grad_one_batch(
                state.params, stats, mb, mb_rng, scale)
            if reduce_grads is not None:
                # Overlap hook: per-BUCKET collectives issued HERE, so
                # microbatch i's reductions overlap microbatch i+1's
                # compute under the latency-hiding scheduler.
                with jax.named_scope("grad_reduce"):
                    grads = reduce_grads(grads)
            grad_acc = jax.tree.map(jnp.add, grad_acc, grads)
            stats = new_stats if new_stats is not None else stats
            return (grad_acc, stats), (loss, aux, model_aux)

        zeros = jax.tree.map(jnp.zeros_like, state.params)
        (grad_acc, stats), (losses, auxes, model_auxes) = jax.lax.scan(
            body, (zeros, state.batch_stats),
            (micro, jnp.arange(k, dtype=jnp.int32)))
        grads = jax.tree.map(lambda g: g / k, grad_acc)
        loss = jnp.mean(losses)
        load = auxes.pop("router_load", None)
        aux = jax.tree.map(jnp.mean, auxes)
        if load is not None:  # counts, not a mean: the microbatches' add up
            aux["router_load"] = jax.tree.map(lambda c: jnp.sum(c, 0), load)
        model_aux = jnp.mean(model_auxes)
        new_stats = stats if state.batch_stats else None
        return grads, (loss, aux, model_aux, new_stats)

    def apply_update(state, grads, new_stats, loss, after_update):
        with jax.named_scope("optimizer"):
            return state.apply_gradients(tx, grads, new_stats,
                                         ema_decay=ema_decay,
                                         swa_start=swa_start,
                                         swa_every=swa_every, loss=loss,
                                         after_update=after_update)

    def train_step(state: TrainState, batch: dict, rng: jax.Array):
        # Per-step dropout key: fold the step counter into the base key —
        # deterministic under resume (same step → same mask), no key chain
        # to checkpoint (the reference relies on torch's stateful global RNG).
        dropout_rng = jax.random.fold_in(rng, state.step)

        scale = state.dynamic_scale.scale if state.dynamic_scale is not None else None

        if grad_accum_steps > 1:
            grads, (loss, aux, model_aux, new_stats) = accum_grads(
                state, batch, dropout_rng, scale)
        else:
            one = transform_batch(batch, dropout_rng)
            grads, (loss, aux, model_aux, new_stats) = grad_one_batch(
                state.params, state.batch_stats, one, dropout_rng, scale)
            if reduce_grads is not None:
                with jax.named_scope("grad_reduce"):
                    grads = reduce_grads(grads)
        if reduce_grads_accum is not None:
            # Monolithic post-backward reduction (the baseline arm the
            # bucketed overlap is measured against): ONE whole-tree
            # collective on the accumulated grads.
            with jax.named_scope("grad_reduce"):
                grads = reduce_grads_accum(grads)
        # ``router_load`` (a model whose routers' selection bias has a
        # rate): the bias is state that a rule of the model's own moves,
        # from the step's counts, once the optimizer is done: outside the
        # gradient, the clip's norm, the moments and the decay, and inside
        # the skip-select like the rest of the state. The counts are the
        # global batch's: the partitioner's own reduction under GSPMD jit,
        # summed over the shards inside shard_map.
        after_update = after_metrics = None
        if "router_load" in aux:
            aux = dict(aux)
            load = aux.pop("router_load")
            if reduce_metrics is not None:
                load = reduce_metrics.total(load)
            resolved["router_bias_rate"] = model.moe.bias_rate
            after_update = lambda old, new: model.balance_routers(  # noqa: E731
                old, new, load)
            after_metrics = lambda params: model.router_metrics(  # noqa: E731
                load, params)
        if reduce_metrics is not None:
            # shard_map: loss/metrics are per-shard means — average
            # across the batch shards so every replica logs (and the
            # sentinel judges) the same numbers the GSPMD step would.
            loss, aux, model_aux = reduce_metrics((loss, aux, model_aux))
            if new_stats is not None:
                # BN running stats averaged across replicas each step
                # (SyncBN-flavored): keeps the replicated state bitwise
                # in sync, which the replicated-DP contract requires.
                new_stats = reduce_metrics(new_stats)

        # A model's veto (apply_model_metrics): None when it sows none.
        model_ok = None
        if "update_invalid" in aux:
            aux = dict(aux)
            model_ok = aux.pop("update_invalid") <= 0

        if fused_update is not None:
            return _fused_epilogue_step(
                state, grads, loss, aux, model_aux, new_stats,
                fused_update=fused_update, numeric_guard=numeric_guard,
                module_grad_norms=module_grad_norms,
                model_health=model_health, model_ok=model_ok,
                after_update=after_update, after_metrics=after_metrics)

        def select(ok, stepped):
            # both branches are computed in-graph and the select is
            # elementwise — no host round-trip, no recompile; the step
            # counter advances either way
            skipped = state.replace(step=state.step + 1)
            # XLA fuses the select into the update's last write and names
            # the fusion by it: it is the optimizer's
            with jax.named_scope("optimizer"):
                return jax.tree.map(
                    lambda new, old: jnp.where(ok, new, old),
                    stepped, skipped)

        gated = numeric_guard or model_ok is not None
        if state.dynamic_scale is not None:
            # GradScaler semantics (torch:amp/grad_scaler.py:302,375,484):
            # unscale, check finite, skip update on overflow, adjust scale.
            grads = jax.tree.map(lambda g: g / scale, grads)
            grads_ok = _tree_finite(grads)
            finite = grads_ok
            if numeric_guard:
                # sentinel: a finite-grads / non-finite-loss step (rare
                # but real: an inf loss whose grad zeroed out) must not
                # feed the EMA/plateau machinery a poisoned loss either.
                finite &= jnp.isfinite(loss)
            if model_ok is not None:
                finite &= model_ok
            new_state = select(finite,
                               apply_update(state, grads, new_stats, loss,
                                            after_update))
            # The scaler adjusts on GRAD overflow only (GradScaler
            # semantics): a non-finite loss with finite grads skips the
            # update above but must not shrink the loss scale.
            new_state = new_state.replace(
                dynamic_scale=state.dynamic_scale.update(grads_ok)
            )
            metrics_extra = {"loss_scale": scale, "grads_finite": grads_ok}
            if gated:
                metrics_extra["update_skipped"] = 1.0 - finite.astype(
                    jnp.float32)
        elif gated:
            # Unscaled training gets the same skip-step gate (sentinel/
            # numeric guard; a model's ``update_invalid``).
            metrics_extra = {}
            ok = model_ok
            if numeric_guard:
                finite = _tree_finite(grads) & jnp.isfinite(loss)
                metrics_extra["grads_finite"] = finite
                ok = finite if ok is None else finite & ok
            new_state = select(ok,
                               apply_update(state, grads, new_stats, loss,
                                            after_update))
            metrics_extra["update_skipped"] = 1.0 - ok.astype(jnp.float32)
        else:
            new_state = apply_update(state, grads, new_stats, loss,
                                     after_update)
            metrics_extra = {}

        gnorm = optax_global_norm(grads)
        metrics = {"loss": loss, "grad_norm": gnorm, "aux_loss": model_aux,
                   **aux, **metrics_extra}
        if after_metrics is not None:  # of the bias the step leaves behind
            with jax.named_scope("optimizer"):
                metrics.update(after_metrics(new_state.params))
        if model_health:
            # Training-dynamics pass on the ACTUAL applied update (the
            # skip-select is already folded into new_state.params);
            # supersedes the module_grad_norms loop (same grad_norm/<k>
            # keys, plus param/update norms and ratios).
            from pytorch_distributed_train_tpu.ops.model_health import (
                health_stats,
            )

            metrics.update(health_stats(grads, state.params,
                                        new_state.params))
        elif module_grad_norms:
            for key, sub in grads.items():
                metrics[f"grad_norm/{key}"] = optax_global_norm(sub)
        return new_state, metrics

    train_step.resolved = resolved
    return train_step


def _head_loss_plan(model, loss_fn, teacher_fn):
    """(the model the step applies, why its LM head keeps the logits path).
    Read off what the step is built from, with no option to set: when the
    loss is ``causal_lm_xent``, nothing else reads the logits and the model
    offers its head's operands (a ``head_operands`` field: GPT-2 today),
    the step asks for them, and the loss comes from the kernels that
    compute the head's products (ops/lm_head_loss.py) wherever they can
    take the traced shapes. The reason is None when the operands are asked
    for, and for a step this plan has nothing to say about (another loss:
    its model traces what it traced)."""
    from pytorch_distributed_train_tpu.losses import causal_lm_xent

    if teacher_fn is not None:
        return model, "the loss's teacher term reads the logits"
    if loss_fn is not causal_lm_xent:
        return model, None
    if not hasattr(model, "head_operands"):
        return model, (f"{type(model).__name__} does not offer its head's "
                       "operands")
    return model.clone(head_operands=True), None


def _fused_epilogue_step(state: TrainState, grads, loss, aux, model_aux,
                         new_stats, *, fused_update, numeric_guard: bool,
                         module_grad_norms: bool,
                         model_health: bool = False, model_ok=None,
                         after_update=None, after_metrics=None):
    """Shared tail of train_step on the fused path: loss-scale unscale +
    finite gate + clip + optimizer update in ONE pass over the grad tree
    (ops/fused_update.py), instead of the chain's three passes plus the
    whole-TrainState two-branch select. Skip/scale semantics match the
    chain path exactly: the gate selects per-leaf against the old state,
    the step counter advances either way, and the scaler adjusts on GRAD
    overflow only."""
    metrics_extra = {}
    finite = None
    new_dynamic_scale = None
    if state.dynamic_scale is not None:
        scale = state.dynamic_scale.scale
        grads = jax.tree.map(lambda g: g / scale, grads)
        grads_ok = _tree_finite(grads)
        finite = grads_ok
        if numeric_guard:
            finite = finite & jnp.isfinite(loss)
        new_dynamic_scale = state.dynamic_scale.update(grads_ok)
        metrics_extra = {"loss_scale": scale, "grads_finite": grads_ok}
        if numeric_guard:
            metrics_extra["update_skipped"] = 1.0 - finite.astype(
                jnp.float32)
    elif numeric_guard:
        finite = _tree_finite(grads) & jnp.isfinite(loss)
        metrics_extra = {
            "grads_finite": finite,
            "update_skipped": 1.0 - finite.astype(jnp.float32),
        }
    if model_ok is not None:  # a model's veto joins the gate
        finite = model_ok if finite is None else finite & model_ok
        metrics_extra["update_skipped"] = 1.0 - finite.astype(jnp.float32)

    with jax.named_scope("optimizer"):
        new_params, new_opt_state, gnorm = fused_update(
            grads, state.opt_state, state.params, finite=finite)
        if after_update is not None:  # the model's own rule, gated alike
            moved = after_update(state.params, new_params)
            new_params = moved if finite is None else jax.tree.map(
                lambda new, old: jnp.where(finite, new, old),
                moved, new_params)
    stats = state.batch_stats
    if new_stats is not None:
        # The chain path's skip branch keeps the OLD stats (the whole
        # stepped-vs-skipped select); match it per-leaf here.
        if finite is not None:
            stats = jax.tree.map(
                lambda new, old: jnp.where(finite, new, old),
                new_stats, state.batch_stats)
        else:
            stats = new_stats
    new_state = state.replace(
        step=state.step + 1, params=new_params, opt_state=new_opt_state,
        batch_stats=stats)
    if new_dynamic_scale is not None:
        new_state = new_state.replace(dynamic_scale=new_dynamic_scale)
    metrics = {"loss": loss, "grad_norm": gnorm, "aux_loss": model_aux,
               **aux, **metrics_extra}
    if after_metrics is not None:
        metrics.update(after_metrics(new_params))
    if model_health:
        from pytorch_distributed_train_tpu.ops.model_health import (
            health_stats,
        )

        metrics.update(health_stats(grads, state.params, new_params))
    elif module_grad_norms:
        for key, sub in grads.items():
            metrics[f"grad_norm/{key}"] = optax_global_norm(sub)
    return new_state, metrics


def optax_global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def make_eval_step(model, loss_fn: Callable,
                   schedule_free: bool = False,
                   param_transform: Callable | None = None,
                   teacher_fn: Callable | None = None,
                   device_augment=None) -> Callable:
    def eval_step(state: TrainState, batch: dict):
        if device_augment is not None:
            # eval ships raw u8 too; the transform reduces to the
            # deterministic normalize (no draws — rng unused).
            batch = device_augment(batch, None, train=False)
        if teacher_fn is not None:
            # losses that SCORE AGAINST a frozen model (DPO's reference)
            # need its logits at eval time too
            batch = {**batch, "teacher_logits": teacher_fn(batch)}
        params = state.eval_params
        if schedule_free:
            # Schedule-Free trains on the z-sequence; the model that's
            # actually good is the x/y interpolation recovered from the
            # optimizer state (optim.schedule_free_eval locates the
            # ScheduleFreeState inside the chain).
            from pytorch_distributed_train_tpu.optim import (
                schedule_free_eval,
            )

            params = schedule_free_eval(state.opt_state, params)
        if param_transform is not None:
            params = param_transform(params)
        logits, _, _ = apply_model(
            # eval_batch_stats: the EMA stats mirror when EMA is on —
            # averaged weights + trajectory stats mis-normalize BN models
            model, params, state.eval_batch_stats, batch,
            train=False, dropout_rng=None,
        )
        loss, aux = loss_fn(logits, batch)
        return {"loss": loss, **aux}

    return eval_step


# ---------------------------------------------------------------- sharding

def offload_state_shardings(state_sharding) -> Any:
    """ZeRO-Offload analogue (DeepSpeed concept; torch FSDP
    CPUOffload(offload_params=) is the in-reference-library cousin): return
    a copy of the TrainState sharding pytree whose OPTIMIZER-STATE subtree
    lives in ``pinned_host`` memory. Between steps the adam/lamb moments sit
    in host DRAM instead of HBM; the train step stages them in and out with
    in-graph ``jax.device_put`` and XLA overlaps the transfers with compute.
    Partition specs are preserved — each host holds exactly the shards its
    devices would have held.

    TPU-only at runtime: the CPU backend has no implementation for the
    placement custom-call (tests cover the metadata transform; a TPU
    executes it)."""
    to_host = lambda s: NamedSharding(  # noqa: E731
        s.mesh, s.spec, memory_kind="pinned_host")
    return state_sharding.replace(
        opt_state=jax.tree.map(to_host, state_sharding.opt_state))


def offload_opt_state(train_step, opt_dev_sharding, opt_host_sharding):
    """Wrap a train step for offloaded optimizer state: stage the moments
    HBM-ward before the update and back to pinned host after. Both sharding
    pytrees are closure constants, so the transfers compile into the one
    step executable (no per-step host round-trip in Python)."""

    def wrapped(state: TrainState, batch: dict, rng: jax.Array):
        state = state.replace(
            opt_state=jax.device_put(state.opt_state, opt_dev_sharding))
        new_state, metrics = train_step(state, batch, rng)
        new_state = new_state.replace(
            opt_state=jax.device_put(new_state.opt_state, opt_host_sharding))
        return new_state, metrics

    return wrapped


def _drop_axis(spec: PartitionSpec, axis: str) -> PartitionSpec:
    """Remove one mesh axis from a PartitionSpec (entries may be tuples)."""
    out = []
    for e in spec:
        if e == axis:
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a != axis)
            out.append(kept if kept else None)
        else:
            out.append(e)
    return PartitionSpec(*out)


def state_shardings(mesh: Mesh, rules, state_shape,
                    zero_stage: int = 3) -> Any:
    """Sharding pytree for a TrainState *shape* tree (from jax.eval_shape).

    One rules table covers params, optimizer mirrors (mu/nu/trace/MultiSteps
    accumulators — same name suffixes), and batch stats (fall through to the
    catch-all → replicated). Divisibility-validated against the mesh.

    ``zero_stage`` selects the torch-FSDP ShardingStrategy analogue on the
    'fsdp' mesh axis (SURVEY C13 `ShardingStrategy{FULL_SHARD,NO_SHARD}`):

    - 3 (default, FULL_SHARD/ZeRO-3): params AND optimizer mirrors sharded
      per the rules — XLA all-gathers weights at use.
    - 1 (ZeRO-1, torch's optimizer-state sharding): params (and the EMA
      mirror) REPLICATED over 'fsdp' — it behaves as a second data axis
      for compute — while optimizer moments keep the sharded layout; the
      partitioner derives the reduce-scatter(grads) -> sharded update ->
      all-gather(params) dance that ZeRO-1 implements by hand. Weight
      memory is not reduced, optimizer memory (2x params for adam) is.

    NO_SHARD is simply fsdp=1; there is no runtime to choose, only layout.
    """
    if zero_stage not in (1, 3):
        raise ValueError(f"zero_stage must be 1 or 3, got {zero_stage}")
    sh = rules.tree_shardings(mesh, state_shape)
    if zero_stage == 1:
        def replicate_fsdp(s):
            return NamedSharding(mesh, _drop_axis(s.spec, "fsdp"))

        sh = sh.replace(params=jax.tree.map(replicate_fsdp, sh.params))
        if sh.ema_params is not None:
            sh = sh.replace(
                ema_params=jax.tree.map(replicate_fsdp, sh.ema_params))
    return sh


def jit_train_step(train_step, mesh: Mesh, state_sharding, batch_axes=("data", "fsdp")):
    batch_sh = NamedSharding(mesh, PartitionSpec(tuple(batch_axes)))
    rep = NamedSharding(mesh, PartitionSpec())
    return jax.jit(
        train_step,
        in_shardings=(state_sharding, batch_sh, rep),
        out_shardings=(state_sharding, rep),
        donate_argnums=(0,),
    )


def jit_eval_step(eval_step, mesh: Mesh, state_sharding, batch_axes=("data", "fsdp")):
    batch_sh = NamedSharding(mesh, PartitionSpec(tuple(batch_axes)))
    rep = NamedSharding(mesh, PartitionSpec())
    return jax.jit(
        eval_step,
        in_shardings=(state_sharding, batch_sh),
        out_shardings=rep,
    )


# ------------------------------------------- overlapped grad collectives
#
# The DDP-reducer analogue (SURVEY [TORCH] reducer.hpp:285): under
# shard_map data parallelism the gradient reduction moves out of the
# monolithic post-backward psum into per-BUCKET pmeans issued inside the
# accumulation scan — bucketed by REVERSE parameter order (the order
# backward produces grads), sized by train.grad_bucket_mb — so the
# collectives for microbatch i overlap microbatch i+1's remaining
# compute once XLA's latency-hiding scheduler is on.

# (LATENCY_HIDING_XLA_FLAGS — the scheduler preset the overlap path
# wants in XLA_FLAGS before backend init — is re-exported from
# config.py via the module imports above: the torch-world analogue is
# NCCL's stream overlap, which DDP gets for free from autograd hooks;
# XLA needs the scheduler told to hide collective latency behind
# compute. bench.py applies it pre-import; trainer runs export it in
# the launcher environment — docs/performance.md.)




def overlap_grad_reducer(params_tree, bucket_mb: int, axis_names):
    """Per-microbatch bucketed reducer (the ``reduce_grads`` hook):
    returns (reduce_fn, buckets). Buckets come from
    parallel.partition.grad_buckets over the params SHAPE tree —
    reverse parameter order, ~bucket_mb each, mirroring DDP's
    ``bucket_cap_mb``; each bucket reduces as ONE tupled pmean, i.e.
    one collective the scheduler can hide behind the next microbatch's
    compute."""
    from pytorch_distributed_train_tpu.parallel.partition import (
        grad_buckets,
    )

    buckets = grad_buckets(params_tree, bucket_mb * 2**20)
    axes = tuple(axis_names)

    def reduce_fn(grads):
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        out = list(leaves)
        for bucket in buckets:
            reduced = jax.lax.pmean(
                tuple(leaves[i] for i in bucket), axes)
            for j, i in enumerate(bucket):
                out[i] = reduced[j]
        return jax.tree_util.tree_unflatten(treedef, out)

    return reduce_fn, buckets


def monolithic_grad_reducer(axis_names):
    """The baseline arm: ONE whole-tree pmean on the ACCUMULATED grads
    (the ``reduce_grads_accum`` hook) — what a hand-written post-
    backward all-reduce does, and what the bucketed in-scan reduction
    is A/B'd against (tools/aot_ab.py ``overlap`` arm)."""
    axes = tuple(axis_names)

    def reduce_fn(grads):
        return jax.lax.pmean(grads, axes)

    return reduce_fn


def metrics_reducer(axis_names):
    """Cross-shard mean for per-shard loss/metrics/batch-stats (the
    ``reduce_metrics`` hook)."""
    axes = tuple(axis_names)

    def reduce_fn(tree):
        return jax.lax.pmean(tree, axes)

    # for what is a count and not a mean (a router's load)
    reduce_fn.total = lambda tree: jax.lax.psum(tree, axes)
    return reduce_fn


def sharded_leaves(state_sharding) -> list[str]:
    """Names of the leaves of a sharding tree that are not fully
    replicated: empty for the DDP layout (the whole TrainState on every
    device, the batch axes data axes only)."""
    from pytorch_distributed_train_tpu.parallel.partition import path_name

    flat, _ = jax.tree_util.tree_flatten_with_path(state_sharding)
    return [path_name(path) for path, sh in flat
            if hasattr(sh, "is_fully_replicated")
            and not sh.is_fully_replicated]


def assert_replicated_for_overlap(state_sharding) -> None:
    """The overlap path is the DDP analogue: pure data parallelism with
    the whole TrainState REPLICATED (the batch axes act as data axes
    only). A sharded param/opt leaf would silently compute garbage
    inside the full-manual shard_map body — refuse loudly instead."""
    bad = sharded_leaves(state_sharding)
    if bad:
        raise ValueError(
            "train.overlap_collectives needs the whole TrainState "
            "replicated (pure data parallelism — set mesh.fsdp=1 or a "
            f"replicating rule set); sharded leaves: {bad[:5]}"
            f"{'...' if len(bad) > 5 else ''}")


class GradReducePlan(NamedTuple):
    """How often the GSPMD step reduces a leaf's gradient across the
    batch axes, and why."""

    mode: str  # per_leaf | per_use
    batch_devices: int
    why: str


def grad_reduce_plan(mesh, state_sharding,
                     batch_axes=("data", "fsdp")) -> GradReducePlan:
    """Read off the layout, with no option to set. Left to itself the
    partitioner reduces every USE of a leaf where its dot or scatter
    produces the contribution (``per_use``): a leaf used twice, the tied
    embedding, is all-reduced twice (GPT-2 small: 154 MB in float32 each,
    2.7 ms a step each on four v5e chips; PERF.md section 5). In pure
    data parallelism (every device of the mesh on a batch axis, more
    than one of them, and every leaf of the state replicated: the walk
    ``assert_replicated_for_overlap`` makes) a model that has such a leaf
    can be told how many ways the batch is split (``per_leaf``) and keep
    the contributions as per-shard partial sums until they have met, so
    that the leaf is reduced once."""
    n = math.prod(mesh.shape.get(ax, 1) for ax in batch_axes)
    if n == 1:
        why = "one device on the batch axes: nothing to reduce"
    elif mesh.size != n:
        why = f"{mesh.size // n} devices a replica: not pure data parallelism"
    elif (bad := sharded_leaves(state_sharding)):
        why = f"{len(bad)} sharded leaves of the state, first {bad[0]}"
    else:
        return GradReducePlan("per_leaf", n, "pure data parallelism, "
                              "the state replicated")
    return GradReducePlan("per_use", n, why)


def shard_rng_fold(rng: jax.Array, axis_names) -> jax.Array:
    """Per-shard PRNG key inside a shard_map body: fold the linearized
    shard index over ``axis_names`` into the (replicated) key. Without
    this every data-parallel replica would draw IDENTICAL dropout/
    augment/mixup randomness for its local batch — the DDP contract is
    per-rank independent draws (torch ranks each own a global-RNG
    stream). Axis sizes come from ``psum(1, ax)`` so no mesh handle is
    needed in-graph."""
    idx = jnp.int32(0)
    for ax in axis_names:
        idx = idx * jax.lax.psum(jnp.int32(1), ax) + jax.lax.axis_index(ax)
    return jax.random.fold_in(rng, idx)


def jit_overlap_train_step(train_step, mesh: Mesh, state_sharding,
                           batch_axes=("data", "fsdp")):
    """shard_map + jit wrap of a train step built with the reduce_*
    hooks: state replicated, batch sharded over ``batch_axes``, grads
    reduced explicitly inside the step body (per-bucket or monolithic —
    whichever hooks the step closed over). Buffer donation is
    preserved: the jit level aliases the replicated state exactly as
    ``jit_train_step`` does. The replicated rng is re-keyed per shard
    (``shard_rng_fold``) so dropout/augment draws are independent
    across replicas — a different stream than the GSPMD step's global-
    batch draws (both are valid samplings; parity tests compare
    deterministic configs)."""
    assert_replicated_for_overlap(state_sharding)
    from pytorch_distributed_train_tpu.utils.compat import shard_map

    axes = tuple(batch_axes)

    def sharded_step(state, batch, rng):
        return train_step(state, batch, shard_rng_fold(rng, axes))

    batch_spec = PartitionSpec(axes)
    smapped = shard_map(
        sharded_step, mesh=mesh,
        in_specs=(PartitionSpec(), batch_spec, PartitionSpec()),
        out_specs=(PartitionSpec(), PartitionSpec()),
        # Full-manual + no replication check: the body's pmeans make the
        # outputs replicated by construction; legacy jax's check_rep
        # cannot see through the scan-carried bucket reductions.
        check_vma=False)
    batch_sh = NamedSharding(mesh, batch_spec)
    rep = NamedSharding(mesh, PartitionSpec())
    return jax.jit(
        smapped,
        in_shardings=(state_sharding, batch_sh, rep),
        out_shardings=(state_sharding, rep),
        donate_argnums=(0,),
    )
