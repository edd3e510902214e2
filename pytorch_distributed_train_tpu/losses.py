"""Loss functions for the acceptance-matrix workloads.

All losses return (scalar_loss, aux_metrics_dict) with the loss in fp32.
Static-shape discipline throughout: MLM and causal-LM losses weight ALL
positions instead of gathering a dynamic number of masked/valid tokens
(dynamic shapes would force recompilation — SURVEY §7.4.5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from pytorch_distributed_train_tpu.ops.lm_head import (
    HeadOperands,
    head_token_xent,
)


def softmax_xent(logits, batch, *_, label_smoothing: float = 0.0):
    """Classification loss. batch: {'image':…, 'label': (B,) int}.

    ``label_smoothing`` follows torch's CrossEntropyLoss(label_smoothing=)
    semantics (uniform mass over classes). Metrics: top-1 always; top-5
    when the class count allows (the ImageNet recipe's second number).
    """
    labels = batch["label"]
    n_cls = logits.shape[-1]
    if "target_probs" in batch:
        # Soft targets from MixUp/CutMix (ops/mixup.py) — smoothing is
        # already folded into the target rows there; accuracy below stays
        # against the original hard labels.
        loss = optax.softmax_cross_entropy(
            logits, batch["target_probs"]).mean()
    elif label_smoothing > 0.0:
        targets = optax.smooth_labels(
            jax.nn.one_hot(labels, n_cls), label_smoothing)
        loss = optax.softmax_cross_entropy(logits, targets).mean()
    else:
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
    acc = (jnp.argmax(logits, axis=-1) == labels).mean()
    metrics = {"accuracy": acc}
    if n_cls > 5:
        top5 = jax.lax.top_k(logits, 5)[1]  # (B, 5) indices
        metrics["top5_accuracy"] = (top5 == labels[:, None]).any(-1).mean()
    return loss, metrics


def mlm_xent(logits, batch, *_):
    """Masked-LM loss. batch: {'input_ids', 'labels', 'label_weights', ...}.

    `labels` holds original token ids at masked positions (anything
    elsewhere); `label_weights` is 1.0 at the positions that count
    (the reference-era BERT convention — ~15% of tokens, BASELINE.json:10).
    """
    labels = batch["labels"]
    weights = batch["label_weights"].astype(jnp.float32)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    denom = jnp.maximum(weights.sum(), 1.0)
    loss = (per_tok * weights).sum() / denom
    acc = ((jnp.argmax(logits, -1) == labels) * weights).sum() / denom
    return loss, {"mlm_accuracy": acc}


def causal_lm_xent(logits, batch, *_):
    """Next-token loss. batch: {'input_ids': (B,S)}; optional 'loss_mask'.

    Shifts inside the loss (logits[:, :-1] vs ids[:, 1:]) so the data
    pipeline ships one tensor, as the reference's LM collate does.

    ``logits`` may be the head's operands instead (ops/lm_head.py
    ``HeadOperands``, which a training step asks of a model that offers
    them): the per-token loss then comes from the kernels that compute the
    head's product, over ALL B*S rows so that they tile evenly, the shift
    a weight of 0 on each sequence's last position.
    """
    ids = batch["input_ids"]
    fused = isinstance(logits, HeadOperands)
    if not fused:
        logits = logits[:, :-1]
    targets = ids[:, 1:]
    weights = batch.get("loss_mask", jnp.ones_like(ids, jnp.float32))[:, 1:]
    weights = weights.astype(jnp.float32)
    if fused:
        pad = ((0, 0), (0, 1))
        per_tok = head_token_xent(logits, jnp.pad(targets, pad))
        weights = jnp.pad(weights, pad)
    else:
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets)
    denom = jnp.maximum(weights.sum(), 1.0)
    loss = (per_tok * weights).sum() / denom
    return loss, {"perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


def exit_distribution(gates):
    """gates (T, ...) -> (p, log p), float32, over the T exits: ``lam_t =
    sigmoid(g_t)``; ``S_0 = 1``, ``S_t = S_{t-1} (1 - lam_t)``; ``p_t =
    lam_t S_{t-1}`` for t < T and ``p_T = S_{T-1}``: the last exit takes
    what is left (``g_T`` is unused), so ``sum_t p_t = 1``. Taken in logs:
    ``log S_t`` is a running sum of ``log(1 - lam) = log_sigmoid(-g)``."""
    g = gates.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)        # log S_1..S_T
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
    logp = jnp.concatenate(
        [jax.nn.log_sigmoid(g[:-1]) + before[:-1], before[-1:]])
    return jnp.exp(logp), logp


def looped_lm_xent(exits, batch, *_):
    """The looped decoder's next-token loss (models/llama.py ``LoopExits``;
    the stage-I objective of arXiv:2510.25741 under a uniform prior over
    exits): ``ce_{t,i}`` is exit t's cross-entropy at position i against
    token i + 1, ``p_{.,i}`` the exit distribution the model's gates give
    (:func:`exit_distribution`), and

        loss = mean_i [ sum_t p_{t,i} ce_{t,i} - beta H(p_{.,i}) ],
        H = -sum_t p_t log p_t.

    No stop-gradient: the gate learns from ``ce`` and from ``H``, every
    other leaf from the T cross-entropies through its T uses. The shift,
    ``loss_mask`` and the mean are ``causal_lm_xent``'s; each exit's
    per-token loss comes from the head's kernels where they take the
    shapes (ops/lm_head.py), its weight ``p_t`` the per-row cotangent of
    their backward pass, and from the logits path elsewhere."""
    from pytorch_distributed_train_tpu.models.llama import LoopExits
    from pytorch_distributed_train_tpu.ops import lm_head

    if not isinstance(exits, LoopExits):
        raise TypeError(
            "looped_lm_xent reads a looped decoder's exits "
            f"(model.loop_steps > 1), not {type(exits).__name__}")
    ids = batch["input_ids"]
    pad = ((0, 0), (0, 1))  # all B*S rows: the shift is a weight of 0
    targets = jnp.pad(ids[:, 1:], pad)
    weights = jnp.pad(batch.get(
        "loss_mask", jnp.ones_like(ids, jnp.float32))[:, 1:].astype(
            jnp.float32), pad)
    denom = jnp.maximum(weights.sum(), 1.0)
    mean = lambda per_tok: (per_tok * weights).sum(  # noqa: E731
        axis=(-2, -1)) / denom

    def exit_xent(x):
        head = HeadOperands(x, exits.table, exits.cp)
        with jax.named_scope("exit_head"):
            if lm_head.unsupported(head) is None:
                return head_token_xent(head, targets)
            return optax.softmax_cross_entropy_with_integer_labels(
                lm_head.logits(head), targets)

    # all T exits' float32 logits are kept for the backward pass (3.0 GiB
    # at the drawn cell's size): one exit's at a time, by checkpointing
    # this call, costs a fourth product an exit, 23.6 ms of a 516.6 ms step
    # (PERF.md section 6, PR 35), and the step fits without it
    ce = jnp.stack([exit_xent(x) for x in exits.x])  # (T, B, S)
    p, logp = exit_distribution(exits.gates)
    entropy = -jnp.sum(jnp.where(p > 0, p * logp, 0.0), axis=0)
    loss = mean(jnp.sum(p * ce, axis=0) - exits.beta * entropy)
    share, exit_ce = mean(p), mean(ce)
    aux = {"exit_entropy": mean(entropy)}
    for t in range(ce.shape[0]):
        aux[f"exit_share_t{t + 1}"] = share[t]
        aux[f"exit_ce_t{t + 1}"] = exit_ce[t]
    return loss, aux


def seq2seq_xent(logits, batch, *_):
    """Encoder-decoder LM loss (t5). batch: {'input_ids' (B,Se),
    'decoder_input_ids' (B,Sd), 'labels' (B,Sd)}; optional
    'label_weights' masks target padding. No shift here — the data
    pipeline builds decoder_input_ids as the shifted-right labels (the
    T5 convention), so logits[t] already predicts labels[t]."""
    labels = batch["labels"]
    weights = batch.get("label_weights",
                        jnp.ones_like(labels)).astype(jnp.float32)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    denom = jnp.maximum(weights.sum(), 1.0)
    loss = (per_tok * weights).sum() / denom
    acc = ((jnp.argmax(logits, -1) == labels) * weights).sum() / denom
    return loss, {"perplexity": jnp.exp(jnp.minimum(loss, 20.0)),
                  "token_accuracy": acc}


def fused_causal_lm_xent(out, batch, *_):
    """Loss for models running the fused chunked head (ModelConfig.
    fused_lm_loss): the model already reduced CE inside its head region
    (chunked_causal_ce below) and returns {'loss_sum', 'weight_sum'}
    instead of (B, S, V) logits — which at 32k vocab never materialize.
    """
    loss = out["loss_sum"] / jnp.maximum(out["weight_sum"], 1.0)
    return loss, {"perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


def chunked_causal_ce(x, kernel, input_ids, loss_mask=None,
                      chunk: int = 256, transpose_kernel: bool = False) -> dict:
    """Fused LM-head + cross-entropy over sequence chunks.

    The torch-era pattern materializes logits (B, S, V) and hands them to
    the loss; at Llama vocab (32k) and seq 2048 that is ~2 GB of fp32 HLO
    temps live through the backward.
    Computing ``head_matmul → CE → scalar`` per sequence chunk under
    `jax.checkpoint` keeps one (B, chunk, V) tile live at a time and saves
    only two scalars per chunk; backward recomputes tiles (the same
    FLOPs-for-HBM trade as chunked attention / flash kernels).

    x: (B, S, E) final hidden states (compute dtype); kernel: (E, V) — or
    (V, E) with ``transpose_kernel`` (tied-embedding heads pass the raw
    embedding table so no transposed copy materializes in HBM);
    input_ids: (B, S) — targets are the shift-by-one, as causal_lm_xent.
    Returns {'loss_sum', 'weight_sum'} fp32 scalars.
    """
    xs = x[:, :-1]
    targets = input_ids[:, 1:]
    weights = (loss_mask[:, 1:] if loss_mask is not None
               else jnp.ones_like(targets)).astype(jnp.float32)
    contract = ((x.ndim - 1,), (1,) if transpose_kernel else (0,))

    B, S, E = xs.shape
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    if pad:  # padded positions carry weight 0 → contribute nothing
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        weights = jnp.pad(weights, ((0, 0), (0, pad)))
    tiles = (
        xs.reshape(B, n_chunks, chunk, E).transpose(1, 0, 2, 3),
        targets.reshape(B, n_chunks, chunk).transpose(1, 0, 2),
        weights.reshape(B, n_chunks, chunk).transpose(1, 0, 2),
    )

    def body(carry, tile):
        xt, tt, wt = tile
        logits = jax.lax.dot_general(
            xt, kernel, (contract, ((), ())),
            preferred_element_type=jnp.float32,
        )
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, tt)
        return (carry[0] + (ce * wt).sum(), carry[1] + wt.sum()), None

    # lax.scan (not a Python unroll): forces chunk-sequential scheduling so
    # peak memory really is ONE tile — unrolled chunks let XLA overlap
    # several chunk backwards and the saving evaporates. checkpoint makes
    # the backward recompute each tile's logits from its saved inputs.
    (loss_sum, weight_sum), _ = jax.lax.scan(
        jax.checkpoint(body), (jnp.float32(0.0), jnp.float32(0.0)), tiles)
    return {"loss_sum": loss_sum, "weight_sum": weight_sum}


def _kd_term(student_logits, teacher_logits, weights, temperature: float):
    """Hinton-style distillation term: T^2 * KL(softmax(t/T) ||
    softmax(s/T)), position-weighted mean. The T^2 factor keeps the KD
    gradient magnitude comparable to the hard loss as T varies (Hinton et
    al. 2015 §2); teacher logits enter under stop_gradient so the graph
    never differentiates through the teacher forward."""
    t = jax.lax.stop_gradient(teacher_logits.astype(jnp.float32))
    s = student_logits.astype(jnp.float32)
    log_p_t = jax.nn.log_softmax(t / temperature, axis=-1)
    log_q_s = jax.nn.log_softmax(s / temperature, axis=-1)
    kl = jnp.sum(jnp.exp(log_p_t) * (log_p_t - log_q_s), axis=-1)
    if weights is None:
        kd = kl.mean()
    else:
        w = weights.astype(jnp.float32)
        kd = (kl * w).sum() / jnp.maximum(w.sum(), 1.0)
    return kd * temperature**2


def make_distill_loss(base_fn, base_name: str, alpha: float,
                      temperature: float):
    """Wrap a base loss with knowledge distillation (distill.py):

        total = alpha * hard_loss + (1 - alpha) * kd_term

    The batch must carry ``teacher_logits`` (same shape as the student's
    logits — steps.make_train_step's ``teacher_fn`` hook adds them). The
    KD positions/weights mirror each base loss's own: all positions for
    classification, ``label_weights`` for MLM, the shifted ``loss_mask``
    for causal LM."""
    if base_name not in ("softmax_xent", "mlm_xent", "causal_lm_xent"):
        raise ValueError(
            f"distillation needs per-position logits; loss {base_name!r} "
            "is unsupported (fused_causal_lm_xent never materializes them)")
    if not 0.0 <= alpha <= 1.0:
        raise ValueError(f"distill.alpha must be in [0, 1], got {alpha}")
    if temperature <= 0.0:
        raise ValueError(
            f"distill.temperature must be > 0, got {temperature}")

    def fn(logits, batch, *args):
        hard, metrics = base_fn(logits, batch, *args)
        t_logits = batch["teacher_logits"]
        if base_name == "softmax_xent":
            s, t, w = logits, t_logits, None
        elif base_name == "mlm_xent":
            s, t, w = logits, t_logits, batch["label_weights"]
        else:  # causal_lm_xent — same shift as the base loss
            s, t = logits[:, :-1], t_logits[:, :-1]
            ids = batch["input_ids"]
            w = batch.get("loss_mask",
                          jnp.ones_like(ids, jnp.float32))[:, 1:]
        kd = _kd_term(s, t, w, temperature)
        total = alpha * hard + (1.0 - alpha) * kd
        return total, {**metrics, "hard_loss": hard, "kd_loss": kd}

    return fn


def make_dpo_loss(beta: float):
    """Direct Preference Optimization (Rafailov et al. 2023) —
    preference fine-tuning without a reward model:

        L = -log sigmoid(beta * [(pi_c - ref_c) - (pi_r - ref_r)])

    where pi/ref are the policy's / frozen reference's summed
    continuation log-probs of the chosen (c) and rejected (r) responses.

    Batch layout (data.datasets.synthetic_dpo / a preference corpus):
    ``input_ids`` (B, 2, S) — dim 1 is [chosen, rejected] —
    ``loss_mask`` (B, 2, S) marking response tokens (prompt masked out).
    The model sees the pair flattened to (2B, S) (steps.model_inputs);
    the frozen reference's logits arrive as ``teacher_logits`` through
    the same teacher hook distillation uses (distill.load_teacher — the
    reference model IS a teacher with a different loss).
    """
    if beta <= 0.0:
        raise ValueError(f"dpo beta must be > 0, got {beta}")

    def seq_logps(logits, ids, mask):
        # next-token logprob of each sequence's masked continuation
        lp = jax.nn.log_softmax(logits[:, :, :-1].astype(jnp.float32), -1)
        tok = jnp.take_along_axis(lp, ids[:, :, 1:, None], axis=-1)[..., 0]
        return (tok * mask[:, :, 1:].astype(jnp.float32)).sum(-1)  # (B, 2)

    def fn(logits, batch, *_):
        ids = batch["input_ids"]            # (B, 2, S)
        B, two, S = ids.shape
        mask = batch.get("loss_mask", jnp.ones_like(ids))
        pi = seq_logps(logits.reshape(B, 2, S, -1), ids, mask)
        ref = seq_logps(
            jax.lax.stop_gradient(
                batch["teacher_logits"]).reshape(B, 2, S, -1), ids, mask)
        margin = beta * ((pi[:, 0] - ref[:, 0]) - (pi[:, 1] - ref[:, 1]))
        loss = -jax.nn.log_sigmoid(margin).mean()
        return loss, {
            "dpo_accuracy": (margin > 0).mean(),
            "reward_margin": margin.mean() / beta,
            "chosen_reward": (pi[:, 0] - ref[:, 0]).mean(),
            "rejected_reward": (pi[:, 1] - ref[:, 1]).mean(),
        }

    return fn


def make_grpo_loss(clip_eps: float = 0.2):
    """Group-relative policy loss over harvested rollouts (online/ —
    the GRPO surrogate of Shao et al. 2024, value-model-free).

    Batch layout (online/rollouts.to_grpo_batch): ``input_ids`` (B, S)
    prompt+completion, ``loss_mask`` (B, S) — 1.0 exactly on the
    SAMPLED completion tokens — and ``advantage`` (B,), the per-prompt-
    group normalized reward ((r - mean) / std over the group: "better
    than the other samples of this prompt" is the whole baseline).

    Per-token surrogate: -advantage * logpi(sampled token), masked and
    token-mean'd. When the batch also carries ``behavior_logprobs``
    (B, S) — the generating policy's per-token logprobs, aligned to the
    same positions — the PPO-style clipped-ratio objective bounds the
    update against off-policy drift (rollouts from version V training
    version V+k); without them the ratio is 1 and this reduces to
    REINFORCE with the group baseline.
    """
    if clip_eps < 0.0:
        raise ValueError(f"grpo clip_eps must be >= 0, got {clip_eps}")

    def fn(logits, batch, *_):
        ids = batch["input_ids"]  # (B, S)
        mask = batch["loss_mask"][:, 1:].astype(jnp.float32)
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        logp = jnp.take_along_axis(
            lp, ids[:, 1:, None], axis=-1)[..., 0]  # (B, S-1)
        adv = jax.lax.stop_gradient(
            batch["advantage"].astype(jnp.float32))[:, None]
        if "behavior_logprobs" in batch:
            behavior = jax.lax.stop_gradient(
                batch["behavior_logprobs"][:, 1:].astype(jnp.float32))
            ratio = jnp.exp(logp - behavior)
            surr = jnp.minimum(
                ratio * adv,
                jnp.clip(ratio, 1.0 - clip_eps, 1.0 + clip_eps) * adv)
            per_tok = -surr
        else:
            per_tok = -adv * logp
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (per_tok * mask).sum() / denom
        aux = {
            "sampled_tokens": mask.sum(),
            "mean_advantage": batch["advantage"].mean(),
            "mean_sample_logp": (logp * mask).sum() / denom,
            # Model-health analytics (obs/model_health.py), free from
            # tensors already in hand. Token entropy of the policy over
            # sampled positions: entropy collapse (policy going
            # deterministic) is the classic RL failure precursor.
            "token_entropy":
                (-(jnp.exp(lp) * lp).sum(-1) * mask).sum() / denom,
        }
        if "behavior_logprobs" in batch:
            # Sampled-token KL estimate to the BEHAVIOR policy
            # (E_behavior[log behavior - log pi] over the sampled
            # tokens): the off-policy drift the clipped ratio bounds —
            # runaway here means rollouts no longer resemble the policy
            # being trained (the kl_runaway alert input).
            aux["kl_behavior"] = ((behavior - logp) * mask).sum() / denom
        return loss, aux

    return fn


LOSSES = {
    "softmax_xent": softmax_xent,
    "mlm_xent": mlm_xent,
    "causal_lm_xent": causal_lm_xent,
    "looped_lm_xent": looped_lm_xent,
    "seq2seq_xent": seq2seq_xent,
    "fused_causal_lm_xent": fused_causal_lm_xent,
}


def get_loss_fn(name: str, label_smoothing: float = 0.0):
    if name not in LOSSES:
        raise KeyError(f"unknown loss {name!r}; have {sorted(LOSSES)}")
    if not 0.0 <= label_smoothing < 1.0:
        raise ValueError(
            f"label_smoothing must be in [0, 1), got {label_smoothing}")
    fn = LOSSES[name]
    if label_smoothing > 0.0:
        if name != "softmax_xent":
            raise ValueError(
                f"label_smoothing is only supported for softmax_xent, "
                f"not {name!r}")
        import functools

        return functools.partial(fn, label_smoothing=label_smoothing)
    return fn
