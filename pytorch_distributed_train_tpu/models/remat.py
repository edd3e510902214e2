"""Rematerialization policy selection (the torch activation-checkpointing
`checkpoint_impl`/selective-checkpoint analogue, config-driven).

``remat=True`` recomputes what is inside each transformer block during
backward, bar one thing under every policy: what a Pallas attention kernel
handed back. The flash kernel's forward returns its output and
log-sum-exp, which are all its two backward kernels need of it; recomputed
with the block it was the same launch twice on the same operands, a fifth
of the kernel's time in a step (PERF.md section 6, PR 36). Kept, it costs a
layer one (B, S, H x Dv) array in the activation dtype and a (B, H, S) fp32
one beside the block input remat already keeps. The kernel tags the two by
name (``ops/attention.py`` ``FLASH_RESIDUALS_NAME``); a block with no
such kernel in it (the CPU, the XLA attention paths) traces no tag and
keeps nothing.

On large models the MXU-bound matmul recompute can dominate backward time;
``remat_policy="dots"`` keeps matmul outputs resident too (XLA's
``dots_saveable``) and recomputes only the cheap elementwise/norm chains —
the classic flops↔HBM dial. "dots_no_batch" saves only non-batch-dim
matmuls (scales better with batch).
"""

from __future__ import annotations

import jax
import flax.linen as nn

from pytorch_distributed_train_tpu.ops.attention import FLASH_RESIDUALS_NAME
from pytorch_distributed_train_tpu.ops.fused_update import (
    FUSED_EPILOGUE_NAME,
)

_policies = jax.checkpoint_policies
_keep_flash = _policies.save_only_these_names(FLASH_RESIDUALS_NAME)

# The tagged names a policy below kept in the traces since this was last
# cleared: the train step clears it before its backward pass is traced and
# reads it after, for the train.compile span's `remat_keeps` (steps.py).
kept: set[str] = set()


def _noting(policy):
    """``policy``, noting in :data:`kept` that it kept the flash kernel's
    residuals. A policy is asked once an equation as a block's backward is
    traced; a trace that JAX answers from its cache asks nothing."""
    def noted(prim, *avals, **params):
        keep = policy(prim, *avals, **params)
        if keep and _keep_flash(prim, *avals, **params):
            kept.add(FLASH_RESIDUALS_NAME)
        return keep
    return noted


POLICIES = {
    # recompute the whole block, bar what a Pallas attention kernel handed
    # back (the module docstring); the default
    "full": _noting(_keep_flash),
    "dots": _noting(_policies.save_from_both_policies(
        _policies.dots_saveable, _keep_flash)),
    "dots_no_batch": _noting(_policies.save_from_both_policies(
        _policies.dots_with_no_batch_dims_saveable, _keep_flash)),
    # Audit-driven epilogue dial (ISSUE 14; ops/fused_update.py): save
    # every intermediate EXCEPT the outputs tagged "fused_epilogue"
    # (bias+GELU, residual+LayerNorm — model.fused_epilogues). The
    # expensive MXU work stays resident; only the cheap elementwise
    # epilogues recompute in backward — the inverse trade of "dots",
    # aimed at the elementwise rows of `perf_ledger --audit`. Remat
    # choices stay orthogonal to the fusion itself: any policy runs
    # over fused or unfused blocks.
    "no_fused_epilogue": _noting(
        _policies.save_anything_except_these_names(FUSED_EPILOGUE_NAME)),
}


def remat_block(block_cls, enabled: bool, policy: str = "full"):
    """Wrap a block class with nn.remat per the configured policy."""
    if not enabled:
        return block_cls
    if policy not in POLICIES:
        raise ValueError(
            f"remat_policy must be one of {sorted(POLICIES)}, got {policy!r}")
    return nn.remat(block_cls, policy=POLICIES[policy])
