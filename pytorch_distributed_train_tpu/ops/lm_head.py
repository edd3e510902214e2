"""The training step's LM head and loss: which path takes them, and the
line that says so.

A model that offers its head's operands (``HeadOperands``: the final
hidden states and the table; models/gpt2.py ``head_operands``) lets the
loss ride the head's products in the Pallas kernels of
ops/lm_head_loss.py; everything else, and every shape or backend those
kernels cannot take, keeps the logits path. This module is the dispatch
(as ops/attention.py is the flash kernel's) and imports the kernels only
when a step takes them.
"""

from __future__ import annotations

import sys
from typing import NamedTuple

import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu.ops import attention


class HeadOperands(NamedTuple):
    """What a model hands the training step in place of logits."""

    x: jax.Array      # (B, S, C) final hidden states, compute dtype
    table: jax.Array  # (V, C) tied table, or (shards, V, C): one view a
    #                   shard of the batch (models/gpt2.py _per_shard_table)
    cp: object        # the mesh's axes (ops/attention.py), None: no mesh

    @property
    def local_rows(self) -> int:
        """Rows one device's kernel call sees."""
        shards = self.table.shape[0] if self.table.ndim == 3 else 1
        return self.x.shape[0] * self.x.shape[1] // shards


def logits(head: HeadOperands):
    """The logits path: (B, S, V) float32 from operands in the compute
    dtype with float32 accumulation; under the per-shard view batched
    over the shards, each its own view."""
    x, table, _ = head
    with jax.named_scope("lm_head"):  # a phase of the step: steps.py
        if table.ndim == 3:
            shards, (B, S, C) = table.shape[0], x.shape
            out = jax.lax.dot_general(
                x.reshape(shards, B // shards, S, C), table,
                (((3,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32).reshape(B, S, -1)
        else:
            out = jax.lax.dot_general(
                x, table, (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
    return out.astype(jnp.float32)


def _interpret() -> bool:
    """Mosaic on a TPU; elsewhere the interpreter, which only a test that
    steers the gate below ever reaches."""
    return not attention._on_tpu()


def unsupported(head: HeadOperands) -> str | None:
    """Why the kernels do not take this head, or None when they do."""
    if not attention._on_tpu():
        return "the backend is not a TPU"
    if (head.cp is not None and head.cp.mesh.size > 1
            and head.table.ndim != 3):
        # GSPMD cannot partition a Mosaic call: under a mesh the kernels
        # run a device on its own rows and its own view of the table
        return "a sharded mesh without the per-shard view of the table"
    rows, (vocab, width) = head.local_rows, head.table.shape[-2:]
    if rows % 128:
        return f"rows={rows} is not a multiple of 128"
    if width % 128:
        return f"width={width} is not a multiple of 128"
    if vocab < 128:
        return f"vocab={vocab} is under one tile of 128"
    return None


def head_token_xent(head: HeadOperands, labels):
    """Per-token cross-entropy (B, S), float32, of the head ``head`` offers
    against ``labels`` (B, S): ops/lm_head_loss.py ``token_xent`` over all
    B*S rows. Under a mesh the kernels run inside a shard_map over the
    batch axes, each device on its own sequences and its own (V, C) view
    of the table, so that view's gradient leaves as per-shard partial sums
    and the
    broadcast's transpose sums the table ONCE (PERF.md section 6, PR 27).
    The scope ``lm_head`` sits INSIDE the region: a Pallas call takes its
    enclosing scope's name, and ``%shard_map.N custom-call`` is how the
    benchmark finds the flash kernel under a mesh."""
    from pytorch_distributed_train_tpu.ops.lm_head_loss import token_xent

    x, table, cp = head

    def local(x, table, labels):
        b, S, C = x.shape
        with jax.named_scope("lm_head"):
            per = token_xent(x.reshape(b * S, C), table.reshape(-1, C),
                             labels.reshape(b * S), interpret=_interpret())
        return per.reshape(b, S)

    if table.ndim == 2:
        return local(x, table, labels)
    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_train_tpu.utils.compat import shard_map

    batch = tuple(cp.batch_axes)
    return shard_map(
        local, mesh=cp.mesh,
        in_specs=(P(batch, None, None), P(batch, None, None), P(batch, None)),
        out_specs=P(batch, None), check_vma=False)(x, table, labels)


_logged: set[tuple] = set()


def log_resolution(out, why: str | None) -> str:
    """Say once a shape, at trace time, whether the training step's head
    and loss took the kernels (``impl=pallas``; ``why`` None) or kept the
    logits path (``impl=xla``, with the reason), beside ``[attention]``,
    ``[kda]`` and ``[moe]``; ``out`` is what the model returned, the
    head's operands or (B, S, V) logits. On stderr: stdout is the product
    of the generation CLIs. Returns the resolution in a word, ``pallas``
    or ``xla: <reason>``."""
    if isinstance(out, HeadOperands):
        rows, (vocab, width) = out.local_rows, out.table.shape[-2:]
    else:
        rows, vocab, width = out.shape[0] * out.shape[1], out.shape[2], "-"
    key = (rows, vocab, width, why)
    if key not in _logged:
        _logged.add(key)
        if why is None:
            from pytorch_distributed_train_tpu.ops.lm_head_loss import (
                tile_sizes,
            )

            tiles = tile_sizes(rows, vocab)
            said = (f"impl=pallas rows={rows} vocab={vocab} width={width} "
                    f"tiles={tiles.n}x{tiles.v} "
                    f"ragged_cols={vocab % tiles.v} logits=float32")
        else:
            said = (f"impl=xla rows={rows} vocab={vocab} width={width} "
                    f"reason={why}")
        print(f"[lm_head] {said}", file=sys.stderr, flush=True)
    return "pallas" if why is None else f"xla: {why}"
