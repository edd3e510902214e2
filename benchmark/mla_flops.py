"""Operations and bytes of latent attention's core as the training path
runs it (expanded form): causal softmax attention whose scores are
``qk_dim`` deep (the plain dims beside the rotated ones) and whose values
are ``v_dim`` deep. A new file beside ``flops.py``, whose own count has one
head dim for all three operands."""

from __future__ import annotations


def causal_mla_attention_cost(batch: int, heads: int, seq: int, qk_dim: int,
                              v_dim: int, itemsize: int = 2) -> dict:
    """Forward AND backward of one call site (one layer), only the
    S(S+1)/2 pairs on and under the diagonal. Forward: Q K^T (2 x pairs x
    qk_dim) and P V (2 x pairs x v_dim) a head; backward dV and dP at
    v_dim, dQ and dK at qk_dim: twice the forward. Recomputed scores, and
    a forward pass run again by activation checkpointing, do not count.
    Bytes: q and k (qk_dim), v and o (v_dim) and their four gradients, each
    read or written once."""
    pairs = seq * (seq + 1) / 2.0
    fwd = 2.0 * batch * heads * pairs * (qk_dim + v_dim)
    return {"flops": 3.0 * fwd,
            "bytes": 2.0 * batch * seq * heads * (2 * qk_dim + 2 * v_dim)
            * itemsize}
