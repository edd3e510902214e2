"""Operations and bytes of the gated delta rule's chunk core (Kimi Delta
Attention) as the ALGORITHM needs them, from a configuration's keys alone:
the same work whichever path (kernel pair or XLA scan), chunk or decay-gate
form the program runs, so a faster path reads a larger share and a path
that does more arithmetic for the same result does not.

The algorithm, at the chunk stated here (``CHUNK`` tokens; a head of d_k key
and d_v value channels, its state d_k x d_v), a token and head, forward:

* the in-chunk tables, the causal half of each alone: A = K K^T below the
  diagonal, (C - 1) / 2 pairs a token, B = Q K^T on and below it and B U,
  (C + 1) / 2 pairs each: 2 d_k (C - 1) / 2 + 2 d_k (C + 1) / 2
  + 2 d_v (C + 1) / 2;
* the inverse's right-hand sides, (I + A)^-1 [K exp G | V] as a triangular
  solve: 2 (d_k + d_v) (C + 1) / 2 (forming the inverse itself is left out:
  under 2 % of the sum at C = 64);
* the state's three products, W S, (Q exp G) S and the update K^T U:
  3 x 2 d_k d_v.

Backward is twice the forward; a forward pass run again by activation
checkpointing, and tables recomputed by a backward kernel, do not count.
Bytes: q, k, v and o in the compute type, g and beta in float32, and the
gradient of each, every one read or written once.
"""

from __future__ import annotations

CHUNK = 64  # tokens a chunk: the count's own, stated, not the program's


def kda_core_cost(batch: int, seq: int, heads: int, d_k: int, d_v: int,
                  chunk: int = CHUNK, itemsize: int = 2) -> dict:
    """Forward AND backward of one layer's core."""
    tables = d_k * (chunk - 1) + d_k * (chunk + 1) + d_v * (chunk + 1)
    solve = (d_k + d_v) * (chunk + 1)
    state = 6.0 * d_k * d_v
    tokens = float(batch * seq * heads)
    moved = (2 * d_k + 2 * d_v) * itemsize + 4 * d_k + 4  # q k v o, g, beta
    return {"flops": 3.0 * tokens * (tables + solve + state),
            "bytes": 2.0 * tokens * moved}


def kda_layers(config: dict):
    """(KDA layers, heads held here, head width) of a configuration that has
    such layers, by the keys each family's file carries; None otherwise."""
    depth = config.get("num_hidden_layers")
    linear = config.get("linear_attn_config")
    if linear and depth and "gqa_layers" in config:
        # softmax layers are listed; every other layer is linear attention
        return (depth - len(config["gqa_layers"]), linear["num_heads"],
                linear["head_dim"])
    group = config.get("layer_group_size")
    if group and depth and "kda_lower_bound" in config:
        # groups of `layer_group_size`, the last of each latent attention
        return (depth - depth // group, config["num_attention_heads"],
                config["head_dim"])
    return None


def layers_cost(config: dict, cell: dict, chips: int):
    """(flops, bytes) of one step's chunk cores, or None where the
    configuration has no such layer."""
    found = kda_layers(config)
    if found is None or not found[0]:
        return None
    layers, heads, width = found
    cost = kda_core_cost(cell["batch_size"] // chips, cell["seq_len"], heads,
                         width, width)
    return layers * cost["flops"], layers * cost["bytes"]
