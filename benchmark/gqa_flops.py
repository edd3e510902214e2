"""Operations and bytes of grouped-query attention's core as the training
path runs it: causal softmax attention of ``heads`` query heads over
``kv_heads`` KV heads, and with a window only the pairs of the band. A new
file beside ``flops.py``, whose own count has as many KV heads as query
heads and no window."""

from __future__ import annotations

import flops
import readers


def band_pairs(seq: int, window: int = 0) -> float:
    """(query, key) pairs of one head: query i meets keys j <= i, and with
    ``window`` > 0 only i - j < window: sum_i min(i + 1, window)."""
    w = min(window, seq) if window > 0 else seq
    return w * (w + 1) / 2.0 + (seq - w) * float(w)


def gqa_attention_cost(batch: int, heads: int, kv_heads: int, seq: int,
                       head_dim: int, window: int = 0,
                       itemsize: int = 2) -> dict:
    """Forward AND backward of one call site (one layer), only the pairs of
    the band. Forward is two products (Q K^T, P V): 4 x pairs x head_dim a
    query head. Backward is four (dV, dP, dQ, dK): twice that. Recomputed
    scores, and a forward pass run again by activation checkpointing, do
    not count. Bytes: q and o at ``heads``, k and v at ``kv_heads``, and
    their four gradients, each read or written once."""
    fwd = 4.0 * batch * heads * head_dim * band_pairs(seq, window)
    return {"flops": 3.0 * fwd,
            "bytes": 4.0 * batch * seq * (heads + kv_heads) * head_dim
            * itemsize}


def layers_cost(config: dict, cell: dict, chips: int, layer_type: str):
    """(flops, bytes) of one step's calls on the layers of ``layer_type``
    (``full_attention`` or ``sliding_attention``) of a configuration that
    lists its layers' types and heads; None where it does not."""
    types = config.get("layer_types")
    heads = config.get("num_attention_heads_per_layer")
    if not types or not heads or "num_key_value_heads" not in config:
        return None
    window = config["sliding_window"] \
        if layer_type == "sliding_attention" else 0
    flops = nbytes = 0.0
    for kind, h in zip(types, heads):
        if kind != layer_type:
            continue
        cost = gqa_attention_cost(
            cell["batch_size"] // chips, h, config["num_key_value_heads"],
            cell["seq_len"], config["head_dim"], window)
        flops, nbytes = flops + cost["flops"], nbytes + cost["bytes"]
    return (flops, nbytes) if flops else None


def roofline_share(ctx, pattern_key: str, layer_type: str):
    """The least time the chip could take for one step's calls on the
    layers of ``layer_type`` over the time the trace shows for the events
    the configuration's ``pattern_key`` names, in percent; None where the
    run's context holds nothing to read (the readers' contract)."""
    found = readers.kernel_seconds(ctx, pattern_key)
    cost = layers_cost(ctx["config"], ctx["cell"], ctx["chips"], layer_type)
    if found is None or cost is None:
        return None
    least = flops.roofline_seconds(*cost, ctx["device_kind"])["seconds"]
    return 100.0 * least / (found[0] / ctx["trace"]["steps"])
