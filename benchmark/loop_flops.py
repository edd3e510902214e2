"""Operations and bytes of the looped decoder's two kernels as the training
path runs them: the attention core once a (pass, layer) APPLICATION, and the
head's two kernels once an exit. A new file beside ``flops.py`` and
``gqa_flops.py``, whose counts are "once a layer" and know no passes: here
``total_ut_steps`` T and ``num_hidden_layers`` L come from the
configuration's file, and a step holds T x L attention call sites and T
exits."""

from __future__ import annotations

import flops
import readers


def loop_counts(config: dict):
    """(passes T, layers L) of a looped configuration; None where the
    configuration has no loop."""
    if "total_ut_steps" not in config or "num_hidden_layers" not in config:
        return None
    return int(config["total_ut_steps"]), int(config["num_hidden_layers"])


def loop_attention_cost(config: dict, cell: dict, chips: int):
    """(flops, bytes) of one step's T x L attention call sites: causal
    pairs S (S + 1) / 2 at ``num_attention_heads`` heads of ``head_dim``
    (as many KV heads), forward and backward once an application
    (``flops.causal_flash_attention_cost``); recomputed scores, and the
    forward that activation checkpointing runs again, do not count."""
    counts = loop_counts(config)
    if counts is None:
        return None
    sites = counts[0] * counts[1]
    cost = flops.causal_flash_attention_cost(
        cell["batch_size"] // chips, config["num_attention_heads"],
        cell["seq_len"], config["head_dim"])
    return sites * cost["flops"], sites * cost["bytes"]


def exit_head_cost(config: dict, cell: dict, chips: int):
    """(flops, bytes) of what the head's two KERNELS compute a step, T
    exits: the forward kernel the logits' product (2 N V C), the backward
    kernel the weights' gradient (2 N V C): 2 x 2 x N x V x C an exit. The
    third product, dX = dl . W, is XLA's and outside the kernels' events,
    so outside this count. Bytes: the float32 logits written once and read
    once, the bfloat16 dl written once, x and the table read by each
    kernel, the table's gradient written."""
    counts = loop_counts(config)
    if counts is None:
        return None
    n = cell["batch_size"] // chips * cell["seq_len"]
    v, c = config["vocab_size"], config["hidden_size"]
    flop = 2.0 * 2.0 * n * v * c
    nbytes = n * v * (4 + 4 + 2) + 2 * (2 * n * c + 2 * v * c) + 2 * v * c
    return counts[0] * flop, counts[0] * nbytes


def roofline_share(ctx, pattern_key: str, cost):
    """The least time the chip could take for ``cost`` (flops, bytes) over
    the time the trace shows, a step, for the events the configuration's
    ``pattern_key`` names, in percent; None where the run's context holds
    nothing to read (the readers' contract)."""
    found = readers.kernel_seconds(ctx, pattern_key)
    if found is None or cost is None:
        return None
    least = flops.roofline_seconds(*cost, ctx["device_kind"])["seconds"]
    return 100.0 * least / (found[0] / ctx["trace"]["steps"])
