"""Shared arithmetic of the per-layer readers in ``layer_metrics/``. A reader
takes the run's context — ``trace`` (trace_reduce.reduce_trace's result),
``counters``, ``config``, ``cell``, ``device_kind``, ``chips`` — and returns a
number, or None when it finds nothing to read."""

from __future__ import annotations

import re

import flops


def input_wait_pct(ctx):
    """Share of the window the step loop spent blocked on the loader's queue
    (the loader's own ``stall_stats.wait_s`` over the window's seconds)."""
    c = ctx["counters"]
    return 100.0 * c["input_wait_s"] / c["window_s"]


def step_device_ms(ctx):
    """Device-busy milliseconds a step: the union of the intervals in which
    an operation ran on device 0, over the whole steps traced."""
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["device0"]["busy_s"] / t["steps"]


def device_idle_pct(ctx):
    """Share of the traced slice in which no operation ran on the device
    (1 - busy union over the slice), averaged over the chips used."""
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def kernel_seconds(ctx, pattern_key: str):
    """(seconds, events) of device 0's operations whose name matches the
    configuration's pattern, or None. Kernels are leaf operations, so their
    self time is their duration."""
    t = ctx["trace"]
    pattern = ctx["config"].get(pattern_key)
    if not t or not pattern or not t["steps"]:
        return None
    rows = [row for name, row in t["device0"]["ops"].items()
            if re.search(pattern, name)]
    if not rows:
        return None
    return sum(r[1] for r in rows), sum(r[0] for r in rows)


def flash_attn_ms_per_step(ctx):
    found = kernel_seconds(ctx, "flash_kernel_pattern")
    return None if found is None else 1e3 * found[0] / ctx["trace"]["steps"]


def flash_attn_roofline(ctx):
    """The least time the chip could take for one step's attention calls
    (causal pairs only, forward and backward, one call site a layer; counted
    by flops.py from the shapes) over the time the trace shows for them."""
    found = kernel_seconds(ctx, "flash_kernel_pattern")
    if found is None:
        return None
    cfg, cell = ctx["config"], ctx["cell"]
    cost = flops.causal_flash_attention_cost(
        cell["batch_size"] // ctx["chips"], cfg["n_head"], cell["seq_len"],
        cfg["n_embd"] // cfg["n_head"])
    least = flops.roofline_seconds(
        cfg["n_layer"] * cost["flops"], cfg["n_layer"] * cost["bytes"],
        ctx["device_kind"])["seconds"]
    return 100.0 * least / (found[0] / ctx["trace"]["steps"])


def collective_exposed_ms_per_step(ctx):
    """The part of device 0's collective operations during which no other
    operation runs there, a step."""
    t = ctx["trace"]
    if not t or not t["steps"] or ctx["chips"] < 2:
        return None
    return 1e3 * t["device0"]["collective_exposed_s"] / t["steps"]
