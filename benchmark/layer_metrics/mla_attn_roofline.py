"""Per-layer metric `mla_attn_roofline`: the least time the chip could take
for one step's latent-attention calls (mla_flops.py: causal pairs, 192-deep
scores, 128-deep values, forward and backward once a latent layer) over the
time the trace shows for the kernel's events (the forward that activation
checkpointing runs again included). None where the configuration names no
kernel or the trace holds none of its events."""

import flops
import mla_flops
import readers


def read(ctx):
    found = readers.kernel_seconds(ctx, "flash_kernel_pattern")
    cfg, cell = ctx["config"], ctx["cell"]
    if found is None or "qk_rope_head_dim" not in cfg:
        return None
    layers = cfg["num_hidden_layers"] // cfg["layer_group_size"]
    cost = mla_flops.causal_mla_attention_cost(
        cell["batch_size"] // ctx["chips"], cfg["num_attention_heads"],
        cell["seq_len"], cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        cfg["v_head_dim"])
    least = flops.roofline_seconds(layers * cost["flops"],
                                   layers * cost["bytes"],
                                   ctx["device_kind"])["seconds"]
    return 100.0 * least / (found[0] / ctx["trace"]["steps"])
