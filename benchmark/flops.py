"""The benchmark's yardstick: chip peaks, model FLOPs per item and the
flash-attention kernel's operations and bytes, all from shapes.

A copy on purpose (the program keeps its own in ``utils/flops.py``): later
PRs may change the program, not the ruler they are measured with. FLOPs are
2 x multiply-adds; a training step is 3 x forward; recomputed work never
counts. Unlike the program's table, attention is counted causal here
(S(S+1)/2 pairs, not S^2), so no share of a peak can be flattered past 100 %.
"""

from __future__ import annotations

# device_kind -> (bf16 peak FLOP/s, HBM bytes/s) of ONE chip. Source: Google
# Cloud TPU documentation, "TPU v5e" system architecture page: 197 TFLOP/s
# bf16, 819 GB/s HBM. Exact match only: an unlisted kind is an error.
PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
}


def peaks(device_kind: str) -> tuple[float, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}: "
                       "add them, with their source, to benchmark/flops.py")
    return PEAKS[device_kind]


def gpt2_train_flops_per_token(n_embd: int, n_layer: int, n_inner: int,
                               vocab: int, seq: int) -> float:
    """Pre-LN decoder with a tied head: q/k/v/o projections 8 d^2, causal
    scores and values 4 d (S+1)/2 per token, MLP 4 d m, head 2 d V."""
    d = n_embd
    layer = 8.0 * d * d + 4.0 * d * (seq + 1) / 2.0 + 4.0 * d * n_inner
    return 3.0 * (n_layer * layer + 2.0 * d * vocab)


def _conv_out(n: int, k: int, s: int, pad: int) -> int:
    return (n + 2 * pad - k) // s + 1


def resnet50_train_flops_per_image(image_size: int, num_classes: int) -> float:
    """He et al. 2015 ResNet-50: 7x7/2 stem, 3x3/2 max-pool, bottleneck
    stages (3, 4, 6, 3) with the stride on the 3x3, global pool, fc."""
    h = _conv_out(image_size, 7, 2, 3)
    flops = 2.0 * h * h * 64 * 7 * 7 * 3
    h = _conv_out(h, 3, 2, 1)
    cin = 64
    for i, blocks in enumerate((3, 4, 6, 3)):
        f = 64 * 2 ** i
        for j in range(blocks):
            s = 2 if i > 0 and j == 0 else 1
            flops += 2.0 * h * h * cin * f
            ho = _conv_out(h, 3, s, 1)
            flops += 2.0 * ho * ho * f * 9 * f + 2.0 * ho * ho * f * 4 * f
            if s != 1 or cin != 4 * f:
                flops += 2.0 * ho * ho * cin * 4 * f
            cin, h = 4 * f, ho
    return 3.0 * (flops + 2.0 * cin * num_classes)


def causal_flash_attention_cost(batch: int, heads: int, seq: int,
                                head_dim: int, itemsize: int = 2) -> dict:
    """What causal attention needs, forward AND backward, for one call site
    (one layer): only the S(S+1)/2 pairs on and under the diagonal. Forward
    is two products (QK^T, PV): 4 B H D S(S+1)/2. Backward is four (dV, dP,
    dQ, dK): twice that; the recomputed scores do not count. Bytes: q, k, v,
    o and their four gradients, each read or written once."""
    pairs = seq * (seq + 1) / 2.0
    fwd = 4.0 * batch * heads * head_dim * pairs
    return {"flops": 3.0 * fwd,
            "bytes": 8.0 * batch * seq * heads * head_dim * itemsize}


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> dict:
    """The least time one chip could take, and which of the two bounds it."""
    peak_flops, peak_bw = peaks(device_kind)
    t_c, t_m = flops / peak_flops, nbytes / peak_bw
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
