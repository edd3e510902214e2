"""Fused weight-dequant matmul Pallas kernels (W8A16 / W4A16 GEMV).

Why this kernel exists (AOT_AB.json, round 5): XLA materializes the
dequantized bf16 weights of the weight-only int8/int4 decode path —
the quantized array is what LIVES in HBM between steps, but each step
still writes + re-reads a full bf16 copy (the v5e cost model shows
int4 decode accessing 2.9x int8's bytes, with ~288 MiB of dequant
temps per step). That forfeits exactly the bandwidth the quantization
was meant to save in the HBM-bound decode regime.

This kernel performs the dequant IN VMEM, between the HBM read and the
MXU: each grid step streams one (H, TILE_N) int8/int4 weight tile and
its scales into VMEM, converts in-register, and dots against the
(rows, H) activations — HBM traffic is the QUANTIZED bytes plus the
small activations/outputs, never a bf16 weight copy. TILE_N aligns to
the int4 GROUP (128), so a tile sees exactly one scale column per
input row (int4) or one scale row (int8's per-output channels).

Decode shapes: x is (rows, H) with rows = B*S tiny (1..k+1 per
sequence in a serving batch), W is (H, N). The contraction dim H stays
UNTILED (a 4096 x 128 int4 tile is 256 KiB — comfortably VMEM); rows
pad to the fp32 sublane tile (8).

Scale layouts (quant.py):
- int8 ``quantize_leaf``: per-output-channel, scale (1, N).
- int4 ``quantize_leaf_int4``: per (input row, output group of G),
  scale (H, N/G, 1) — the scale sits INSIDE the contraction, which is
  why it cannot be factored out of the matmul after the fact.

Validated like the flash kernels: interpret-mode numerics on CPU
(tests/test_quant_matmul.py) + a Mosaic compile for a described v5e
(tests/test_tpu_compile.py). Integration into the decode model path
is the documented follow-up — the kernel is the hard part the cost
model demanded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 128  # == quant.py's int4 group size; one scale column per tile


def _w8_kernel(x_ref, w_ref, s_ref, o_ref, *, out_dtype):
    # x: (R, H) bf16; w: (H, T) int8; s: (1, T) f32 per-output scales
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...]).astype(out_dtype)


def _w4_kernel(x_ref, w_ref, s_ref, o_ref, *, out_dtype):
    # x: (R, H) bf16; w: (H, T) int4; s: (NG, H) f32 — the FULL scale
    # table, transposed. Scale varies along the CONTRACTION dim, so it
    # must multiply the weights BEFORE the dot — in VMEM, not in HBM.
    # The whole (NG, H) table rides one constant-index block (Mosaic
    # tiling forbids an (H, 1) column block; the pipeline keeps a
    # constant block resident across grid steps, so HBM reads it once)
    # and the tile's group column is a dynamic row slice at grid index
    # j — tile width == group size makes j THE group id.
    x = x_ref[...].astype(jnp.float32)
    # row select without dynamic_slice (unimplemented in the TC
    # lowering): mask-reduce the table against an iota — 43-row
    # VMEM math, negligible next to the dot
    s = s_ref[...]  # (NG, H)
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jnp.sum(jnp.where(rows == pl.program_id(0), s, 0.0),
                  axis=0, keepdims=True)  # (1, H)
    w = w_ref[...].astype(jnp.float32) * col.T  # (H, T) * (H, 1)
    o_ref[...] = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(out_dtype)


def _pad_rows(x2, mult: int = 8):
    R = x2.shape[0]
    pad = (-R) % mult
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, R


def quant_matmul(x: jax.Array, q: dict, *, interpret: bool = False,
                 out_dtype=None) -> jax.Array:
    """``x @ dequant(q)`` with the dequant fused into the tile stream.

    x: (..., H) activations (bf16/f32); q: a quant.py struct —
    {'w_int8', 'scale'} (per-output scales) or {'w_int4', 'scale'}
    (group-wise). Returns (..., N) in ``out_dtype`` (default x.dtype).
    N and (for int4) H must be multiples of TILE_N and the group size
    respectively — true for every transformer kernel this serves.
    """
    from pytorch_distributed_train_tpu import quant

    if not quant._is_quant_leaf(q):
        raise ValueError(
            "quant_matmul takes a quant.py leaf struct "
            f"({{'w_int8'|'w_int4', 'scale'}}), got keys "
            f"{sorted(q) if isinstance(q, dict) else type(q).__name__}")
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    H = x.shape[-1]
    x2, R = _pad_rows(x.reshape(-1, H))
    Rp = x2.shape[0]

    if quant._W4 in q:
        w, scale = q[quant._W4], q[quant._S]
        axis, G = quant._int4_grouping(w.shape, scale.shape)
        N = w.shape[1]
        if (w.ndim != 2 or w.shape[0] != H or axis != 1 or G != TILE_N
                or N % TILE_N):
            raise ValueError(
                f"W4 fused matmul needs a 2D (H={H}, N) weight grouped "
                f"along axis 1 with G == {TILE_N} and N % {TILE_N} == "
                f"0, got shape {w.shape}, axis {axis}, G {G}")
        s2t = scale.reshape(H, N // G).T  # (NG, H): row g scales tile g
        out = pl.pallas_call(
            functools.partial(_w4_kernel, out_dtype=out_dtype),
            grid=(N // TILE_N,),
            in_specs=[
                pl.BlockSpec((Rp, H), lambda j: (0, 0)),
                pl.BlockSpec((H, TILE_N), lambda j: (0, j)),
                pl.BlockSpec((N // G, H), lambda j: (0, 0)),
            ],
            out_specs=pl.BlockSpec((Rp, TILE_N), lambda j: (0, j)),
            out_shape=jax.ShapeDtypeStruct((Rp, N), out_dtype),
            interpret=interpret,
        )(x2, w, s2t)
    else:
        w, scale = q[quant._W], q[quant._S]
        if (w.ndim != 2 or w.shape[0] != H or w.shape[1] % TILE_N
                or scale.shape != (1, w.shape[1])):
            raise ValueError(
                f"W8 fused matmul needs a 2D (H={H}, N) weight with "
                f"per-output (1, N) scales and N % {TILE_N} == 0, got "
                f"w {w.shape}, scale {scale.shape}")
        N = w.shape[1]
        out = pl.pallas_call(
            functools.partial(_w8_kernel, out_dtype=out_dtype),
            grid=(N // TILE_N,),
            in_specs=[
                pl.BlockSpec((Rp, H), lambda j: (0, 0)),
                pl.BlockSpec((H, TILE_N), lambda j: (0, j)),
                pl.BlockSpec((1, TILE_N), lambda j: (0, j)),
            ],
            out_specs=pl.BlockSpec((Rp, TILE_N), lambda j: (0, j)),
            out_shape=jax.ShapeDtypeStruct((Rp, N), out_dtype),
            interpret=interpret,
        )(x2, w, scale.astype(jnp.float32))
    return out[:R].reshape(*lead, N)
