"""Fused weight-dequant matmul kernels (ops/quant_matmul.py).

Correctness anchor: the kernel must equal dequantize-then-matmul in
f32 — fusing the dequant into the tile stream changes WHERE the
scales multiply (VMEM, inside the pallas_call), never the math. Run
in interpret mode on CPU, same discipline as the flash-attention
kernels; the v5e Mosaic compile is covered by
tests/test_tpu_compile.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu import quant
from pytorch_distributed_train_tpu.ops.quant_matmul import quant_matmul

H, N = 256, 384  # N = 3 tiles of 128; H = 2 int4 groups


def _w(seed, shape=(H, N)):
    return jnp.asarray(
        np.random.default_rng(seed).normal(0, 0.05, shape), jnp.float32)


@pytest.mark.parametrize("rows", [1, 5, 8])
def test_w8_matches_dequant_matmul(rows):
    w = _w(0)
    q = quant.quantize_leaf(w)
    assert q["scale"].shape == (1, N)
    x = jnp.asarray(
        np.random.default_rng(1).normal(0, 1, (rows, H)), jnp.float32)
    ref = x @ quant.dequantize_leaf(q, jnp.float32)
    got = quant_matmul(x, q, interpret=True, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_w4_matches_dequant_matmul():
    w = _w(2)
    q = quant.quantize_leaf_int4(w)
    axis, G = quant._int4_grouping(q["w_int4"].shape, q["scale"].shape)
    assert (axis, G) == (1, 128)
    x = jnp.asarray(
        np.random.default_rng(3).normal(0, 1, (3, H)), jnp.float32)
    ref = x @ quant.dequantize_leaf(q, jnp.float32)
    got = quant_matmul(x, q, interpret=True, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_leading_dims_and_bf16(rows=2):
    w = _w(4)
    q = quant.quantize_leaf(w)
    x = jnp.asarray(
        np.random.default_rng(5).normal(0, 1, (rows, 3, H)), jnp.bfloat16)
    got = quant_matmul(x, q, interpret=True)
    assert got.shape == (rows, 3, N)
    assert got.dtype == jnp.bfloat16
    ref = (x.reshape(-1, H).astype(jnp.float32)
           @ quant.dequantize_leaf(q, jnp.float32)).reshape(rows, 3, N)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_unsupported_layouts_raise():
    # int4 grouped along axis 0 (wide-in weights) is the documented v1
    # gap — must refuse, not silently miscompute
    w = _w(6, (N * 2, H))  # axis 0 is the largest → grouping axis 0
    q4 = quant.quantize_leaf_int4(w)
    x = jnp.ones((1, N * 2), jnp.float32)
    with pytest.raises(ValueError, match="W4 fused"):
        quant_matmul(x, q4, interpret=True)
    # 3D kernels unsupported
    q8 = quant.quantize_leaf(jnp.zeros((H, 4, 64), jnp.float32))
    with pytest.raises(ValueError, match="W8 fused"):
        quant_matmul(jnp.ones((1, H)), q8, interpret=True)
