"""ops/kda.py: the chunked gated delta rule against the token-by-token
recurrence, outputs and every gradient, at each chunk size the model may
use and over several segments; and the guards on its shapes."""

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_train_tpu.ops import kda


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(seed, B=2, S=192, H=2, dk=16, dv=8, spread=3.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, S, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    # log-decays all the way down to the bound: the blocks' reference
    # points are what keeps exp() inside float32 there
    g = -5.0 * jax.nn.sigmoid(spread * jax.random.normal(ks[3], (B, S, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, S, H, dv))


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_chunked_equals_the_recurrence_outputs_and_all_gradients(chunk):
    args, w = _inputs(chunk)
    # 192 tokens: 12, 6 or 3 chunks, walked two chunks a segment or one
    run = lambda *a: kda.kda_chunked(*a, chunk=chunk, segment_chunks=2)  # noqa: E731
    want, got = kda.kda_recurrent(*args), run(*args)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    grads = lambda f: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), grads(kda.kda_recurrent),
                          grads(run)):
        assert float(jnp.max(jnp.abs(a - b))) \
            < 2e-5 * float(jnp.max(jnp.abs(a))), name


def test_a_decay_at_the_bound_for_a_whole_chunk_stays_finite():
    (q, k, v, g, beta), _ = _inputs(7, S=128)
    g = jnp.full_like(g, -5.0)  # 64 x 5 = 320: exp(320) is not a float32
    out = kda.kda_chunked(q, k, v, g, beta, chunk=64)
    assert bool(jnp.all(jnp.isfinite(out)))
    want = kda.kda_recurrent(q, k, v, g, beta)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-6
    dg = jax.grad(lambda g_: jnp.sum(kda.kda_chunked(q, k, v, g_, beta)))(g)
    assert bool(jnp.all(jnp.isfinite(dg)))


def test_the_state_carries_across_segments_and_bf16_operands_keep_f32_state():
    (q, k, v, g, beta), _ = _inputs(5, S=256, spread=1.0)
    g = g * 0.02  # slow decays: a token still sees the first segment
    one = kda.kda_chunked(q, k, v, g, beta, chunk=32, segment_chunks=8)
    many = kda.kda_chunked(q, k, v, g, beta, chunk=32, segment_chunks=1)
    assert float(jnp.max(jnp.abs(one - many))) < 1e-6
    cut = kda.kda_chunked(q[:, 128:], k[:, 128:], v[:, 128:], g[:, 128:],
                          beta[:, 128:], chunk=32)
    assert float(jnp.max(jnp.abs(one[:, 128:] - cut))) > 1e-3
    low = kda.kda_chunked(*(x.astype(jnp.bfloat16) for x in (q, k, v)), g,
                          beta, chunk=32)
    assert low.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(low.astype(jnp.float32) - one))) < 0.05


@pytest.mark.parametrize("kwargs,match", [
    (dict(chunk=24), "multiple of 16"),
    (dict(chunk=64), "divide"),
    (dict(chunk=16, lower_bound=-6.0), "overflows"),
])
def test_shapes_and_bounds_the_algorithm_cannot_take_are_refused(kwargs,
                                                                 match):
    (q, k, v, g, beta), _ = _inputs(1, S=48)
    with pytest.raises(ValueError, match=match):
        kda.kda_chunked(q, k, v, g, beta, **kwargs)
