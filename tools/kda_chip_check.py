"""The KDA chunk core on the device it runs on, against the token-by-token
recurrence, at one benchmark cell's own shape: worst error of the output and
of the five gradients, and whether any is not a number.

    python tools/kda_chip_check.py [--shape 1 8192 8 128] [--seed 0]

Four readings, one JSON line each: one-token log-decays drawn log-uniformly
down to -40 with step sizes up to 2 through the UNBOUNDED form of
ops/kda.py (``lower_bound=None``), then decays inside (-5, 0) with step
sizes in (0, 1) through the bounded form and, the same data, through the
unbounded one; each with bfloat16 operands (as the cells run) and the first
also with float32 operands. The ground truth is the recurrence of
``kda.kda_recurrent`` in float32 at the highest matmul precision, walked in
blocks under ``jax.checkpoint`` so that its backward pass fits beside 8192
tokens' states. On a TPU the dispatch takes the kernel pair
(``[kda] ... impl=pallas``); elsewhere the XLA scan, and the line says so.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from pytorch_distributed_train_tpu.ops import kda  # noqa: E402


def recurrent_in_blocks(q, k, v, g, beta, block=256):
    """``kda.kda_recurrent``'s step, the state carried across blocks."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    B, S, H, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    blocks = tuple(jnp.moveaxis(x, 1, 0).reshape(S // block, block,
                                                 *x.shape[:1], *x.shape[2:])
                   for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        jax.checkpoint(lambda s, xs: jax.lax.scan(step, s, xs)),
        jnp.zeros((B, H, dk, v.shape[-1]), f32), blocks)
    return jnp.moveaxis(o.reshape(S, B, H, -1), 0, 1)


def draw(seed, shape, lowest, dtype):
    B, S, H, d = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], shape)) * d ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    if lowest is None:  # inside a bounded gate's (-5, 0), beta in (0, 1)
        g = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], shape))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    else:  # log-uniform magnitudes from 1e-3 down to ``lowest``
        g = -jnp.exp(jax.random.uniform(
            ks[3], shape, minval=jnp.log(1e-3), maxval=jnp.log(-lowest)))
        beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (B, S, H)))
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    return (q, k, v, g, beta), jax.random.normal(ks[5], shape)


def reading(name, seed, shape, lowest, lower_bound, dtype):
    args, w = draw(seed, shape, lowest, dtype)

    def both(f):
        def loss(*a):
            out = f(*a).astype(jnp.float32)
            return jnp.sum(out * w), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        return (out, *grads)

    with jax.default_matmul_precision("highest"):
        want = both(recurrent_in_blocks)
    got = both(lambda *a: kda.kda_chunked(*a, lower_bound=lower_bound))
    worst, finite = {}, True
    for what, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), want, got):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        finite &= bool(jnp.all(jnp.isfinite(b)))
        worst[what] = float(jnp.max(jnp.abs(a - b))
                            / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30))
    line = {"reading": name, "shape": list(shape),
            "operands": jnp.dtype(dtype).name,
            "form": "bounded" if lower_bound is not None else "unbounded",
            "log_decay_min": float(jnp.min(args[3])),
            "beta_max": float(jnp.max(args[4])), "finite": finite,
            "worst_error_over_max": worst,
            "device": jax.devices()[0].device_kind}
    print(json.dumps(line), flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", type=int, nargs=4, default=(1, 8192, 8, 128))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    shape = tuple(args.shape)
    lines = [
        reading("decays_to_-40_beta_to_2", args.seed, shape, -40.0, None,
                jnp.bfloat16),
        reading("decays_to_-40_beta_to_2", args.seed, shape, -40.0, None,
                jnp.float32),
        reading("decays_in_(-5,0)_beta_in_(0,1)", args.seed, shape, None,
                -5.0, jnp.bfloat16),
        reading("decays_in_(-5,0)_beta_in_(0,1)", args.seed, shape, None,
                None, jnp.bfloat16),
    ]
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "kda_chip_check.jsonl"), "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0 if all(line["finite"] for line in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
