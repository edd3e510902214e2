"""ops/kda_inputs.py: the KDA mixer's input shaping. The Pallas kernel pair,
which the dispatch takes on a TPU, against the XLA chain in every output
and every gradient: here in interpret mode, reached by steering the gate as
tests/test_kda.py does for the core's kernels."""

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_train_tpu.ops import attention, kda, kda_inputs

K, LOWER = 4, -5.0
OUTPUTS = ("q", "k", "v", "g")
OPERANDS = ("yq", "yk", "yv", "a", "q_conv", "k_conv", "v_conv", "A_log",
            "dt_bias")


def _steer(patch, tile=128, heads=2):
    """The dispatch sees a TPU; the kernels run interpreted, a tile of 128
    tokens and two heads a grid step."""
    patch.setattr(attention, "_on_tpu", lambda: True)
    patch.setattr(kda, "_interpret", lambda: True)
    patch.setattr(kda, "_logged", set())
    patch.setattr(kda_inputs, "KERNEL_TILE", tile)
    patch.setattr(kda_inputs, "KERNEL_HEADS", heads)


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    _steer(monkeypatch)


def _operands(seed, dtype=jnp.float32, B=2, S=384, H=4, d=128):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    y = [jax.random.normal(k, (B, S, H, d)).astype(dtype) for k in ks[:3]]
    a = 2.0 * jax.random.normal(ks[3], (B, S, H, d))
    taps = [0.5 * jax.random.normal(k, (K, H, d))
            for k in jax.random.split(ks[4], 3)]
    a_log = 0.3 * jax.random.normal(ks[5], (H,))
    dt_bias = jax.random.uniform(ks[6], (H, d), minval=-5.0, maxval=-1.0)
    return (*y, a, *taps, a_log, dt_bias), ks[7]


def _shape(*ops, lower_bound=LOWER, **kw):
    yq, yk, yv, a, wq, wk, wv, a_log, dt_bias = ops
    return kda_inputs.shape_inputs(yq, yk, yv, a, [wq, wk, wv], a_log,
                                   dt_bias, lower_bound=lower_bound, **kw)


def _outputs_and_gradients(ops, key, lower_bound=LOWER):
    weights = [jax.random.normal(k, ops[0].shape)
               for k in jax.random.split(key, 4)]

    def weighted(*ops):
        out = _shape(*ops, lower_bound=lower_bound)
        return sum(jnp.sum(o.astype(jnp.float32) * w)
                   for o, w in zip(out, weights)), out

    grads, out = jax.grad(weighted, argnums=tuple(range(9)),
                          has_aux=True)(*ops)
    return dict(zip(OUTPUTS + OPERANDS, (*out, *grads)))


@pytest.fixture(scope="module")
def both_paths():
    """{dtype name: (the XLA chain's, the kernel pair's)} outputs and
    gradients by name: three tiles of 128 tokens, so that the convolution's
    rows cross two boundaries in each direction; batch 2; two head groups
    of two heads. ``float32-softplus``: the unbounded gate's form (no
    lower bound: g = -exp(A_log) softplus(a + dt_bias))."""
    found = {}
    for name, dtype, lower in (("float32", jnp.float32, LOWER),
                               ("bfloat16", jnp.bfloat16, LOWER),
                               ("float32-softplus", jnp.float32, None)):
        ops, key = _operands(1, dtype)
        want = _outputs_and_gradients(ops, key, lower)
        with pytest.MonkeyPatch.context() as patch:
            _steer(patch)
            found[name] = (want, _outputs_and_gradients(ops, key, lower))
    return found


@pytest.mark.parametrize("name", OUTPUTS + OPERANDS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16",
                                   "float32-softplus"])
def test_the_pair_equals_the_xla_chain(both_paths, dtype, name):
    """Outputs and all nine gradients. float32 operands: to the order of
    the sums (the small gradients add 768 rows a channel, ``A_log``'s 128
    channels more); bfloat16: the casts' rounding points are the same, so
    what differs is a last bit here and there of a bfloat16 result. The
    gate's two forms share everything but g and its three gradients."""
    want, got = (x[name] for x in both_paths[dtype])
    assert want.dtype == got.dtype and want.shape == got.shape
    want, got = want.astype(jnp.float32), got.astype(jnp.float32)
    low = dtype == "bfloat16" and name in ("q", "k", "v", "yq", "yk", "yv")
    tol = 2 ** -7 if low else 2e-6
    assert float(jnp.max(jnp.abs(got - want))) \
        <= tol * float(jnp.max(jnp.abs(want))), name
    if low:  # a last bit, not a different number: nine in ten are equal
        assert float(jnp.mean(got == want)) > 0.9


def test_the_first_tokens_see_zeros_in_every_batch_row_and_head_group(
        as_on_a_tpu):
    """Token t < K - 1 has K - 1 - t taps on nothing: the rows kept from
    the tile before are zero at a sequence's start, whatever the grid
    walked last (the batch row and head group before this one)."""
    ops, _ = _operands(2, S=256)
    yv, wv = ops[2], ops[6]
    v = _shape(*ops)[2]
    for t in range(K - 1):
        y = sum(wv[K - 1 - j] * yv[:, t - j] for j in range(t + 1))
        assert float(jnp.max(jnp.abs(v[:, t] - jax.nn.silu(y)))) < 1e-6, t
    # and the next tile's first rows DO see the tile before
    cut = _shape(*(x[:, 128:] if x.ndim == 4 and x.shape[1] == 256 else x
                   for x in ops))[2]
    assert float(jnp.max(jnp.abs(v[:, 128:131] - cut[:, :3]))) > 1e-3
    assert float(jnp.max(jnp.abs(v[:, 131:] - cut[:, 3:]))) < 1e-6


@pytest.mark.parametrize("lower", [LOWER, None])
def test_a_gate_at_its_bounds_stays_finite(as_on_a_tpu, lower):
    """A pre-activation far out on either side: g reaches lower_bound (the
    unbounded gate: -exp(A_log) times the pre-activation itself) or 0 and
    every gradient is a number."""
    ops, key = _operands(3, S=128, H=2)
    a = jnp.where(ops[3] > 0, 1e4, -1e4)
    found = _outputs_and_gradients((*ops[:3], a, *ops[4:]), key, lower)
    g = found["g"]
    assert float(jnp.max(g)) == 0.0
    if lower is None:  # exp(A_log) in (0.4, 2.5), softplus(1e4 - 5) ~ 1e4
        assert -3e4 < float(jnp.min(g)) < -3e3
    else:
        assert float(jnp.min(g)) == lower
    for name, x in found.items():
        assert bool(jnp.all(jnp.isfinite(x.astype(jnp.float32)))), name


@pytest.mark.parametrize("shape,reason", [
    (dict(S=256, d=64), "d_k=64 d_v=64: not multiples of 128"),
    (dict(S=192), "S=192 is not whole tiles of 128"),
    (dict(S=256), None),
])
def test_the_dispatch_says_once_a_shape_what_shaped_the_inputs(
        shape, reason, as_on_a_tpu, capfd):
    """``[kda] ... inputs=pallas`` with the tile and the heads a grid step
    where the kernels take the shaping, ``inputs=xla reason=...`` where
    the gate refuses (the core's own gate: one decision for both)."""
    ops, _ = _operands(4, B=1, H=2, **shape)
    for _ in range(2):
        out = _shape(*ops)
    assert out[0].shape == ops[0].shape and out[3].dtype == jnp.float32
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("[kda]")]
    assert len(lines) == 1, lines
    d = ops[0].shape[-1]
    assert lines[0].startswith(f"[kda] S={shape['S']} heads=2 d={d} inputs=")
    assert lines[0].endswith(
        "inputs=pallas tile=128 heads_per_step=2" if reason is None
        else f"inputs=xla reason={reason}"), lines


def test_off_a_tpu_the_xla_chain_shapes_the_inputs(monkeypatch, capfd):
    monkeypatch.setattr(kda, "_logged", set())
    ops, _ = _operands(5, B=1, S=128, H=1)
    assert "pallas_call" not in str(jax.make_jaxpr(_shape)(*ops))
    assert "inputs=xla reason=the backend is not a TPU" \
        in capfd.readouterr().err


def test_the_pair_runs_a_device_on_its_own_block_under_a_mesh(
        devices8, as_on_a_tpu):
    """Batch and heads are independent, the sequence is not: under a
    sharded mesh the pair runs in the manual region the core's kernels run
    in, the small gradients summed over the batch axes by its transpose;
    with the sequence sharded the gate leaves the shaping to XLA."""
    from pytorch_distributed_train_tpu.config import MeshConfig
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh

    cp = attention.ContextParallelConfig(
        mesh=build_mesh(MeshConfig(data=2, tensor=2), devices8[:4]))
    ops, key = _operands(6, S=128, H=4)
    w = jax.random.normal(key, ops[0].shape)
    loss = lambda **kw: lambda *o: sum(  # noqa: E731
        jnp.sum(x.astype(jnp.float32) * w) for x in _shape(*o, **kw))
    assert "shard_map" in str(jax.make_jaxpr(loss(cp=cp))(*ops))
    which = (0, 3, 4, 7, 8)  # yq, a, q's taps, A_log, dt_bias
    with cp.mesh:
        got = jax.jit(jax.value_and_grad(loss(cp=cp), argnums=which))(*ops)
    want = jax.value_and_grad(
        lambda *o: sum(jnp.sum(x.astype(jnp.float32) * w)
                       for x in kda_inputs.shape_inputs_xla(
                           *o[:4], o[4:7], *o[7:], LOWER)),
        argnums=which)(*ops)
    assert abs(float(got[0] - want[0])) < 1e-5 * abs(float(want[0]))
    for a, b in zip(got[1], want[1]):
        assert float(jnp.max(jnp.abs(a - b))) \
            < 1e-5 * float(jnp.max(jnp.abs(b)))
    ring = attention.ContextParallelConfig(
        mesh=build_mesh(MeshConfig(data=2, context=2), devices8[:4]))
    assert "pallas_call" not in str(jax.make_jaxpr(loss(cp=ring))(*ops))
