"""ops/kda.py: the chunked gated delta rule against the token-by-token
recurrence, outputs and every gradient, at each chunk size the model may
use and over several segments, in both forms of the decay gate (a bounded
one's product form; an unbounded one's, with one-token decays down to
exp(-40) and step sizes up to 2); and the guards on its shapes. The same for
the kernel pair of ops/kda_kernel.py, which the dispatch takes on a TPU:
here in interpret mode, reached by steering the gate as a test of the
head's kernels does (tests/test_lm_head_loss.py)."""

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_train_tpu.ops import attention, kda


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The dispatch sees a TPU; the kernels run interpreted."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(kda, "_interpret", lambda: True)
    monkeypatch.setattr(kda, "_logged", set())

    def kernels(chunk=kda.KERNEL_CHUNK, heads=kda.KERNEL_HEADS):
        monkeypatch.setattr(kda, "KERNEL_CHUNK", chunk)
        monkeypatch.setattr(kda, "KERNEL_HEADS", heads)

    return kernels


KERNEL = dict(dk=128, dv=128)  # head widths the kernels take


def _inputs(seed, B=2, S=192, H=2, dk=16, dv=8, spread=3.0, lowest=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, S, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    if lowest is None:
        # log-decays all the way down to the bound: the blocks' reference
        # points are what keeps exp() inside float32 there
        g = -5.0 * jax.nn.sigmoid(
            spread * jax.random.normal(ks[3], (B, S, H, dk)))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    else:
        # an unbounded gate: one-token log-decays log-uniform from -1e-3
        # down to ``lowest``, step sizes up to 2
        g = -jnp.exp(jax.random.uniform(
            ks[3], (B, S, H, dk), minval=jnp.log(1e-3),
            maxval=jnp.log(-lowest)))
        beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (B, S, H)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, S, H, dv))


@pytest.mark.parametrize("impl,chunk,heads,lowest", [
    ("xla", 16, 0, None), ("xla", 32, 0, None), ("xla", 64, 0, None),
    # the kernel pair: two tiles of 128 tokens, 4 or 2 chunks a tile, one
    # head a grid step or both in step
    ("pallas", 32, 1, None), ("pallas", 64, 2, None),
    # the unbounded gate's form: decays down to exp(-40) a token (16 tokens
    # of a block reach exp(-640)), beta up to 2
    ("xla", 32, 0, -40.0), ("xla", 64, 0, -40.0), ("pallas", 64, 2, -40.0),
])
def test_chunked_equals_the_recurrence_outputs_and_all_gradients(
        impl, chunk, heads, lowest, request):
    form = dict(lower_bound=None) if lowest else {}
    if impl == "pallas":
        request.getfixturevalue("as_on_a_tpu")(chunk, heads)
        args, w = _inputs(chunk, S=256, lowest=lowest, **KERNEL)
        run = lambda *a: kda.kda_chunked(*a, **form)  # noqa: E731
    else:
        args, w = _inputs(chunk, lowest=lowest)
        # 192 tokens: 12, 6 or 3 chunks, walked two chunks a segment or one
        run = lambda *a: kda.kda_chunked(  # noqa: E731
            *a, chunk=chunk, segment_chunks=2, **form)
    if lowest:
        assert float(jnp.min(args[3])) < -35.0 and float(jnp.max(args[4])) > 1.9
    want, got = kda.kda_recurrent(*args), run(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    # step sizes past 1 make the chunk's triangular system worse
    # conditioned: three times the room, forward and backward
    room = 3.0 if lowest else 1.0
    assert float(jnp.max(jnp.abs(got - want))) < room * 2e-6
    grads = lambda f: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), grads(kda.kda_recurrent),
                          grads(run)):
        assert bool(jnp.all(jnp.isfinite(b))), name
        assert float(jnp.max(jnp.abs(a - b))) \
            < room * 2e-5 * float(jnp.max(jnp.abs(a))), name


def test_the_bounded_form_cannot_take_an_unbounded_gate():
    """What the second form is for: under decays of exp(-40) a token the
    bounded gate's product form multiplies exp(+280) by 0 inside a block
    (its reference point sits 7 tokens past a key), and the result is not a
    number; the same call told the gate's form is the recurrence's."""
    args, _ = _inputs(9, B=1, S=64, lowest=-40.0)
    bad = kda.kda_chunked(*args, chunk=32)
    assert not bool(jnp.all(jnp.isfinite(bad)))
    good = kda.kda_chunked(*args, chunk=32, lower_bound=None)
    assert float(jnp.max(jnp.abs(good - kda.kda_recurrent(*args)))) < 6e-6


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_decay_at_the_bound_for_a_whole_chunk_stays_finite(impl, request):
    if impl == "pallas":  # a chunk is the whole tile: 128 x 5 = 640
        request.getfixturevalue("as_on_a_tpu")(128)
    (q, k, v, g, beta), _ = _inputs(7, S=128,
                                    **(KERNEL if impl == "pallas" else {}))
    g = jnp.full_like(g, -5.0)  # 64 x 5 = 320: exp(320) is not a float32
    out = kda.kda_chunked(q, k, v, g, beta, chunk=64)
    assert bool(jnp.all(jnp.isfinite(out)))
    want = kda.kda_recurrent(q, k, v, g, beta)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-6
    dg = jax.grad(lambda g_: jnp.sum(kda.kda_chunked(q, k, v, g_, beta)))(g)
    assert bool(jnp.all(jnp.isfinite(dg)))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_state_carries_across_segments_and_bf16_operands_keep_f32_state(
        impl, request):
    if impl == "pallas":  # across grid steps: two tiles of 128 tokens
        request.getfixturevalue("as_on_a_tpu")()
    (q, k, v, g, beta), _ = _inputs(5, S=256, spread=1.0,
                                    **(KERNEL if impl == "pallas" else {}))
    g = g * 0.02  # slow decays: a token still sees the first segment
    one = kda.kda_chunked(q, k, v, g, beta, chunk=32, segment_chunks=8)
    many = kda.kda_chunked(q, k, v, g, beta, chunk=32, segment_chunks=1) \
        if impl == "xla" else kda.kda_recurrent(q, k, v, g, beta)
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


@pytest.mark.parametrize("shape,on_tpu,reason,form", [
    (dict(S=256, **KERNEL), False, "the backend is not a TPU", {}),
    (dict(S=256), True, "d_k=16 d_v=8: not multiples of 128", {}),
    (dict(S=192, **KERNEL), True, "S=192 is not whole tiles of 128", {}),
    (dict(S=256, **KERNEL), True, None, {}),
    # an unbounded gate's form: the same decision, the line says the form
    (dict(S=256), True, "d_k=16 d_v=8: not multiples of 128",
     dict(lower_bound=None)),
    (dict(S=128, **KERNEL), True, None, dict(lower_bound=None)),
])
def test_the_dispatch_says_once_a_shape_what_took_the_core(
        shape, on_tpu, reason, form, request, capfd):
    """``[kda] ... impl=pallas`` with the kernels' tile where they take the
    core, ``impl=xla reason=...`` where they do not; one line a shape."""
    if on_tpu:
        request.getfixturevalue("as_on_a_tpu")()
    else:
        request.getfixturevalue("monkeypatch").setattr(kda, "_logged", set())
    (q, k, v, g, beta), _ = _inputs(3, B=1, H=1, **shape)
    want = kda.kda_recurrent(q, k, v, g, beta)
    for _ in range(2):
        out = kda.kda_chunked(q, k, v, g, beta, chunk=32, **form)
        assert float(jnp.max(jnp.abs(out - want))) < 2e-6
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("[kda]")]
    assert len(lines) == 1, lines
    assert f"S={shape['S']} " in lines[0] and "state_dtype=float32" in lines[0]
    gate = " gate=unbounded" if form else ""
    if reason is None:
        assert (f"chunk={kda.KERNEL_CHUNK} " in lines[0]
                and lines[0].endswith(
                    f"impl=pallas tile=128 chunks_per_step="
                    f"{128 // kda.KERNEL_CHUNK} heads_per_step=1{gate}")), \
            lines
        assert kda.chunk_in_use(shape["S"], 128, 128) == kda.KERNEL_CHUNK
    else:
        assert lines[0].endswith(f"chunk=32 chunks={shape['S'] // 32} "
                                 f"heads=1 d_k={q.shape[-1]} "
                                 f"d_v={v.shape[-1]} state_dtype=float32 "
                                 f"impl=xla reason={reason}{gate}"), lines
        assert kda.chunk_in_use(shape["S"], q.shape[-1], v.shape[-1]) == 32


def test_the_kernels_run_a_device_on_its_own_block_under_a_mesh(
        devices8, as_on_a_tpu):
    """GSPMD cannot partition a Mosaic call, and batch and heads are
    independent: under a sharded mesh the pair runs in a manual region over
    the batch and tensor axes (as the flash kernel does); with the sequence
    sharded it leaves the core to the scan."""
    from pytorch_distributed_train_tpu.config import MeshConfig
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh

    as_on_a_tpu()
    cp = attention.ContextParallelConfig(
        mesh=build_mesh(MeshConfig(data=2, tensor=2), devices8[:4]))
    args, w = _inputs(11, B=2, S=128, H=2, **KERNEL)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * w)  # noqa: E731
    run = lambda *a: kda.kda_chunked(*a, cp=cp)  # noqa: E731
    assert "shard_map" in str(jax.make_jaxpr(run)(*args))
    with cp.mesh:
        got = jax.jit(jax.value_and_grad(loss(run), argnums=(0, 3)))(*args)
    want = jax.value_and_grad(loss(kda.kda_recurrent), argnums=(0, 3))(*args)
    assert abs(float(got[0] - want[0])) < 1e-4 * abs(float(want[0]))
    for a, b in zip(got[1], want[1]):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5 * float(jnp.max(jnp.abs(b)))
    ring = attention.ContextParallelConfig(
        mesh=build_mesh(MeshConfig(data=2, context=2), devices8[:4]))
    assert kda.unsupported(128, 128, 128, jnp.bfloat16, ring) \
        == "mesh: the sequence is sharded"
