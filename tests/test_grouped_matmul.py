"""ops/grouped_matmul.py in interpret mode on the CPU, against
``jax.lax.ragged_dot``: the four expert cells' matrices at cut row counts
under the kernels' own tile rule, values and both gradients, over routings
that put a group's boundary on and off a tile's edge, leave groups empty,
give one group every row, fill the row bound and leave most of it to no
group; the grid's table against a count by hand; and the contract through
``ops/moe.py`` ``_grouped_bank`` itself: NaN in every row past the groups,
of the rows and of the incoming cotangent, reaches nothing (PR 26's stale
rows: gradients 36 000 times too large on a v5e), with two ``where``s over
the rows where the bank made six."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_train_tpu.ops import grouped_matmul as gm
from pytorch_distributed_train_tpu.ops import moe

BF16, F32 = jnp.bfloat16, jnp.float32
R, G = 640, 4
# a cell's (d_model, mlp_dim) and the pairs a held expert has on average
# there (the row tile follows it: 256, 128, 128, 128)
CELLS = {
    "lfm2moe": (2048, 1792, 2048),
    "kanana2": (2048, 768, 768),
    "lagunas": (3072, 1024, 320),
    "ling3f": (2560, 768, 256),
}
# with the head-share cell, whose experts are too wide for the interpreter
# at every routing: its own test below, at half its widths
EXPERT_CELLS = {**CELLS, "solar2": (4096, 1280, 204)}
ROUTINGS = {
    "on_tile_edges": [128, 128, 128, 128],  # and a fifth of the bound free
    "off_tile_edges": [100, 60, 200, 90],
    "skewed": [5, 400, 3, 40],
    "empty_groups": [0, 300, 0, 100],
    "one_group_holds_every_row": [0, 640, 0, 0],
    "the_bound_is_full": [130, 250, 60, 200],
    "no_rows_at_all": [0, 0, 0, 0],
    "the_last_group_alone": [0, 0, 0, 77],
}


def _operands(K, N, seed=0, rows=R, groups=G):
    kx, kw, kc = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (rows, K), BF16)
    w = (0.05 * jax.random.normal(kw, (groups, K, N))).astype(BF16)
    # a cotangent the kernels' bfloat16 rounding leaves as it is
    ct = jax.random.normal(kc, (rows, N), BF16).astype(F32)
    return x, w, ct


@functools.partial(jax.jit, static_argnums=(4, 5))
def _both(x, w, ct, sizes, mean_rows, kernel):
    """(out, d rows, d weights), the rows of no group zeroed in the two
    row-shaped results. By the kernels, which get NaN in every row of no
    group, of the rows and of the cotangent, and must not read one; or by
    ``ragged_dot`` with those rows zeroed on both sides."""
    in_group = (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]
    held = lambda a: jnp.where(in_group, a, 0)  # noqa: E731
    if kernel:
        dirty = lambda a: jnp.where(in_group, a, jnp.nan)  # noqa: E731
        out, vjp = jax.vjp(lambda x, w: gm.grouped_matmul(
            x, w, sizes, mean_rows=mean_rows, interpret=True), dirty(x), w)
        d_rows, d_weights = vjp(dirty(ct))
        return held(out), held(d_rows), d_weights
    out, vjp = jax.vjp(lambda x, w: held(jax.lax.ragged_dot(
        held(x), w, sizes, preferred_element_type=F32)), x, w)
    return (out, *vjp(ct))


def _close(got, want, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all(), what
    # bfloat16 results of float32 sums taken in another order
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2.0 ** -6 * max(np.abs(want).max(), 1e-3),
                               err_msg=what)


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("cell", CELLS)
def test_values_and_both_gradients_match_ragged_dot(cell, routing):
    D, F, mean_rows = CELLS[cell]
    sizes = jnp.asarray(ROUTINGS[routing], jnp.int32)
    # gate / up on the first routings, down's transposed shape on the rest
    K, N = (D, F) if list(ROUTINGS).index(routing) % 2 == 0 else (F, D)
    x, w, ct = _operands(K, N)
    got = _both(x, w, ct, sizes, mean_rows, True)
    want = _both(x, w, ct, sizes, mean_rows, False)
    for g, r, what in zip(got, want, ("out", "d rows", "d weights")):
        assert g.shape == r.shape and g.dtype == r.dtype, what
        _close(g, r, f"{what} of {K}x{N} under {routing}")
    empty = np.asarray(sizes) == 0
    assert not np.asarray(got[2], np.float32)[empty].any()


# The head-share cell's geometry at half its widths and a quarter of its row
# bound: 4096 x 1280 is K : N = 16 : 5 in whole tiles of 128, eight held
# experts of ~200 pairs a layer (one with none, one past a quarter of the
# rows), a bound that is no whole row tile.
WIDE_ROWS, WIDE_SIZES = 1640, [210, 0, 190, 420, 150, 230, 180, 205]


@pytest.fixture(scope="module")
def wide():
    """{by the kernels?: for gate / up's orientation and for down's, (out,
    d rows, d weights)}."""
    assert max(WIDE_SIZES) > WIDE_ROWS // 4
    sizes = jnp.asarray(WIDE_SIZES, jnp.int32)
    assert gm.tile_sizes(2048, 640, 204).m == 128
    return {kernel: [_both(*_operands(K, N, rows=WIDE_ROWS, groups=8), sizes,
                           204, kernel)
                     for K, N in ((2048, 640), (640, 2048))]
            for kernel in (True, False)}


@pytest.mark.parametrize("what", ["out", "d_rows", "d_weights"])
def test_the_head_share_cells_geometry_matches_ragged_dot(wide, what):
    at = ("out", "d_rows", "d_weights").index(what)
    for got, want, shape in zip(wide[True], wide[False], ("up", "down")):
        assert got[at].shape == want[at].shape \
            and got[at].dtype == want[at].dtype
        _close(got[at], want[at], f"{what} of {shape} at the wide geometry")
        if what == "d_weights":  # the expert of no pairs: zeros, written
            assert not np.asarray(got[at], np.float32)[1].any()


@pytest.mark.parametrize("m", [16, 128, 512])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_the_grids_table_visits_each_tile_of_each_group_once(routing, m):
    sizes = np.asarray(ROUTINGS[routing])
    ends = np.cumsum(sizes)
    want = []  # (tile, group), groups in order, a group's tiles in order
    for g, (size, end) in enumerate(zip(sizes, ends)):
        if size:
            want += [(t, g) for t in range((end - size) // m,
                                           (end - 1) // m + 1)]
        else:  # one step of its own, where its rows would start
            want.append((min(end // m, -(-R // m) - 1), g))
    bounds, group, tile, steps = gm.group_steps(
        jnp.asarray(sizes, jnp.int32), R, m)
    assert group.shape == tile.shape == (-(-R // m) + G - 1,)
    assert int(steps) == len(want) <= group.shape[0]
    got = list(zip(np.asarray(tile)[:len(want)].tolist(),
                   np.asarray(group)[:len(want)].tolist()))
    assert got == want
    assert np.asarray(bounds).tolist() == [0, *ends.tolist()]
    # never a step back: a result block is revisited by neighbours only
    assert (np.diff(np.asarray(tile)[:len(want)]) >= 0).all()
    ratio = float(gm.tile_visits_ratio(jnp.asarray(sizes, jnp.int32), R, m))
    assert ratio == pytest.approx(len(want) / max(-(-int(ends[-1]) // m), 1))


def test_the_tile_rule_at_the_cells_shapes_and_what_it_refuses():
    for D, F, mean_rows in EXPERT_CELLS.values():
        for K, N in ((D, F), (F, D)):
            t = gm.tile_sizes(K, N, mean_rows)
            assert N % t.n == K % t.back_n == K % t.wk == N % t.wn == 0
            assert not any(s % 128 for s in t)
    assert gm.unsupported(2048, 1792) == "the backend is not a TPU"
    x, w, _ = _operands(256, 128)
    with pytest.raises(ValueError, match="do not fit"):
        gm.grouped_matmul(x, w, jnp.zeros((G,), jnp.int32), mean_rows=64,
                          tiles=gm.Tiles(128, 96, 128, 128, 128))


# The rule at every expert cell's shape, for gate / up and for down, pinned
# whole: a cell's program is its tiles, so a new arm of the rule may move
# only shapes no cell has (a group's whole matrix is a block wherever it has
# 4 Mi elements or fewer).
TILES_AT_THE_CELLS = {
    "lfm2moe": (gm.Tiles(256, 1792, 2048, 2048, 1792),
                gm.Tiles(256, 2048, 1792, 1792, 2048)),
    "kanana2": (gm.Tiles(128, 768, 2048, 2048, 768),
                gm.Tiles(128, 2048, 768, 768, 2048)),
    "lagunas": (gm.Tiles(128, 1024, 3072, 3072, 1024),
                gm.Tiles(128, 3072, 1024, 1024, 1536)),
    "ling3f": (gm.Tiles(128, 768, 2560, 2560, 768),
               gm.Tiles(128, 2560, 768, 768, 1280)),
    "solar2": (gm.Tiles(128, 640, 2048, 2048, 1280),
               gm.Tiles(128, 2048, 640, 1280, 2048)),
}


@pytest.mark.parametrize("cell", TILES_AT_THE_CELLS)
def test_every_width_takes_the_kernels_under_the_tiles_it_had(monkeypatch,
                                                              cell):
    from pytorch_distributed_train_tpu.ops import attention

    D, F, mean_rows = EXPERT_CELLS[cell]
    up, down = TILES_AT_THE_CELLS[cell]
    assert gm.tile_sizes(D, F, mean_rows) == up
    assert gm.tile_sizes(F, D, mean_rows) == down
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert gm.unsupported(D, F) is None and gm.unsupported(F, D) is None


# ------------------------------------------------- through the bank itself

def _bank(monkeypatch, D, F, mean_rows):
    """``_grouped_bank`` as on a TPU, its kernels in the interpreter."""
    monkeypatch.setattr(gm, "unsupported", lambda K, N: None)
    kw = jax.random.split(jax.random.PRNGKey(7), 3)
    weights = [(0.05 * jax.random.normal(k, s)).astype(BF16) for k, s in
               zip(kw, ((G, D, F), (G, D, F), (G, F, D)))]

    def bank(rows, weights, sizes):
        return moe._grouped_bank(rows, sizes, [lambda w=w: w
                                               for w in weights], BF16,
                                 mean_rows)

    return bank, weights


@pytest.mark.parametrize("routing", ["off_tile_edges", "skewed",
                                     "empty_groups", "no_rows_at_all"])
def test_nan_in_the_rows_of_no_group_reaches_nothing(monkeypatch, routing):
    D, F, mean_rows = 256, 384, 100
    bank, weights = _bank(monkeypatch, D, F, mean_rows)
    sizes = jnp.asarray(ROUTINGS[routing], jnp.int32)
    past = (jnp.arange(R) >= jnp.sum(sizes))[:, None]
    rows, _, _ = _operands(D, F, seed=3)
    ct = jax.random.normal(jax.random.PRNGKey(4), (R, D), F32)

    def run(fill):
        out, vjp = jax.vjp(lambda r, w: bank(r, w, sizes),
                           jnp.where(past, fill, rows).astype(BF16), weights)
        return (out, *vjp(jnp.where(past, fill, ct)))

    dirty, clean = run(jnp.nan), run(0.0)
    for got, want in zip(jax.tree.leaves(dirty), jax.tree.leaves(clean)):
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, np.asarray(want, np.float32))
    out, d_rows = np.asarray(dirty[0]), np.asarray(dirty[1], np.float32)
    assert not out[np.asarray(past)[:, 0]].any()
    assert not d_rows[np.asarray(past)[:, 0]].any()
    if int(jnp.sum(sizes)):
        assert np.abs(out).max() > 0 and np.abs(d_rows).max() > 0


def _wheres_over_the_rows(jaxpr) -> int:
    return sum(1 for eqn in jaxpr.eqns if eqn.primitive.name == "select_n"
               and eqn.outvars[0].aval.shape[:1] == (R,)) + sum(
        _wheres_over_the_rows(sub.jaxpr if hasattr(sub, "jaxpr") else sub)
        for eqn in jaxpr.eqns if eqn.primitive.name in ("pjit", "jit")
        for sub in [eqn.params["jaxpr"]])


@pytest.mark.parametrize("path,wheres", [("kernel", 2), ("ragged_dot", 5)])
def test_the_bank_zeroes_the_rows_once_in_and_once_out(monkeypatch, path,
                                                       wheres):
    """In the forward pass's jaxpr: two ``where``s over (R, .) round the
    kernels; round ``ragged_dot``, where no TPU is, PR 26's zeroing on both
    sides of every product (five: it wrote the first of them twice)."""
    D, F, mean_rows = 256, 384, 100
    bank, weights = _bank(monkeypatch, D, F, mean_rows)
    if path == "ragged_dot":
        monkeypatch.undo()
    rows, _, _ = _operands(D, F)
    sizes = jnp.asarray(ROUTINGS["skewed"], jnp.int32)
    jaxpr = jax.make_jaxpr(bank)(rows, weights, sizes)
    text = str(jaxpr)
    assert ("ragged_dot" in text) == (path == "ragged_dot")
    assert ("grouped_matmul_rows" in text) == (path == "kernel")
    assert _wheres_over_the_rows(jaxpr.jaxpr) == wheres
