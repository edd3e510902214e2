"""The Pallas kernels of the main path compile for a TPU v5e at real
widths — asked of the chip's own compiler, for a DESCRIBED v5e:2x2 with
no chip attached (on-chip-measurement guide, section 2, rehearsal 3).

What this guards: Mosaic refusals interpret mode cannot see (VMEM
budgets, the fused backward's raised limit with a KV head's dK and dV
resident, lane padding of D=64, block-shape alignment, the 4-axis
backward grid, (S, 1) i32 position refs, the per-offset specialisations
and their static slices of the resident block) under the kernel's own
tile rule at GPT-2 small's attention shape — the shape the benchmark's
cells train — and at the S 2048 / D 128 causal, GQA and window shapes and
every other cell's own, plus the ring chunk kernel on a 4-device mesh
and the W8/W4 GEMV kernels; that the one-chip GPT-2 step keeps its 24
kernel calls (a layer's forward and its one fused backward) under the
names the benchmark finds them by; and that the four-chip
data-parallel GPT-2 step all-reduces its tied table once. A compile that passes here
is a compile, not a chip run.

Rules this file keeps (only one process at a time may load the TPU
library, and the suite runs under several xdist workers): the topology
is described inside a module-scoped fixture that skips when it cannot
be — nothing at import, not autouse, not in conftest.py; every compile
runs in this test's own process; all such tests live in this one file;
the persistent compile cache stays off around them.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from pytorch_distributed_train_tpu.ops import flash_attention as fa

LOOP_FLASH_KERNELS = 2  # the looped step's, at depth 1: see its test


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep the cache off here.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


def _custom_calls(text):
    """Trace names of a compiled program's custom calls, as the benchmark's
    trace reader sees them: ``%attn.12 custom-call``."""
    return [f"{m.group(1)} custom-call" for m in re.finditer(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = [^\n]*? custom-call\(", text, re.M)]


def _assert_head_rides_its_products(text, pattern, n_flash, rows, vocab):
    """What a compiled GPT-2 training step holds since the loss's passes
    ride the head's products (ops/lm_head_loss.py): the flash kernels under
    the names the benchmark's pattern finds, the head's two kernels under
    names it does NOT match (a Pallas call takes its enclosing scope's
    name; counted into `flash_attn_ms_per_step` they would halve
    `flash_attn_roofline`), and no elementwise pass over an array of the
    logits' shape."""
    names = _custom_calls(text)
    kernels = [n for n in names if re.search(pattern, n)]
    assert len(kernels) == n_flash, kernels
    head = [n for n in names if "lm_head" in n]
    assert len(head) == 2 and not set(head) & set(kernels), head
    assert any("lm_head_fwd" in n for n in head), head
    assert any("lm_head_bwd" in n for n in head), head
    logits = re.compile(
        rf"\[(\d+,\d+,{vocab}|{rows},{vocab}|{vocab},{rows})\]")
    loops = [ln.strip()[:160] for ln in text.splitlines()
             if "kind=kLoop" in ln and logits.search(ln)
             # a product's operand re-described inside its fusion: no pass
             and "calls=%bitcast_fusion" not in ln]
    assert not loops, loops


def _attn_loss(q, k, v, **kw):
    return fa.flash_attention(q, k, v, **kw).astype(jnp.float32).sum()


# (id, B, S, H, Hkv, D, window). gpt2_small is the preset's attention
# shape at the benchmark cells' batch a chip; the rest are the
# long-sequence shapes (llama-style heads of 128, GQA 4:1, Mistral-style
# window) and the window/full cell's own.
FLASH_SHAPES = [
    ("gpt2_small", 16, 1024, 12, 12, 64, 0),
    ("s2048_d128", 2, 2048, 8, 8, 128, 0),
    ("s2048_d128_gqa", 2, 2048, 8, 2, 128, 0),
    ("s2048_d128_window512", 2, 2048, 8, 8, 128, 512),
    # the window/full attention cell's two kinds (preset laguna_s_lm_ep32):
    # 72 query heads over 8 KV heads inside a window of 512, 48 over 8 causal
    ("s8192_d128_gqa72_window512", 1, 8192, 72, 8, 128, 512),
    ("s8192_d128_gqa48", 1, 8192, 48, 8, 128, 0),
    # the looped cell's (preset ouro_2_6b_lm_l8) and the head-share cell's
    # one NoPE layer at an eighth of its heads (solar_open2_lm_ep40_tp8)
    ("s4096_d128_mha16", 1, 4096, 16, 16, 128, 0),
    ("s8192_d128_gqa8_over_1", 1, 8192, 8, 1, 128, 0),
    # the short-convolution cell's one attention layer (preset
    # lfm2_8b_a1b_lm_ep4): 32 query heads over 8 KV heads of 64, two rows
    ("s8192_d64_gqa32_over_8_b2", 2, 8192, 32, 8, 64, 0),
    # the 16k window/full cell's two kinds (preset mellum2_12b_a2_5b_lm_ep4):
    # 32 query heads over 4 KV heads of 128 at 16384 keys, where the
    # backward is the SPLIT pair (`backward_plan`: 41.9 MB would be resident)
    ("s16384_d128_gqa32_over_4_window1024", 1, 16384, 32, 4, 128, 1024),
    ("s16384_d128_gqa32_over_4", 1, 16384, 32, 4, 128, 0),
]


@pytest.mark.parametrize("direction", ["fwd", "fwd_bwd"])
@pytest.mark.parametrize("name,B,S,H,Hkv,D,window", FLASH_SHAPES,
                         ids=[s[0] for s in FLASH_SHAPES])
def test_flash_attention_compiles(one_chip, name, B, S, H, Hkv, D, window,
                                  direction):
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16,
                              sharding=one_chip)
    # the dispatch's own gates must agree these shapes take the kernel
    assert fa.supported(q, kv, kv, causal=True, mask=None, window=window)
    assert fa.profitable(q)
    loss = functools.partial(_attn_loss, causal=True, window=window)
    fn = loss if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    _compile(fn, q, kv, kv)


@pytest.mark.parametrize("direction", ["fwd", "fwd_bwd"])
def test_flash_attention_compiles_at_latent_attentions_head_dims(one_chip,
                                                                 direction):
    """The hybrid cell's one softmax layer (benchmark cell
    ling3f-1chip-ep64-s8k): 192-deep scores (128 plain + 64 rotated dims,
    blocks whose last dim is one and a half lane widths) beside 128-deep
    values, at (2, 8192, 32): V and O block specs carry V's own head
    dim. Eight major blocks of 1024 a Q tile (`_MAJOR_BYTES` at D 192), so
    K's and V's index maps are the clamped ones, forward and fused
    backward: 72 of a head's 128 steps enter a tile, 70 blocks fetched."""
    sds = lambda d: jax.ShapeDtypeStruct(  # noqa: E731
        (2, 8192, 32, d), jnp.bfloat16, sharding=one_chip)
    q, k, v = sds(192), sds(192), sds(128)
    assert fa.supported(q, k, v, causal=True, mask=None)
    assert fa.call_plan(q, k, causal=True) == fa.TilePlan(256, 136, 16)
    assert fa.call_fetch_plan(q, k, causal=True) == fa.FetchPlan(128, 72, 70)
    loss = functools.partial(_attn_loss, causal=True)
    fn = loss if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    out = jax.eval_shape(functools.partial(fa.flash_attention, causal=True),
                         q, k, v)
    assert out.shape == v.shape
    _compile(fn, q, k, v)


def test_kda_core_compiles_at_the_hybrid_cells_shape(one_chip):
    """ops/kda.py forward and backward at (2, 8192, 32, 128), the default chunk:
    plain XLA (no Mosaic kernel), inside the memory the cell leaves it."""
    from pytorch_distributed_train_tpu.ops import kda

    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    x = sds(2, 8192, 32, 128)
    g, beta = sds(2, 8192, 32, 128, dtype=jnp.float32), \
        sds(2, 8192, 32, dtype=jnp.float32)
    loss = lambda *a: kda.kda_chunked(*a).astype(jnp.float32).sum()  # noqa: E731
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, x, x, g, beta).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def test_kda_kernel_pair_compiles_at_the_hybrid_cells_shape(one_chip,
                                                            monkeypatch):
    """The same entry where the dispatch sees a TPU: ops/kda_kernel.py's
    pair, forward and backward, one Mosaic call each under its own name,
    no scan left, and the saved states (one float32 state a tile and head)
    inside the memory the cell leaves."""
    from pytorch_distributed_train_tpu.ops import attention as attention_lib
    from pytorch_distributed_train_tpu.ops import kda

    monkeypatch.setattr(attention_lib, "_on_tpu", lambda: True)
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    x = sds(2, 8192, 32, 128)
    g, beta = sds(2, 8192, 32, 128, dtype=jnp.float32), \
        sds(2, 8192, 32, dtype=jnp.float32)
    loss = lambda *a: kda.kda_chunked(*a).astype(jnp.float32).sum()  # noqa: E731
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                        x, x, x, g, beta)
    names = _custom_calls(compiled.as_text())
    assert sorted(n.split(".")[0] for n in names if "kda" in n) == \
        ["%kda_bwd", "%kda_fwd"], names
    assert " while(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


GROUPED_SHAPES = [  # rows of the bound, held experts, D, F, mean rows
    ("lfm2moe", 32768, 8, 2048, 1792, 2048),
    ("kanana2", 49152, 16, 2048, 768, 768),
    ("lagunas", 10240, 8, 3072, 1024, 320),
    ("ling3f", 8192, 8, 2560, 768, 256),
    # the head-share cell: a row bound that is no whole row tile
    ("solar2", 6560, 8, 4096, 1280, 204),
    # the 16k cell: two held rows a token, the narrowest expert (7 x 128)
    ("mellum2", 40960, 16, 2304, 896, 2048),
]


@pytest.mark.parametrize("name,R,G,D,F,mean_rows", GROUPED_SHAPES,
                         ids=[s[0] for s in GROUPED_SHAPES])
def test_grouped_matmul_kernels_compile_at_the_expert_cells_shapes(
        one_chip, name, R, G, D, F, mean_rows):
    """ops/grouped_matmul.py under its own tile rule, forward and both
    gradients, for gate / up's matrices and for down's: the row kernel
    twice (the forward, ``d rows`` against the weights read transposed) and
    the weights kernel once, each under its own name, the grid's steps a
    number the program computes (a dynamic grid axis)."""
    from pytorch_distributed_train_tpu.ops import grouped_matmul as gm

    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    loss = lambda x, w, sizes: jnp.square(gm.grouped_matmul(  # noqa: E731
        x, w, sizes, mean_rows=mean_rows)).sum()
    for K, N in ((D, F), (F, D)):
        compiled = _compile(jax.grad(loss, argnums=(0, 1)), sds(R, K),
                            sds(G, K, N), sds(G, dtype=jnp.int32))
        names = [n for n in _custom_calls(compiled.as_text())
                 if "grouped" in n]
        assert sorted("rows" in n for n in names) == [False, True, True] \
            and all("grouped_matmul_" in n for n in names), names
        # the float32 result, its cotangent's bfloat16 twin at most, and
        # nothing else of the bound's size
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 1.2 * (4 + 2) * R * N + (64 << 20)


def test_kda_mixer_in_its_kernels_moves_no_tensor_round_them(one_chip,
                                                             monkeypatch):
    """One KDA mixer at the hybrid cell's widths with its gradient, under
    ``jax.checkpoint`` as ``model.remat`` runs it: the shaping's pair
    (ops/kda_inputs.py) twice forward and once backward beside the core's,
    the four projections' results row-major (a product onto (H d): onto
    (H, d) the compiler puts the sequence on the lanes), and so no layout
    copy under the shaping's scope, in front of the pair or behind it."""
    from pytorch_distributed_train_tpu.models import hybrid
    from pytorch_distributed_train_tpu.ops import attention as attention_lib

    monkeypatch.setattr(attention_lib, "_on_tpu", lambda: True)
    B, S, D = 2, 8192, 2560
    mixer = hybrid.KDAMixer(32, 128, 4, -5.0, 1e-6, jnp.bfloat16,
                            jnp.float32)
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip),
        tree)
    x = jax.ShapeDtypeStruct((B, S, D), jnp.bfloat16)
    params = jax.eval_shape(lambda: mixer.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros(x.shape, x.dtype)))
    layer = jax.checkpoint(lambda p, x: mixer.apply(p, x)[0])
    loss = lambda p, x: jnp.sum(layer(p, x).astype(jnp.float32) ** 2)  # noqa: E731
    text = _compile(jax.grad(loss, argnums=(0, 1)), described(params),
                    described(x)).as_text()
    names = [n.split(".")[0] for n in _custom_calls(text) if "kda" in n]
    assert sorted(names) == ["%kda_bwd", "%kda_fwd", "%kda_fwd",
                             "%kda_inputs_bwd", "%kda_inputs_fwd",
                             "%kda_inputs_fwd"], names
    moved = [ln.strip()[:200] for ln in text.splitlines()
             if re.search(r"= \S+ (copy|transpose)\(", ln)
             and re.search(r"/kda_inputs/", ln)]
    assert not moved, moved
    minor = [ln.strip()[:200] for ln in text.splitlines()
             if re.search(r"_proj/dot_general", ln)
             and re.search(r"= \w+\[2,8192,32,128\]\{1,3,2,0", ln)
             and re.search(r"/[qkva]_proj/", ln)]
    assert not minor, minor


@pytest.mark.parametrize("direction", ["fwd", "fwd_bwd"])
@pytest.mark.parametrize("D,Hkv", [(64, 12), (128, 4)],
                         ids=["d64_mha", "d128_gqa"])
def test_ring_chunk_kernel_compiles(one_chip, D, Hkv, direction):
    """The ring's inner kernel: traced global positions as (S, 1) i32
    refs, GQA unexpanded, fp32 (o, lse) out."""
    B, S, H = 2, 512, 12

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, kv = sds((B, S, H, D)), sds((B, S, Hkv, D))
    pos = sds((S,), jnp.int32)
    assert fa.chunk_supported(q, kv, kv)

    def loss(q_, k_, v_, qp, kp):
        o, lse = fa.flash_attention_chunk(q_, k_, v_, qp, kp, causal=True)
        return o.sum() + jnp.where(lse > fa.NEG_INF / 2, lse, 0.0).sum()

    fn = loss if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    _compile(fn, q, kv, kv, pos, pos)


@pytest.mark.parametrize("direction", ["fwd", "fwd_bwd"])
def test_ring_attention_compiles_on_four_chips(topo, direction):
    """Mosaic INSIDE shard_map with ppermute over the described 4-device
    mesh. ring_attention_local is called with interpret=False: the public
    wrapper keys interpret on the RUNTIME backend, which is the CPU here,
    and the point is the TPU lowering."""
    from pytorch_distributed_train_tpu.ops.ring_attention import (
        ring_attention_local,
    )
    from pytorch_distributed_train_tpu.utils.compat import shard_map

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("context",))
    spec = P(None, "context", None, None)
    sharding = NamedSharding(mesh, spec)
    B, S, H, Hkv, D = 1, 4096, 8, 2, 128

    def ring(q, k, v):
        body = functools.partial(
            ring_attention_local, axis_name="context", axis_size=4,
            causal=True, chunk_impl="pallas", interpret=False)
        out = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)(q, k, v)
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16,
                              sharding=sharding)
    fn = ring if direction == "fwd" else jax.grad(ring, argnums=(0, 1, 2))
    text = _compile(fn, q, kv, kv).as_text()
    assert "collective-permute" in text


def _lowered_step(config, one_chip, monkeypatch, overrides=()):
    """A benchmark configuration's one-chip training step, lowered for the
    described chip, with the configuration's file and the run's config."""
    import json

    from pytorch_distributed_train_tpu import losses as losses_lib
    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.ops import attention as attention_lib
    from pytorch_distributed_train_tpu.optim import make_optimizer
    from pytorch_distributed_train_tpu.train_state import TrainState

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", f"{config}.json"),
              encoding="utf-8") as f:
        bench = json.load(f)
    # the dispatch asks the RUNTIME backend (the CPU here): steer it in
    # the test, as the described-chip guide says, not through an option
    monkeypatch.setattr(attention_lib, "_on_tpu", lambda: True)
    cfg = get_preset(bench["preset"])
    cfg.apply_overrides(list(bench["overrides"]) + list(overrides))
    model = build_model(cfg.model, cfg.precision)
    tx, _ = make_optimizer(cfg.optim, cfg.total_steps, 0)
    dummy = steps_lib.dummy_inputs(cfg.loss, cfg.model, cfg.data)

    def init(rng):
        params = model.init({"params": rng}, *dummy, train=False)["params"]
        return TrainState.create(params=params, tx=tx, batch_stats={},
                                 dynamic_scale=None, ema=False, swa=False)

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    step = steps_lib.make_train_step(
        model, losses_lib.get_loss_fn(cfg.loss,
                                      label_smoothing=cfg.label_smoothing),
        tx)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (cfg.data.batch_size, cfg.data.seq_len), jnp.int32,
        sharding=one_chip)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    lowered = jax.jit(step, donate_argnums=(0,)).lower(
        described(jax.eval_shape(init, jax.random.PRNGKey(0))), batch, rng)
    return lowered, bench, cfg


def test_gpt2_step_keeps_its_24_flash_kernels_by_name(one_chip, monkeypatch):
    """The one-chip GPT-2 small step, lowered and compiled for the
    described chip at the benchmark cell's batch: 12 layers x (forward,
    the fused backward: dQ with dK/dV) = 24 instructions (36 while the
    backward was two kernels) whose trace names (`%attn.N custom-call`)
    match the configuration's `flash_kernel_pattern`. The benchmark's
    `flash_attn_ms_per_step` finds the kernel by that name alone, so a
    `name=` on a `pallas_call` or a renamed module would zero the metric.
    The cell's own settings (loss causal_lm_xent, no option) land on the
    head's kernels: `_assert_head_rides_its_products`."""
    lowered, bench, cfg = _lowered_step(
        "gpt2_small", one_chip, monkeypatch, ["data.batch_size=16"])
    assert 2 * bench["n_layer"] == 24
    _assert_head_rides_its_products(
        lowered.compile().as_text(), bench["flash_kernel_pattern"], 24,
        cfg.data.batch_size * cfg.data.seq_len, cfg.model.vocab_size)


def test_hybrid_step_holds_the_kda_kernels_by_name(one_chip, monkeypatch):
    """The hybrid cell's step at 2 x 8192 tokens, lowered (its compile is
    two minutes here): five KDA layers, each the forward kernel twice
    (`model.remat` runs a block's forward again) and the backward kernel
    once, by the names their device operations carry (`%kda_fwd.N
    custom-call`, which the configuration's `flash_kernel_pattern` must
    NOT match: counted into `flash_attn_ms_per_step` they would move
    `mla_attn_roofline`), and no `while` loop left under the core's
    scope. The MLA layer's flash kernel runs its forward ONCE beside its
    one fused backward (dK and dV of a head's 8192 keys at 192 + 128 in
    VMEM, under the kernel's own raised limit): remat keeps what the
    forward handed back (models/remat.py), and neither kernel of the
    two-kernel backward is lowered."""
    lowered, bench, _ = _lowered_step(
        "ling3_flash_lm_ep64", one_chip, monkeypatch,
        ["data.batch_size=2", "data.seq_len=8192"])
    text = lowered.as_text(debug_info=True)
    kernels = re.findall(r'kernel_name = "(\w+)"', text)
    assert (kernels.count("kda_fwd"), kernels.count("kda_bwd")) == (10, 5), \
        kernels
    # and the shaping's pair in front of each (ops/kda_inputs.py)
    assert (kernels.count("kda_inputs_fwd"),
            kernels.count("kda_inputs_bwd")) == (10, 5), kernels
    flash = [kernels.count(k) for k in
             ("_fwd_kernel", "_bwd_fused_kernel", "_bwd_dq_kernel",
              "_bwd_dkv_kernel")]
    assert flash == [1, 1, 0, 0], kernels
    for name in ("kda_fwd", "kda_bwd", "kda_inputs_fwd", "kda_inputs_bwd"):
        assert not re.search(bench["flash_kernel_pattern"],
                             f"%{name}.1 custom-call")
    loops = sorted(set(re.findall(r'"[^"]*kda_chunk[^"]*/while"', text)))
    assert not loops, loops


def test_short_convolution_step_holds_its_grouped_kernels_by_name(
        one_chip, monkeypatch):
    """The short-convolution cell's step at 2 x 8192 tokens, lowered: four
    expert layers, each the row kernel of ops/grouped_matmul.py three times
    forward, three in the forward ``model.remat`` runs again and three for
    ``d rows``, and the weights kernel three times, by the names their
    device operations carry; no ``ragged_dot`` left; every kernel's
    ``op_name`` under the scope `moe_grouped_ms_per_step` sums."""
    lowered, bench, _ = _lowered_step(
        "lfm2_8b_a1b_lm_ep4", one_chip, monkeypatch,
        ["data.batch_size=2", "data.seq_len=8192"])
    text = lowered.as_text(debug_info=True)
    kernels = re.findall(r'kernel_name = "(\w+)"', text)
    assert (kernels.count("grouped_matmul_rows"),
            kernels.count("grouped_matmul_weights")) == (36, 12), kernels
    assert "ragged_dot" not in text
    for name in ("grouped_matmul_rows", "grouped_matmul_weights"):
        assert not re.search(bench["flash_kernel_pattern"],
                             f"%{name}.1 custom-call")
    scoped = re.findall(
        r'"([^"]*/grouped_matmul_(?:rows|weights)/pallas_call)"', text)
    assert scoped and all("/moe/experts/grouped_product/" in s
                          for s in scoped), scoped[:4]


def test_head_share_step_compiles_inside_the_programs_memory(one_chip,
                                                             monkeypatch):
    """The head-share cell's step at 1 x 8192 tokens, compiled: its expert
    bank is the grouped kernels (nine a layer and pass, as every expert
    cell's) and the step holds no ``conditional`` (a second form of the
    bank under a ``cond`` kept both branches' residuals, 3 GiB of a
    program that may use 15.75): 11.98 GiB, 9.40 of arguments and 2.59 of
    temporaries (sandbox compile, PR 47)."""
    lowered, _, _ = _lowered_step(
        "solar_open2_lm_ep40_tp8", one_chip, monkeypatch,
        ["data.batch_size=1", "data.seq_len=8192"])
    compiled = lowered.compile()
    text = compiled.as_text()
    names = _custom_calls(text)
    assert (sum("grouped_matmul_rows" in n for n in names),
            sum("grouped_matmul_weights" in n for n in names)) == (36, 12)
    assert " conditional(" not in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes + memory.argument_size_in_bytes \
        < 12.3 * 2 ** 30


def test_16k_step_holds_the_split_pair_under_the_kinds_names(one_chip,
                                                             monkeypatch):
    """The 16k window/full preset's step at the cell's shape (1 x 16384,
    four layers), compiled for the described chip: the flash kernel's
    forward AND both kernels of its split backward (dQ; dK/dV) carry their
    module's name, three events a layer, so `swa_kernel_pattern` finds the
    three window layers' nine and `gqa_kernel_pattern` the full layer's
    three (a roofline that missed one kernel of the pair would divide by
    too little time); the bank's grouped kernels twelve a layer (the row
    kernel nine times with the forward run again, the weights kernel
    three); and the
    program fits the chip with room for what the trainer holds beside it
    (10.93 GiB of 15.75; sandbox compile, PR 48)."""
    lowered, bench, cfg = _lowered_step(
        "mellum2_12b_a2_5b_lm_ep4", one_chip, monkeypatch)
    assert (cfg.data.batch_size, cfg.data.seq_len) == (1, 16384)
    compiled = lowered.compile()
    names = _custom_calls(compiled.as_text())
    count = lambda key: sum(  # noqa: E731
        bool(re.search(bench[key], n)) for n in names)
    assert (count("swa_kernel_pattern"), count("gqa_kernel_pattern"),
            count("flash_kernel_pattern")) == (9, 3, 12)
    assert (sum("grouped_matmul_rows" in n for n in names),
            sum("grouped_matmul_weights" in n for n in names)) == (36, 12)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes + memory.argument_size_in_bytes \
        < 11.3 * 2 ** 30


def test_dp4_gpt2_step_reduces_the_tied_table_once(topo, monkeypatch):
    """The four-chip data-parallel GPT-2 small step (benchmark cell
    gpt2s-dp4-b64: the cell's widths and 16 sequences a chip, depth cut to
    2 for the suite's sake), compiled for the described v5e:2x2 the way
    the trainer builds it: steps.grad_reduce_plan sees four devices on the
    batch axes and a replicated state, the model is told the four ways,
    and the compiled step holds ONE all-reduce of the (50304, 768) table,
    in float32, where the partitioner alone leaves two (the head's
    contribution and the lookup's). One chip of the same topology plans
    per_use, and jit_train_step passes jax.jit no compile options either
    way (the 24-kernel test above compiles that step). The head's kernels
    run inside a shard_map of their own, a chip on its view of the table:
    the view, and the one all-reduce, survive them."""
    import json

    from pytorch_distributed_train_tpu import losses as losses_lib
    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.ops import attention as attention_lib
    from pytorch_distributed_train_tpu.optim import make_optimizer
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
    from pytorch_distributed_train_tpu.parallel.partition import (
        rules_for_model,
    )
    from pytorch_distributed_train_tpu.train_state import TrainState

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "gpt2_small.json"),
              encoding="utf-8") as f:
        bench = json.load(f)
    monkeypatch.setattr(attention_lib, "_on_tpu", lambda: True)
    cfg = get_preset(bench["preset"])
    cfg.apply_overrides(list(bench["overrides"]) + [
        "data.batch_size=64", "model.num_layers=2"])
    mesh = build_mesh(cfg.mesh, devices=topo.devices)
    batch_axes = tuple(cfg.mesh.batch_axes)
    model = build_model(cfg.model, cfg.precision, mesh=mesh,
                        mesh_cfg=cfg.mesh)
    tx, _ = make_optimizer(cfg.optim, cfg.total_steps, 0)
    dummy = steps_lib.dummy_inputs(cfg.loss, cfg.model, cfg.data)

    def init(rng):
        params = model.init({"params": rng}, *dummy, train=False)["params"]
        return TrainState.create(params=params, tx=tx, batch_stats={},
                                 dynamic_scale=None, ema=False, swa=False)

    shape = jax.eval_shape(init, jax.random.PRNGKey(0))
    rules = rules_for_model(cfg.model.name)
    sharding = steps_lib.state_shardings(mesh, rules, shape)
    plan = steps_lib.grad_reduce_plan(mesh, sharding, batch_axes)
    assert (plan.mode, plan.batch_devices) == ("per_leaf", 4)
    one = build_mesh(cfg.mesh, devices=topo.devices[:1])
    assert steps_lib.grad_reduce_plan(
        one, steps_lib.state_shardings(one, rules, shape),
        batch_axes).mode == "per_use"

    step = steps_lib.jit_train_step(
        steps_lib.make_train_step(
            model.clone(tied_shards=plan.batch_devices),
            losses_lib.get_loss_fn(
                cfg.loss, label_smoothing=cfg.label_smoothing), tx),
        mesh, sharding, batch_axes)
    state = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), shape, sharding)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (cfg.data.batch_size, cfg.data.seq_len), jnp.int32,
        sharding=NamedSharding(mesh, P(batch_axes)))}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    text = step.lower(state, batch, rng).compile().as_text()
    # the flash kernels under shard_map (two a layer: forward and the fused
    # backward), the head's two beside them under their own names, a chip's
    # 16 sequences each
    _assert_head_rides_its_products(
        text, bench["flash_kernel_pattern"], 2 * cfg.model.num_layers,
        cfg.data.batch_size // 4 * cfg.data.seq_len, cfg.model.vocab_size)
    table = f"[{cfg.model.vocab_size},{cfg.model.hidden_size}]"
    reduced = [m.group(1) for m in re.finditer(
        r"= (\w+\[[\d,]*\])[^\n]*? all-reduce\(", text[text.index("\nENTRY"):])
        if m.group(1).endswith(table)]
    assert reduced == ["f32" + table], reduced


def _all_reduced(text, shape):
    """How often an array of ``shape`` (``f32[2048,5632]``) is all-reduced
    in a compiled program, alone or inside a tupled all-reduce."""
    return sum(len(re.findall(re.escape(shape), m.group(1)))
               for m in re.finditer(
                   r"= ([^\n]*?) all-reduce(?:-start)?\(",
                   text[text.index("\nENTRY"):]))


def test_looped_step_holds_its_flash_and_head_kernels_by_name(one_chip,
                                                              monkeypatch):
    """The looped decoder's step at the cell's shape (1 x 4096, the whole
    vocabulary, T 4 passes; depth cut to 1 for the suite's sake), compiled
    for the described chip: the flash kernel's instructions under the names
    `flash_kernel_pattern` finds, the head's two kernels once an exit under
    the names `head_kernel_pattern` finds and the flash pattern does not
    (at width 2048 the backward kernel asks for more VMEM than at 768:
    ops/lm_head_loss.py `_vmem_limit`), and no elementwise pass over an
    array of the logits' shape."""
    lowered, bench, cfg = _lowered_step(
        "ouro_2_6b_lm_l8", one_chip, monkeypatch, ["model.num_layers=1"])
    text = lowered.compile().as_text()
    names = _custom_calls(text)
    flash = [n for n in names if re.search(bench["flash_kernel_pattern"], n)]
    head = [n for n in names if re.search(bench["head_kernel_pattern"], n)]
    # forward and the fused backward (remat keeps what the forward handed
    # back and does not run it again: models/remat.py): once a layer in the
    # scanned pass's body, whatever the number of passes
    assert len(flash) == LOOP_FLASH_KERNELS, flash
    assert not set(flash) & set(head)
    passes = cfg.model.loop_steps
    assert sum("lm_head_fwd" in n for n in head) == passes, head
    assert sum("lm_head_bwd" in n for n in head) == passes, head
    rows, vocab = cfg.data.seq_len, cfg.model.vocab_size
    logits = re.compile(rf"\[(\d+,\d+,{vocab}|{rows},{vocab}|{vocab},{rows})\]")
    loops = [ln.strip()[:160] for ln in text.splitlines()
             if "kind=kLoop" in ln and logits.search(ln)
             and "calls=%bitcast_fusion" not in ln]
    assert not loops, loops


def test_dp4_looped_step_reduces_each_weight_once(topo, monkeypatch):
    """The looped decoder under `data=4` (four sequences of 4096, depth cut
    to 1), compiled for the described v5e:2x2 the way the trainer builds
    it: steps.grad_reduce_plan sees pure data parallelism, so the step is
    a replica's own program inside shard_map (its flash and head kernels
    as on one chip, a chip on its own sequence) and the gradient tree is
    reduced once: ONE all-reduce of a layer's (2048, 5632) projections and
    of the (2048, 49152) head, in float32, where the partitioner alone
    leaves one a use (four: tests/test_ouro_lm.py holds the same on CPU
    devices, with the values)."""
    from pytorch_distributed_train_tpu import losses as losses_lib
    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.ops import attention as attention_lib
    from pytorch_distributed_train_tpu.optim import make_optimizer
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
    from pytorch_distributed_train_tpu.parallel.partition import (
        rules_for_model,
    )
    from pytorch_distributed_train_tpu.train_state import TrainState

    monkeypatch.setattr(attention_lib, "_on_tpu", lambda: True)
    cfg = get_preset("ouro_2_6b_lm_l8")
    cfg.apply_overrides(["data.batch_size=4", "model.num_layers=1"])
    mesh = build_mesh(cfg.mesh, devices=topo.devices)
    batch_axes = tuple(cfg.mesh.batch_axes)
    replica = build_model(cfg.model, cfg.precision)  # no mesh inside
    tx, _ = make_optimizer(cfg.optim, cfg.total_steps, 0)
    dummy = steps_lib.dummy_inputs(cfg.loss, cfg.model, cfg.data)

    def init(rng):
        params = replica.init({"params": rng}, *dummy, train=False)["params"]
        return TrainState.create(params=params, tx=tx, batch_stats={},
                                 dynamic_scale=None, ema=False, swa=False)

    shape = jax.eval_shape(init, jax.random.PRNGKey(0))
    sharding = steps_lib.state_shardings(
        mesh, rules_for_model(cfg.model.name), shape)
    plan = steps_lib.grad_reduce_plan(mesh, sharding, batch_axes)
    assert (plan.mode, plan.batch_devices) == ("per_leaf", 4)
    step = steps_lib.jit_overlap_train_step(
        steps_lib.make_train_step(
            replica, losses_lib.get_loss_fn(cfg.loss), tx,
            reduce_grads_accum=steps_lib.monolithic_grad_reducer(batch_axes),
            reduce_metrics=steps_lib.metrics_reducer(batch_axes)),
        mesh, sharding, batch_axes)
    state = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), shape, sharding)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (cfg.data.batch_size, cfg.data.seq_len), jnp.int32,
        sharding=NamedSharding(mesh, P(batch_axes)))}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    text = step.lower(state, batch, rng).compile().as_text()
    names = _custom_calls(text)
    assert sum("lm_head_fwd" in n for n in names) == cfg.model.loop_steps
    # gate_proj and up_proj of the layer, each used four times: once each
    assert _all_reduced(text, "f32[2048,5632]") == 2 * cfg.model.num_layers
    assert _all_reduced(text, "f32[2048,49152]") == 1


@pytest.mark.parametrize("bits", [8, 4])
def test_quant_gemv_compiles(one_chip, bits):
    """Fused weight-dequant GEMV (ops/quant_matmul.py) at the ~1B llama
    decode shape the serving benches use: (1, 2048) x (2048, 5504)."""
    from pytorch_distributed_train_tpu import quant
    from pytorch_distributed_train_tpu.ops.quant_matmul import quant_matmul

    H, N = 2048, 5504
    w = jax.ShapeDtypeStruct((H, N), jnp.float32)
    leaf = jax.eval_shape(
        quant.quantize_leaf if bits == 8 else quant.quantize_leaf_int4, w)
    q = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
         for k, v in leaf.items()}
    x = jax.ShapeDtypeStruct((1, H), jnp.bfloat16, sharding=one_chip)
    _compile(quant_matmul, x, q)
