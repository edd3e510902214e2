"""The looped decoder (models/llama.py ``loop_steps``, preset
``ouro_2_6b_lm_l8``) against its plain reference at a small size on the CPU:
every exit's logits, the exit distribution, the loss and every gradient
leaf; the tree holds ONE stack's leaves and a leaf's gradient is the sum
over its uses; the loss's edges; remat; the head's kernels (interpreter)
under per-token weights; the counts; and the guard that every other
``llama`` preset traces what the parent traced."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from lm_family import close as _close
from lm_family import (
    decay_mask,
    family,
    preset_tree,
    rehearsal_cfg,
    tree_and_lowered_step_are_the_parents,
)

from pytorch_distributed_train_tpu import losses
from pytorch_distributed_train_tpu.config import get_preset
from pytorch_distributed_train_tpu.models import llama
from pytorch_distributed_train_tpu.models.registry import build_model

OURO = "ouro_2_6b_lm_l8"
T, L, S = 3, 3, 128  # the rehearsal's: T differs from every other number

# (not the autouse fixture of the other families' files: the trainers and
# the lowered steps below run as a run lowers them)
_exact_products = functools.partial(jax.default_matmul_precision, "highest")


@pytest.fixture(scope="module")
def bench():
    """(configuration file, Reference at the rehearsal's sizes, the program's
    model at the same sizes in float32, seeded variables, ids (2, S))."""
    fam = family(OURO)
    assert (fam.ref.T, fam.ref.L) == (T, L)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, S), 0, fam.ref.V)
    return (fam.config, fam.mod, fam.ref, fam.cfg, fam.model,
            fam.ref.init_variables(7), ids)


def _program_loss(model, params, ids, **kw):
    out = model.apply({"params": params}, ids, train=True)
    return losses.looped_lm_xent(out, {"input_ids": ids, **kw})


def test_every_exits_logits_and_gates_match_the_reference(bench):
    _, _, ref, _, model, variables, ids = bench
    with _exact_products():
        out = jax.jit(lambda v: model.apply(v, ids, train=True))(variables)
        assert isinstance(out, llama.LoopExits)
        assert out.x.shape == (T, 2, S, ref.d) and out.gates.shape == (T, 2, S)
        assert out.table.shape == (ref.V, ref.d) and out.beta == 0.05
        exits = jax.jit(ref._exits)
        for row in range(2):
            _, g, logits = exits(variables["params"], ids[row])
            for t in range(T):
                _close(out.x[t, row] @ out.table.T, logits[t])
            _close(out.gates[:, row], g)


def test_the_sandwich_alone_is_one_pass_of_the_reference(bench):
    """``sandwich_norm`` without the loop: plain logits from a tree with four
    scales a layer and no gate, the reference's FIRST exit."""
    config, _, ref, cfg, _, variables, ids = bench
    one = rehearsal_cfg(OURO, "model.loop_steps=1")
    model = build_model(one.model, one.precision)
    params = {k: v for k, v in variables["params"].items()
              if k != "exit_gate"}
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, ids))["params"]
    assert jax.tree.structure(shapes) == jax.tree.structure(params)
    assert {k for k in shapes["layer0"] if k.endswith("norm")} == {
        "input_norm", "attn_out_norm", "post_attn_norm", "mlp_out_norm"}
    with _exact_products():
        logits = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
        assert logits.shape == (2, S, ref.V) and logits.dtype == jnp.float32
        _, _, first = jax.jit(ref._exits)(variables["params"], ids[0])
        _close(logits[0], first[0])


def test_exit_distribution_sums_to_one_and_the_last_takes_the_remainder(
        bench):
    _, mod, _, _, _, _, _ = bench
    g = jax.random.normal(jax.random.PRNGKey(0), (4, 5, 7)) * 3.0
    p, logp = losses.exit_distribution(g)
    _close(jnp.sum(p, 0), jnp.ones((5, 7)), 1e-6)
    lam = jax.nn.sigmoid(g)
    _close(p[0], lam[0], 1e-6)
    _close(p[1], lam[1] * (1 - lam[0]), 1e-6)
    _close(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), 1e-6)
    _close(jnp.exp(logp), p, 1e-6)
    # g_T is unused: the last gate moves nothing
    moved, _ = losses.exit_distribution(g.at[3].add(5.0))
    assert jnp.array_equal(moved, p)
    # and the reference's recurrence, written out, is the same numbers
    _close(p[:, 0], mod.exit_distribution(g[:, 0]), 1e-6)


def test_loss_and_every_gradient_leaf_match_the_reference(bench):
    _, _, ref, _, model, variables, ids = bench
    params = variables["params"]
    with _exact_products():
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p: _program_loss(model, p, ids), has_aux=True))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, ids)))(params)
    _close(loss, want, 1e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    other = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    assert len(flat) == 5 + 11 * L  # ONE stack's leaves, not T of them
    for path, g in flat:
        _close(g, other[path], 5e-5)
    # the nine gauges, by name
    assert sorted(aux) == sorted(
        ["exit_entropy"] + [f"exit_{k}_t{t}" for k in ("share", "ce")
                            for t in range(1, T + 1)])
    _close(sum(aux[f"exit_share_t{t}"] for t in range(1, T + 1)), 1.0, 1e-6)


def test_the_references_sweep_is_the_whole_models_gradient(bench):
    """``_sweep`` walks the T x L applications from the host and adds each
    use's gradient to its layer's; ``jax.grad`` of the model in one piece
    says the same."""
    _, _, ref, _, _, variables, ids = bench
    params = variables["params"]
    with _exact_products():
        total = float(ids.shape[0] * (ids.shape[1] - 1))
        loss, grads, mean_ce, mean_p = ref._sweep("float32", params, ids)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, ids)))(params)
    _close(loss / total, want, 1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        _close(a / total, b, 5e-5)
    assert mean_ce.shape == mean_p.shape == (T,)
    _close(jnp.sum(mean_p), 1.0, 1e-6)


def test_a_weights_gradient_is_the_sum_over_t_untied_copies(bench):
    """T copies of the stack, initialised alike, one a pass: the looped
    program's gradient of a leaf is the SUM of the copies' gradients (the
    embedding's is the first copy's, which alone looks ids up)."""
    _, mod, ref, _, model, variables, ids = bench
    params = variables["params"]
    row = ids[0]

    def untied(copies):
        x = copies[0]["tok_embed"]["embedding"][row]
        ces, gs = [], []
        for t in range(T):
            for i in range(L):
                x = ref._layer(copies[t][f"layer{i}"], x, lambda a: a)
            ce, g, x = ref._exit(copies[t], x, row, lambda a: a)
            ces.append(ce)
            gs.append(g)
        per = mod.token_loss(jnp.stack(ces), jnp.stack(gs), ref.beta)
        return jnp.sum(per[:-1]) / (S - 1)

    with _exact_products():
        each = jax.jit(jax.grad(untied))([params] * T)
        tied = jax.jit(jax.grad(
            lambda p: _program_loss(model, p, row[None])[0]))(params)
    summed = jax.tree.map(lambda *g: sum(g), *each)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(tied)[0],
                            jax.tree.leaves(summed)):
        _close(a, b, 5e-5)
    one = each[0]["layer0"]["mlp"]["up_proj"]["kernel"]
    assert float(jnp.linalg.norm(
        tied["layer0"]["mlp"]["up_proj"]["kernel"] - one)) \
        > 0.1 * float(jnp.linalg.norm(one))  # not ONE use's gradient


def test_beta_0_and_closed_gates_is_the_last_exits_causal_lm_xent(bench):
    _, _, ref, cfg, _, variables, ids = bench
    cfg.model.loop_entropy_beta = 0.0
    try:
        model = build_model(cfg.model, cfg.precision)
    finally:
        cfg.model.loop_entropy_beta = 0.05
    params = jax.tree.map(lambda x: x, variables["params"])
    params["exit_gate"] = {
        "kernel": jnp.zeros_like(params["exit_gate"]["kernel"]),
        "bias": jnp.full((1,), -1e4, jnp.float32)}
    mask = (jax.random.uniform(jax.random.PRNGKey(5), ids.shape) > 0.3
            ).astype(jnp.float32)
    with _exact_products():
        out = model.apply({"params": params}, ids, train=True)
        loss, aux = losses.looped_lm_xent(
            out, {"input_ids": ids, "loss_mask": mask})
        last = (out.x[-1] @ out.table.T).astype(jnp.float32)
        want, _ = losses.causal_lm_xent(
            last, {"input_ids": ids, "loss_mask": mask})
    assert np.isfinite(float(loss))
    _close(loss, want, 1e-6)
    _close(aux[f"exit_share_t{T}"], 1.0, 1e-6)
    _close(aux[f"exit_ce_t{T}"], want, 1e-6)


def test_remat_on_and_off_give_the_same_gradients(bench):
    _, _, _, cfg, model, variables, ids = bench
    assert model.remat
    cfg.model.remat = False
    try:
        plain = build_model(cfg.model, cfg.precision)
    finally:
        cfg.model.remat = True
    params = variables["params"]
    with _exact_products():
        a = jax.jit(jax.grad(
            lambda p: _program_loss(model, p, ids)[0]))(params)
        b = jax.jit(jax.grad(
            lambda p: _program_loss(plain, p, ids)[0]))(params)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        _close(x, y, 1e-6)


def test_the_heads_kernels_under_per_token_weights_match_the_logits_path(
        monkeypatch):
    """Each exit's per-token loss through the head's kernels (interpreter:
    the gate the dispatch asks is steered here, as tests/test_lm_head_loss
    does), weighted by p_t: the loss, the gauges and the gradients of the
    hidden states, the table and the gates are the logits path's."""
    from pytorch_distributed_train_tpu.ops import attention, lm_head

    B, S_, C, V = 1, 128, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(ks[0], (2, B, S_, C), jnp.float32)
    table = 0.05 * jax.random.normal(ks[1], (V, C), jnp.float32)
    gates = jax.random.normal(ks[2], (2, B, S_), jnp.float32)
    batch = {"input_ids": jax.random.randint(ks[3], (B, S_), 0, V)}

    def loss(x, table, gates):
        return losses.looped_lm_xent(
            llama.LoopExits(x, table, gates, None, 0.05), batch)

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
    with _exact_products():
        (want, want_aux), want_grads = grad(x, table, gates)
        monkeypatch.setattr(attention, "_on_tpu", lambda: True)
        monkeypatch.setattr(lm_head, "_interpret", lambda: True)
        assert lm_head.unsupported(
            lm_head.HeadOperands(x[0], table, None)) is None
        (got, got_aux), got_grads = grad(x, table, gates)
    _close(got, want, 1e-6)
    for k in want_aux:
        _close(got_aux[k], want_aux[k], 1e-6)
    for a, b in zip(got_grads, want_grads):
        _close(a, b, 2e-5)


def test_the_loss_and_the_loop_are_set_together():
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = get_preset("ouro_2_6b_lm_l8")
    cfg.loss = "causal_lm_xent"
    with pytest.raises(ValueError, match="looped_lm_xent"):
        Trainer(cfg)
    with pytest.raises(TypeError, match="loop_steps"):
        losses.looped_lm_xent(jnp.zeros((1, 4, 8)), {"input_ids":
                                                     jnp.zeros((1, 4), int)})
    looped = build_model(get_preset("ouro_2_6b_lm_l8").model,
                         get_preset("ouro_2_6b_lm_l8").precision)
    with pytest.raises(ValueError, match="training-path"):
        jax.eval_shape(lambda: looped.clone(decode=True).init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
            train=False))


def test_preset_counts_flops_decay_mask_and_partition_rules():
    """612 438 017 parameters by the tree and by the formula; the layers
    and the head count T times in the FLOPs a token (13.9 G trained at the
    causal half of the attention term, ISSUE 35's count); norm scales and
    the gate's bias do not decay, the gate's matrix does; the new leaves
    are replicated under the ``llama`` rules."""
    from pytorch_distributed_train_tpu.parallel.partition import (
        rules_for_model,
    )
    from pytorch_distributed_train_tpu.utils import flops

    cfg, _, shapes, count = preset_tree(OURO)
    assert count == 612438017 == flops.llama_param_count(cfg.model)
    assert set(shapes) == {"exit_gate", "final_norm", "lm_head",
                           "tok_embed"} | {f"layer{i}" for i in range(8)}

    d, m, v, s = 2048, 5632, 49152, 4096
    layer = 2.0 * (4 * d * d + 3 * d * m)
    fwd = flops.llama_fwd_flops_per_token(cfg.model, s)
    assert fwd == 4 * (8 * (layer + 4.0 * s * d) + 2.0 * d * v)
    once = get_preset("ouro_2_6b_lm_l8").model
    once.loop_steps = 1
    assert fwd == 4 * flops.llama_fwd_flops_per_token(once, s)
    causal = 3 * (fwd - 4 * 8 * 2.0 * s * d)  # half the un-masked pairs
    assert 13.8e9 < causal < 14.0e9

    flat = decay_mask(cfg, shapes)
    assert flat["['exit_gate']['kernel']"] and not flat["['exit_gate']['bias']"]
    assert not flat["['layer0']['attn_out_norm']['scale']"]
    assert not flat["['layer7']['mlp_out_norm']['scale']"]
    assert flat["['layer0']['mlp']['up_proj']['kernel']"]

    rules = rules_for_model("llama")
    for name, shape in (("layer0/attn_out_norm/scale", (2048,)),
                        ("layer3/mlp_out_norm/scale", (2048,)),
                        ("exit_gate/kernel", (2048, 1))):
        assert tuple(rules.spec_for(name, shape)) == (), name
    named = [pat.pattern for pat, _ in rules.rules[:-1]]  # not the catch-all
    assert any(p.startswith("exit_gate") for p in named)
    assert any("attn_out_norm" in p and "mlp_out_norm" in p for p in named)


# every other `llama` preset, at small sizes: (leaves, sha256 of the tree's
# signature, sha256 of the lowered step), read on the parent commit (PR 31)
PARENTS = {
    "llama2_7b": (
        21, "14a0e08804997bac797fcca75e41ecca248591399f9df989c4909b9b4d2fc4d8",
        "392677570050b2e3d8634f0b62ede489bffb6bfa0314e740add81d89b2a686f4"),
    "mixtral_8x7b": (
        23, "05bec929da9e7745a70562df863e8810f2d72a9c4769cf64f73d02ae4404d607",
        "97aed697ad89c7336587ff31f3d3b2b95d24c951ab69310b810e5ba64ffa86f2"),
    "llama2_7b:logits": (
        21, "14a0e08804997bac797fcca75e41ecca248591399f9df989c4909b9b4d2fc4d8",
        "0bc96b542b6bdcf3d24b9de13177de5c3b94c1df34593816c9d4b84b0d6384a9"),
}
SMALL = ["model.hidden_size=64", "model.num_layers=2", "model.num_heads=4",
         "model.mlp_dim=128", "model.vocab_size=256", "model.max_seq_len=128",
         "data.seq_len=128", "data.batch_size=2"]
OVERRIDES = {
    "llama2_7b": SMALL + ["model.num_kv_heads=4"],
    "mixtral_8x7b": SMALL + ["model.num_kv_heads=2",
                             "model.attention_window=32"],
    "llama2_7b:logits": SMALL + ["model.num_kv_heads=4",
                                 "model.fused_lm_loss=false",
                                 "loss=causal_lm_xent"],
}


@pytest.mark.parametrize("preset", sorted(PARENTS))
def test_the_other_llama_presets_tree_and_lowered_step_are_the_parents(
        preset):
    """The loop, the sandwich and the exits are fields with defaults on the
    one decoder: a preset that sets none of them builds the parent's tree
    and lowers the parent's training step, byte for byte."""
    cfg = get_preset(preset.split(":")[0])
    cfg.apply_overrides(OVERRIDES[preset])
    tree_and_lowered_step_are_the_parents(cfg, [], *PARENTS[preset])


def test_replicas_reduce_each_looped_weight_once_and_train_as_one_device(
        tmp_path, capfd):
    """Eight CPU devices on the data axis, the state replicated: the looped
    decoder's step is a replica's own program inside shard_map, the
    gradient tree reduced once (ONE all-reduce of a weight used T times,
    where the partitioner leaves one a use), and two steps move the
    parameters as the one-device trainer's do."""
    import re

    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
    from pytorch_distributed_train_tpu.trainer import Trainer

    def trainer_on(devices, where):
        cfg = get_preset("ouro_2_6b_lm_l8")
        cfg.apply_overrides([
            "model.hidden_size=32", "model.num_layers=2",
            "model.loop_steps=3", "model.num_heads=2",
            "model.num_kv_heads=2", "model.mlp_dim=64",
            "model.vocab_size=128", "model.max_seq_len=32",
            "data.seq_len=32", "data.batch_size=8", "data.synthetic_size=16",
            "precision.compute_dtype=float32", "optim.warmup_steps=0",
            "total_steps=2", "checkpoint.save_every_steps=0",
            "checkpoint.resume=none", f"checkpoint.dir={tmp_path / where}"])
        return Trainer(cfg, mesh=build_mesh(cfg.mesh, devices))

    many, one = trainer_on(jax.devices(), "a"), trainer_on(
        jax.devices()[:1], "b")
    try:
        n = len(jax.devices())
        assert (many.grad_reduce.mode, many.grad_reduce.batch_devices) \
            == ("per_leaf", n)
        assert "a looped decoder" in many.grad_reduce.why
        assert one.grad_reduce.mode == "per_use"
        assert "[parallel] grad all-reduce: per_leaf" in capfd.readouterr().out
        ids = np.asarray(jax.random.randint(
            jax.random.PRNGKey(2), (8, 32), 0, 128), np.int32)
        text = many.train_step.lower(
            many.state, {"input_ids": ids}, many.step_rng).compile().as_text()
        reduced = [m.group(1) for m in re.finditer(
            r"= ([^\n]*?) all-reduce(?:-start)?\(", text)]
        assert sum(len(re.findall(r"f32\[32,64\]", r)) for r in reduced) \
            == 2 * 2  # gate_proj and up_proj of two layers: once each
        states = []
        for t in (many, one):
            state = t.state
            for _ in range(2):
                state, metrics = t.train_step(state, {"input_ids": ids},
                                              t.step_rng)
            states.append((state, metrics))
        (a, ma), (b, mb) = states
        np.testing.assert_allclose(ma["loss"], mb["loss"], rtol=1e-5)
        np.testing.assert_allclose(ma["exit_share_t1"], mb["exit_share_t1"],
                                   rtol=1e-5)
        for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
            np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-6)
    finally:
        many.close()
        one.close()


def test_fit_logs_the_nine_exit_gauges_the_loop_line_and_the_spans_word(
        tmp_path, capfd):
    """Through ``Trainer.fit`` by the preset (no side script): one `[loop]`
    line at build, the nine exit gauges in the registry after a cadenced
    log (`train_exit_*`, mirrored from the loss's aux by `_log_train`), the
    `train.compile` span's `loop` beside `head_loss`, the scopes
    `loop_pass` and `exit_head` in the step's op names."""
    import threading

    from pytorch_distributed_train_tpu.obs import spans as spans_lib
    from pytorch_distributed_train_tpu.obs.registry import get_registry
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = get_preset("ouro_2_6b_lm_l8")
    cfg.apply_overrides([
        "model.hidden_size=32", "model.num_layers=2", "model.loop_steps=3",
        "model.num_heads=2", "model.num_kv_heads=2", "model.mlp_dim=64",
        "model.vocab_size=128", "model.max_seq_len=32", "data.seq_len=32",
        "data.batch_size=8", "data.synthetic_size=64", "total_steps=3",
        "obs.log_every_steps=2", "eval_every_steps=1000000",
        "checkpoint.save_every_steps=0", "checkpoint.async_save=false",
        "checkpoint.resume=none", f"checkpoint.dir={tmp_path}"])
    llama._loop_logged.clear()  # the line is said once a layout a process
    trainer = Trainer(cfg)
    try:
        text = trainer.train_step.lower(
            trainer.state, {"input_ids": np.zeros((8, 32), np.int32)},
            trainer.step_rng).as_text(debug_info=True)
        trainer.fit()
    finally:
        trainer.close()
    said = capfd.readouterr()
    assert ("[loop] passes=3 layers=2 applications=6 exits=3 beta=0.05 "
            "impl=scan sandwich=1") in said.err
    registry = get_registry()
    shares = [registry.get_value(f"train_exit_share_t{t}") for t in (1, 2, 3)]
    assert all(s is not None and 0.0 < s < 1.0 for s in shares)
    assert abs(sum(shares) - 1.0) < 1e-5
    for t in (1, 2, 3):
        assert 4.0 < registry.get_value(f"train_exit_ce_t{t}") < 6.0
    assert 0.5 < registry.get_value("train_exit_entropy") < 1.1  # ln 3
    main = threading.main_thread().name
    spans = [s for s in spans_lib.get_recorder().events()
             if s.thread == main and s.name == "train.compile"]
    assert spans[-1].args["loop"] == "scan x3"
    assert spans[-1].args["head_loss"] == "xla: the backend is not a TPU"
    assert spans[-1].args["remat_keeps"] == "none"  # no kernel on the CPU
    assert "loop_pass" in text and "exit_head" in text
