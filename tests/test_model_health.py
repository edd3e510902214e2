"""Model-health observability plane (ISSUE 20): in-graph training-
dynamics telemetry oracles, the bitwise off-parity contract, the
host-side early-warning monitor, the registry ``module=`` label mirror,
and the rollout/GRPO analytics oracles.

The heavyweight acceptance drills (subprocess trainer storm -> fleet
alert -> postmortem; overlap shard_map parity) live in
tests/test_zmodel_health.py."""

import json
import math

import numpy as np
import pytest
from tiny import tiny_cfg

import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu import steps as steps_lib
from pytorch_distributed_train_tpu.config import (
    MeshConfig,
    ModelConfig,
    OptimConfig,
    PrecisionConfig,
)
from pytorch_distributed_train_tpu.faults import registry as fregistry
from pytorch_distributed_train_tpu.losses import get_loss_fn, make_grpo_loss
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.obs import events as events_lib
from pytorch_distributed_train_tpu.obs.collector import (
    family_value,
    parse_exposition,
)
from pytorch_distributed_train_tpu.obs.model_health import ModelHealthMonitor
from pytorch_distributed_train_tpu.obs.registry import get_registry
from pytorch_distributed_train_tpu.online.rollouts import (
    RolloutBatch,
    RolloutRecord,
    to_grpo_batch,
)
from pytorch_distributed_train_tpu.optim import make_optimizer
from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
from pytorch_distributed_train_tpu.parallel.partition import rules_for_model
from pytorch_distributed_train_tpu.train_state import TrainState

# vit_b16: BN-free (no batch_stats), so every param leaf is trainable
# and the health pass covers every top-level module.
MODEL_CFG = ModelConfig(name="vit_b16", num_classes=10, image_size=8,
                        patch_size=4, hidden_size=32, num_layers=2,
                        num_heads=4, mlp_dim=64, dropout_rate=0.0)
OPT_CFG = OptimConfig(name="momentum", learning_rate=0.1,
                      schedule="constant", warmup_steps=0,
                      weight_decay=1e-4)


def _batch(n=16, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "image": jnp.asarray(
            rng.standard_normal((n, 8, 8, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, n), jnp.int32),
    }


def _build(**step_kw):
    """Single-device vit setup; returns (fresh_state_fn, jitted_step)."""
    mesh = build_mesh(MeshConfig(data=1), jax.devices("cpu")[:1])
    model = build_model(MODEL_CFG, PrecisionConfig())
    loss_fn = get_loss_fn("softmax_xent")
    tx, _ = make_optimizer(OPT_CFG, total_steps=100)
    rules = rules_for_model(MODEL_CFG.name)

    def init_state(rng):
        x = jnp.zeros((2, 8, 8, 3))
        variables = model.init({"params": rng}, x, train=False)
        return TrainState.create(
            params=variables["params"], tx=tx,
            batch_stats=variables.get("batch_stats", {}))

    shape = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    sharding = steps_lib.state_shardings(mesh, rules, shape)

    def fresh():
        return jax.jit(init_state, out_shardings=sharding)(
            jax.random.PRNGKey(0))

    step = steps_lib.jit_train_step(
        steps_lib.make_train_step(model, loss_fn, tx, **step_kw),
        mesh, sharding, ("data", "fsdp"))
    return fresh, step


def _tree_norm(tree) -> float:
    return math.sqrt(sum(
        float(np.sum(np.square(np.asarray(x, np.float64))))
        for x in jax.tree.leaves(tree)))


def _diff_norm(new, old) -> float:
    return math.sqrt(sum(
        float(np.sum(np.square(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))
        for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))))


@pytest.fixture(scope="module")
def health_run():
    """One compiled model_health=True step, run twice; keeps the param
    snapshots around so every oracle test reads from ONE compile."""
    fresh, step = _build(model_health=True)
    state = fresh()
    snaps = [jax.device_get(state.params)]
    metrics = []
    rng = jax.random.PRNGKey(42)
    for i in range(2):
        state, m = step(state, _batch(seed=i), rng)
        snaps.append(jax.device_get(state.params))
        metrics.append({k: float(v) for k, v in jax.device_get(m).items()})
    return {"snaps": snaps, "metrics": metrics}


# ----------------------------------------------- in-graph stats oracles
def test_health_stats_numpy_oracle(health_run):
    """Every in-graph scalar against a float64 numpy oracle computed
    from the host-side param snapshots: param_norm is the PRE-update
    tree norm, update_norm the actual applied update ||new - old||,
    update_ratio_max the worst module's ratio, and the per-module grad
    norms RSS-compose to the step's global grad_norm."""
    for i, m in enumerate(health_run["metrics"]):
        old, new = health_run["snaps"][i], health_run["snaps"][i + 1]
        assert m["param_norm"] == pytest.approx(
            _tree_norm(old), rel=1e-4)
        assert m["update_norm"] == pytest.approx(
            _diff_norm(new, old), rel=1e-4)
        ratios = {}
        for key in old:
            p = _tree_norm(old[key])
            u = _diff_norm(new[key], old[key])
            assert m[f"param_norm/{key}"] == pytest.approx(p, rel=1e-4)
            assert m[f"update_norm/{key}"] == pytest.approx(u, rel=1e-4)
            ratios[key] = u / (p + 1e-12)
            assert m[f"update_ratio/{key}"] == pytest.approx(
                ratios[key], rel=1e-4)
        assert m["update_ratio_max"] == pytest.approx(
            max(ratios.values()), rel=1e-4)
        # per-module grad norms RSS-compose to the global grad norm
        rss = math.sqrt(sum(
            m[f"grad_norm/{k}"] ** 2 for k in old))
        assert m["grad_norm"] == pytest.approx(rss, rel=1e-4)


def test_model_health_off_is_bitwise_noop(health_run):
    """The flag only ADDS metrics entries: with it off, the same init
    and batches produce bitwise-identical params, and none of the
    plane's keys appear in the metrics."""
    fresh, step = _build(model_health=False)
    state = fresh()
    rng = jax.random.PRNGKey(42)
    for i in range(2):
        state, m = step(state, _batch(seed=i), rng)
    off = jax.device_get(state.params)
    on = health_run["snaps"][-1]
    for a, b in zip(jax.tree.leaves(on), jax.tree.leaves(off)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    off_keys = set(jax.device_get(m).keys())
    assert "update_ratio_max" not in off_keys
    assert not any(k.startswith(("param_norm", "update_norm",
                                 "update_ratio")) for k in off_keys)
    on_keys = set(health_run["metrics"][0])
    assert {"update_ratio_max", "param_norm", "update_norm"} <= on_keys


def test_health_stats_under_grad_accum():
    """grad_accum_steps>1: the stats still measure the ACTUAL applied
    update of the whole accumulated step — same oracle, accum path."""
    fresh, step = _build(model_health=True, grad_accum_steps=4)
    state = fresh()
    old = jax.device_get(state.params)
    state, m = step(state, _batch(n=16, seed=0), jax.random.PRNGKey(1))
    new = jax.device_get(state.params)
    m = {k: float(v) for k, v in jax.device_get(m).items()}
    assert m["update_norm"] == pytest.approx(_diff_norm(new, old),
                                             rel=1e-4)
    assert m["param_norm"] == pytest.approx(_tree_norm(old), rel=1e-4)
    rss = math.sqrt(sum(m[f"grad_norm/{k}"] ** 2 for k in old))
    assert m["grad_norm"] == pytest.approx(rss, rel=1e-4)
    assert m["update_norm"] > 0.0


# ------------------------------------------------- host-side monitor
@pytest.fixture()
def _clean_obs(tmp_path, monkeypatch):
    monkeypatch.delenv(events_lib.ENV_VAR, raising=False)
    events_lib.configure(str(tmp_path / "events"), who="host0")
    yield str(tmp_path / "events")
    events_lib._reset_for_tests()


def _feed_healthy(mon, n=8, base=None):
    base = base or {}
    for i in range(n):
        rec = {"grad_norm": 1.0, "update_norm": 0.1,
               "update_ratio_max": 0.01, "reward_mean": 0.5,
               "token_entropy": 2.0, "lr": 0.05, "loss_scale": 1.0}
        rec.update(base)
        assert mon.observe(i, rec) is False
    return n


def test_monitor_directional_verdicts(_clean_obs):
    """'above' series warn only on upward deviation, 'below' only on
    downward — a gradient norm falling or a reward jumping is news, not
    danger. Warnings land in the journal WITH the optimizer context."""
    reg = get_registry()
    before = reg.get_value("model_health_warnings_total",
                           {"series": "grad_norm"}) or 0.0
    mon = ModelHealthMonitor(min_samples=4, min_rel=0.1)
    n = _feed_healthy(mon)
    # healthy-direction deviations: no warning, value enters the window
    assert mon.observe(n, {"grad_norm": 1e-6, "reward_mean": 100.0,
                           "token_entropy": 50.0}) is False
    assert reg.get_value("model_health_warnings_total",
                         {"series": "grad_norm"}) in (None, before)
    # unhealthy directions: grad_norm up, reward down, entropy down
    assert mon.observe(n + 1, {"grad_norm": 500.0, "reward_mean": -9.0,
                               "token_entropy": 0.001,
                               "lr": 0.05, "loss_scale": 1.0}) is False
    assert reg.get_value("model_health_warnings_total",
                         {"series": "grad_norm"}) == before + 1
    assert reg.get_value("model_health_warning_streak") == 1.0
    events = [e for e in events_lib.load_events(_clean_obs)
              if e["category"] == "model"]
    warned = {e["detail"]["series"] for e in events
              if e["name"] == "early_warning"}
    assert warned == {"grad_norm", "reward_mean", "token_entropy"}
    for e in events:
        assert e["detail"]["lr"] == 0.05          # context stamped
        assert e["detail"]["loss_scale"] == 1.0
    # NaN and absent series are skipped, never warnings
    assert mon.observe(n + 2, {"grad_norm": float("nan")}) is False


def test_monitor_streak_arms_rewind_and_resets(_clean_obs):
    class FakeProfiler:
        calls = []

        def anomaly(self, kind, step, **detail):
            self.calls.append((kind, step, detail))

    reg = get_registry()
    armed_before = reg.family_total("model_health_rewinds_armed_total")
    mon = ModelHealthMonitor(min_samples=4, min_rel=0.1, arm_streak=3,
                             profiler=FakeProfiler())
    n = _feed_healthy(mon)
    spike = {"grad_norm": 500.0, "lr": 0.05}
    assert mon.observe(n, spike) is False      # streak 1
    assert mon.observe(n + 1, spike) is False  # streak 2
    assert mon.observe(n + 2, spike) is True   # streak 3: ARM
    assert reg.family_total(
        "model_health_rewinds_armed_total") == armed_before + 1
    assert reg.get_value("model_health_warning_streak") == 3.0
    armed = [e for e in events_lib.load_events(_clean_obs)
             if e["category"] == "model" and e["name"] == "rewind_armed"]
    assert len(armed) == 1 and armed[0]["detail"]["streak"] == 3
    assert armed[0]["detail"]["lr"] == 0.05
    # profiler poked on every warned observation
    assert len(FakeProfiler.calls) == 3
    assert FakeProfiler.calls[0][0] == "model_health"
    assert "grad_norm" in FakeProfiler.calls[0][2]["series"]
    # reset: windows forgotten, streak cleared, spike no longer judged
    mon.reset()
    assert reg.get_value("model_health_warning_streak") == 0.0
    assert mon.observe(99, spike) is False
    assert reg.family_total(
        "model_health_rewinds_armed_total") == armed_before + 1


# ------------------------------------------- registry module= mirror
def test_set_from_mapping_routes_module_keys_to_label():
    """``grad_norm/<module>`` mirrors as one ``train_grad_norm`` family
    with a bounded ``module=`` label; the label-less series keeps the
    tree-wide scalar, so every fixed-name scrape consumer (collector,
    alerts) still reads it."""
    reg = get_registry()
    reg.set_from_mapping(
        {"grad_norm": 2.0, "grad_norm/conv_init": 1.5,
         "update_ratio/conv_init": 0.25, "skip_me": "text"},
        prefix="train")
    assert reg.get_value("train_grad_norm") == 2.0
    assert reg.get_value("train_grad_norm",
                         {"module": "conv_init"}) == 1.5
    assert reg.get_value("train_update_ratio",
                         {"module": "conv_init"}) == 0.25
    text = reg.render()
    assert 'train_grad_norm{module="conv_init"} 1.5' in text
    # the scrape consumer's reader sees the label-less tree-wide value
    fams = parse_exposition(text)
    assert family_value(fams, "train_grad_norm") == 2.0
    assert family_value(fams, "train_grad_norm",
                        {"module": "conv_init"}) == 1.5


# ------------------------------------------- rollout batch analytics
def _encode(s):
    return [1 + (b % 254) for b in s.encode()]


def test_rollout_analytics_gauges_match_numpy():
    records = []
    recs = [("p0", "aa", "v1", 0), ("p0", "abcd", "v1", 0),
            ("p1", "x", "v1", 1), ("p1", "xyz", "v2", 1)]
    for prompt, completion, ver, gid in recs:
        records.append(RolloutRecord(
            prompt=prompt, completion=completion, finish_reason="stop",
            weight_version=ver, group=gid))
    batch = RolloutBatch(records=records)
    out = to_grpo_batch(batch, _encode,
                        lambda p, c: float(len(c)), seq_len=16)
    reg = get_registry()
    raw = np.asarray([2.0, 4.0, 1.0, 3.0], np.float32)
    assert reg.get_value("rollout_reward_mean") == pytest.approx(
        float(raw.mean()))
    assert reg.get_value("rollout_reward_std") == pytest.approx(
        float(raw.std()))
    assert reg.get_value("rollout_advantage_mean") == pytest.approx(
        float(out["advantage"].mean()), abs=1e-6)
    assert reg.get_value("rollout_advantage_std") == pytest.approx(
        float(out["advantage"].std()))
    assert reg.get_value("rollout_mixed_versions") == 2.0
    # group normalization: each group's advantages are +-1 here
    np.testing.assert_allclose(np.sort(out["advantage"].reshape(2, 2)),
                               [[-1.0, 1.0], [-1.0, 1.0]], atol=1e-5)


# ------------------------------------------------ GRPO aux oracles
def _np_log_softmax(x):
    x = x - x.max(-1, keepdims=True)
    return x - np.log(np.exp(x).sum(-1, keepdims=True))


def test_grpo_token_entropy_and_kl_oracle():
    rng = np.random.default_rng(7)
    B, S, V = 3, 6, 11
    logits = rng.standard_normal((B, S, V)).astype(np.float32) * 2.0
    ids = rng.integers(0, V, (B, S)).astype(np.int32)
    mask = np.zeros((B, S), np.float32)
    mask[:, 2:5] = 1.0  # completion tokens only
    behavior = (rng.standard_normal((B, S)) - 3.0).astype(np.float32)
    batch = {"input_ids": jnp.asarray(ids),
             "loss_mask": jnp.asarray(mask),
             "advantage": jnp.asarray(rng.standard_normal(B),
                                      jnp.float32),
             "behavior_logprobs": jnp.asarray(behavior)}
    loss, aux = make_grpo_loss(0.2)(jnp.asarray(logits), batch)
    lp = _np_log_softmax(logits[:, :-1].astype(np.float64))
    m = mask[:, 1:]
    denom = max(m.sum(), 1.0)
    entropy = (-(np.exp(lp) * lp).sum(-1) * m).sum() / denom
    assert float(aux["token_entropy"]) == pytest.approx(entropy,
                                                        rel=1e-5)
    logp = np.take_along_axis(lp, ids[:, 1:, None], axis=-1)[..., 0]
    kl = ((behavior[:, 1:] - logp) * m).sum() / denom
    assert float(aux["kl_behavior"]) == pytest.approx(kl, rel=1e-5)
    assert np.isfinite(float(loss))
    # without behavior_logprobs: REINFORCE path, entropy still there,
    # no KL estimate
    batch.pop("behavior_logprobs")
    loss2, aux2 = make_grpo_loss(0.2)(jnp.asarray(logits), batch)
    assert "kl_behavior" not in aux2
    assert float(aux2["token_entropy"]) == pytest.approx(entropy,
                                                         rel=1e-5)
    adv = np.asarray(batch["advantage"])[:, None]
    reinforce = (-adv * logp * m).sum() / denom
    assert float(loss2) == pytest.approx(reinforce, rel=1e-5)


# ------------------------------------- trainer e2e: early-warning drill
def test_trainer_grad_spike_warns_before_sentinel(tmp_path, monkeypatch):
    """``step.grad_spike`` storm on a tiny trainer: the model-health
    monitor journals early warnings on the inflated grad/update
    telemetry and pokes the profiler anomaly hook, while the loss-based
    sentinel — watching an UNTOUCHED loss — never records a bad step.
    The fleet-level half of the drill (grad_norm_spike alert +
    postmortem) is tests/test_zmodel_health.py."""
    from pytorch_distributed_train_tpu.trainer import Trainer

    monkeypatch.delenv("RESTART_GENERATION", raising=False)
    monkeypatch.delenv(fregistry.ENV_VAR, raising=False)
    fregistry._reset_for_tests()
    cfg = tiny_cfg(
        "total_steps=14", f"checkpoint.dir={tmp_path}/ckpt",
        f"obs.jsonl_path={tmp_path}/metrics.jsonl",
        f"obs.events_dir={tmp_path}/events", "obs.model_health=true",
        "sentinel.enabled=true")
    # organic loss jitter can't reach 50% of median — the sentinel can
    # only trip on a loss spike, and this drill never inflates the loss
    cfg.sentinel.spike_min_rel = 0.5
    cfg.faults.inject = ("step.grad_spike@step=11:count=2",)
    reg = get_registry()
    warn_before = reg.get_value("model_health_warnings_total",
                                {"series": "grad_norm"}) or 0.0
    poke_before = reg.get_value("profiler_anomalies_total",
                                {"kind": "model_health"}) or 0.0
    try:
        t = Trainer(cfg)
        t.fit()
        t.close()
    finally:
        events_lib._reset_for_tests()
        fregistry._reset_for_tests()
    # the storm warned on both inflated observations
    assert reg.get_value("model_health_warnings_total",
                         {"series": "grad_norm"}) >= warn_before + 2
    assert reg.get_value("profiler_anomalies_total",
                         {"kind": "model_health"}) >= poke_before + 2
    # the flag raised at step N inflates the step that completes as N+1
    # (same stance as step.nan) — the storm lands on steps 12 and 13
    warnings = [e for e in events_lib.load_events(cfg.obs.events_dir)
                if e["category"] == "model"
                and e["name"] == "early_warning"]
    storm = [e for e in warnings if e.get("step") in (12, 13)]
    assert len(storm) >= 2
    series = {e["detail"]["series"] for e in storm}
    assert "grad_norm" in series and "update_ratio_max" in series
    # optimizer-scale context on every warning record
    assert all(e["detail"]["lr"] == pytest.approx(0.05) for e in storm)
    # a 2-step storm stays under arm_streak=3: no rewind armed, and the
    # untouched loss means the sentinel saw nothing at all
    assert t._rewinds == 0
    kinds = [e[1] for e in t.recorder.events()]
    assert "sentinel_bad_step" not in kinds
    assert "sentinel_rewind" not in kinds
    rows = [json.loads(line) for line in open(cfg.obs.jsonl_path)]
    summary = [r for r in rows if r.get("tag") == "summary"][-1]
    assert summary["rewinds"] == 0
    # the in-graph plane rode the whole run: every train record carries
    # the aggregates, and the inflation is visible at the storm steps
    train = {r["step"]: r for r in rows if r.get("tag") == "train"}
    assert all("update_ratio_max" in r for r in train.values())
    assert train[12]["grad_norm"] > 100 * train[11]["grad_norm"]
