"""Fault injection + elastic recovery (SURVEY §5.3): a tpurun-supervised
training job is hard-killed mid-run, the gang restarts, training resumes
from the latest Orbax step and finishes with the SAME losses an
uninterrupted run produces. Plus the multi-process jax.distributed
bring-up over the launcher's env contract (the MultiProcessTestCase
analogue, SURVEY §4.3).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from tiny import TINY

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}

TRAIN_WORKER = """
import os, sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
from pytorch_distributed_train_tpu.config import TrainConfig
from pytorch_distributed_train_tpu.trainer import Trainer

cfg = TrainConfig()
cfg.model.name = "resnet18"; cfg.model.num_classes = 10
cfg.model.image_size = 8
cfg.data.dataset = "synthetic_images"; cfg.data.synthetic_size = 256
cfg.data.batch_size = 32; cfg.data.num_workers = 1; cfg.data.prefetch = 2
cfg.optim.name = "momentum"; cfg.optim.learning_rate = 0.05
cfg.optim.schedule = "constant"; cfg.optim.warmup_steps = 0
cfg.total_steps = 8
cfg.checkpoint.dir = {ckpt!r}
cfg.checkpoint.save_every_steps = 2
cfg.checkpoint.async_save = False
cfg.obs.log_every_steps = 1
cfg.obs.jsonl_path = {metrics!r}
cfg.obs.fault_inject_at_step = {fault}
t = Trainer(cfg)
t.fit()
t.close()
"""


def _read_metrics(path):
    rows = {}
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            if r.get("tag") == "train":
                rows[r["step"]] = r
    return rows


def _run_worker(tmp_path, tag, fault, supervised):
    ckpt = str(tmp_path / f"ckpt-{tag}")
    metrics = str(tmp_path / f"metrics-{tag}.jsonl")
    script = tmp_path / f"worker-{tag}.py"
    script.write_text(TRAIN_WORKER.format(
        repo=REPO, ckpt=ckpt, metrics=metrics, fault=fault))
    env = {**os.environ, **CPU_ENV}
    if supervised:
        from pytorch_distributed_train_tpu.elastic import (
            ElasticAgent,
            LaunchConfig,
        )

        cfg = LaunchConfig(nprocs=1, max_restarts=2, monitor_interval_s=0.2,
                           env=CPU_ENV)
        rc = ElasticAgent(cfg, [sys.executable, str(script)]).run()
    else:
        env["RESTART_GENERATION"] = "0"
        rc = subprocess.run([sys.executable, str(script)], env=env,
                            timeout=600).returncode
    return rc, metrics


@pytest.mark.slow
def test_crash_resume_reaches_same_loss(tmp_path):
    # Reference: uninterrupted 8-step run.
    rc, ref_metrics = _run_worker(tmp_path, "ref", fault=0, supervised=False)
    assert rc == 0
    ref = _read_metrics(ref_metrics)
    # Faulted: killed at step 5 (after checkpoints at 2 and 4), supervised
    # by the launcher → restarts, resumes from step 4, finishes 8.
    rc, fault_metrics = _run_worker(tmp_path, "fault", fault=5,
                                    supervised=True)
    assert rc == 0
    got = _read_metrics(fault_metrics)
    assert max(got) == 8 and max(ref) == 8
    # Same losses where both ran (deterministic data+step rng); the faulted
    # run re-executes steps 5.. from the restored step-4 state.
    for s in sorted(set(ref) & set(got)):
        np.testing.assert_allclose(
            got[s]["loss"], ref[s]["loss"], rtol=1e-4,
            err_msg=f"step {s}: resume diverged from uninterrupted run",
        )


DIST_WORKER = """
import os, sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
from pytorch_distributed_train_tpu.launch import initialize_distributed

initialize_distributed()
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 2, jax.device_count()
import numpy as np
from jax.experimental import multihost_utils

rank = jax.process_index()
got = multihost_utils.process_allgather(np.array([rank + 1]))
assert got.tolist() == [[1], [2]], got
with open(os.path.join({out!r}, f"dist-ok-{{rank}}"), "w") as f:
    f.write(str(got.tolist()))
"""


@pytest.mark.slow
def test_multiprocess_jax_distributed_bringup(tmp_path):
    """tpurun env contract → jax.distributed.initialize on loopback: two
    OS processes form one JAX job (SURVEY §3.2 TPU mapping, §4.3)."""
    from pytorch_distributed_train_tpu.elastic import (
        ElasticAgent,
        LaunchConfig,
    )

    script = tmp_path / "dist.py"
    script.write_text(DIST_WORKER.format(repo=REPO, out=str(tmp_path)))
    cfg = LaunchConfig(nprocs=2, max_restarts=0, monitor_interval_s=0.2,
                       env=CPU_ENV)
    rc = ElasticAgent(cfg, [sys.executable, str(script)]).run()
    assert rc == 0
    assert (tmp_path / "dist-ok-0").exists()
    assert (tmp_path / "dist-ok-1").exists()


STALL_WORKER = """
import os, sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
from pytorch_distributed_train_tpu.config import TrainConfig
from pytorch_distributed_train_tpu.trainer import Trainer

cfg = TrainConfig()
cfg.model.name = "resnet18"; cfg.model.num_classes = 10
cfg.model.image_size = 8
cfg.data.dataset = "synthetic_images"; cfg.data.synthetic_size = 256
cfg.data.batch_size = 32; cfg.data.num_workers = 1; cfg.data.prefetch = 2
cfg.optim.name = "momentum"; cfg.optim.learning_rate = 0.05
cfg.optim.schedule = "constant"; cfg.optim.warmup_steps = 0
cfg.total_steps = 8
cfg.checkpoint.dir = {ckpt!r}
cfg.checkpoint.save_every_steps = 2
cfg.checkpoint.async_save = False
cfg.obs.log_every_steps = 1
cfg.obs.jsonl_path = {metrics!r}
# Timeout must exceed first-step compile (the beat only lands at step end);
# the shared compile cache makes generation 1's compile a cache hit, so
# only generation 0 pays it. Production uses minutes here for the same
# reason.
cfg.obs.heartbeat_timeout_s = 30.0
cfg.obs.stall_inject_at_step = 5
cfg.obs.compile_cache_dir = {cache!r}
t = Trainer(cfg)
t.fit()
t.close()
"""


@pytest.mark.slow
def test_stalled_step_dump_abort_restart_resume(tmp_path, capfd):
    """The full stalled-step chain (SURVEY §5.3a, VERDICT r1 item 9): a
    worker WEDGES (not crashes) at step 5 → the heartbeat monitor fires →
    the flight-recorder ring is dumped (stderr + file) → the process
    aborts (exit 134) → the elastic agent gang-restarts → generation 1
    resumes from the step-4 checkpoint and completes all 8 steps. All four
    artifacts are asserted."""
    from pytorch_distributed_train_tpu.elastic import (
        ElasticAgent,
        LaunchConfig,
    )

    ckpt = str(tmp_path / "ckpt")
    metrics = str(tmp_path / "metrics.jsonl")
    script = tmp_path / "worker.py"
    script.write_text(STALL_WORKER.format(repo=REPO, ckpt=ckpt,
                                          metrics=metrics,
                                          cache=str(tmp_path / "xla-cache")))
    cfg = LaunchConfig(nprocs=1, max_restarts=2, monitor_interval_s=0.2,
                       env=CPU_ENV)
    rc = ElasticAgent(cfg, [sys.executable, str(script)]).run()
    out, err = capfd.readouterr()
    assert rc == 0, (rc, err[-800:])

    # 1. the heartbeat fired on the wedged step (worker stderr)
    assert "[heartbeat] no step completed" in err, err[-800:]
    assert "[stall-inject] wedging at step 5" in out
    # 2. the flight-recorder dump was written — to stderr and to the
    #    dump file in the checkpoint dir (dump_dir wiring)
    assert "flight recorder" in err.lower()
    dumps = [f for f in os.listdir(ckpt) if f.startswith("flight_")]
    assert dumps, os.listdir(ckpt)
    with open(os.path.join(ckpt, dumps[0])) as f:
        dump_text = f.read()
    # The ring shows the last COMPLETED step (4) — step 5 wedged before its
    # step-end event, which is precisely the diagnostic a stalled job needs.
    assert "step step=4" in dump_text, dump_text
    assert "step step=5" not in dump_text, dump_text
    # 3. the agent observed the abort and gang-restarted (generation 1)
    assert "gen 1" in out, out[-800:]
    # 4. generation 1 resumed from the checkpoint and completed
    assert "[resume] restored step 4" in out, out[-1500:]
    got = _read_metrics(metrics)
    assert max(got) == 8, sorted(got)


PREEMPT_WORKER = """
import os, sys, time
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
from pytorch_distributed_train_tpu.config import TrainConfig
from pytorch_distributed_train_tpu.trainer import Trainer

cfg = TrainConfig()
cfg.model.name = "resnet18"; cfg.model.num_classes = 10
cfg.model.image_size = 8
cfg.data.dataset = "synthetic_images"; cfg.data.synthetic_size = 2048
cfg.data.batch_size = 16; cfg.data.num_workers = 1
cfg.optim.name = "momentum"; cfg.optim.learning_rate = 0.05
cfg.optim.schedule = "constant"; cfg.optim.warmup_steps = 0
cfg.total_steps = 100000  # far horizon: only SIGTERM ends this run
cfg.checkpoint.dir = {ckpt!r}
cfg.checkpoint.save_every_steps = 10**9  # no cadence saves
cfg.checkpoint.async_save = False
cfg.obs.log_every_steps = 1
cfg.obs.jsonl_path = {metrics!r}
t = Trainer(cfg)
print("TRAINER_READY", flush=True)
t.fit()
"""


@pytest.mark.slow
def test_sigterm_preemption_saves_resumable_checkpoint(tmp_path):
    """GKE-style preemption drill (SURVEY §5.3): SIGTERM mid-training must
    (a) dump the flight recorder, (b) unwind through fit()'s finally and
    write a final checkpoint at the current step — with cadence saves
    disabled, any checkpoint present proves the preemption path wrote it —
    and (c) exit 143 so the supervisor sees a signal death, not success."""
    import signal
    import time

    ckpt = str(tmp_path / "ckpt")
    metrics = str(tmp_path / "metrics.jsonl")
    script = tmp_path / "worker.py"
    script.write_text(PREEMPT_WORKER.format(
        repo=REPO, ckpt=ckpt, metrics=metrics))
    env = {**os.environ, **CPU_ENV, "RESTART_GENERATION": "0"}
    proc = subprocess.Popen([sys.executable, str(script)], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    try:
        # Wait for steps to flow (metrics lines appear), then preempt.
        deadline = time.time() + 300
        while time.time() < deadline:
            if os.path.exists(metrics) and os.path.getsize(metrics) > 0:
                break
            time.sleep(0.5)
        else:
            proc.kill()
            raise AssertionError("no training steps before deadline")
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 143, (proc.returncode, err[-800:])
    assert "flight recorder" in err.lower()
    # The checkpoint written on the way down restores.
    from pytorch_distributed_train_tpu.checkpoint import CheckpointManager
    from pytorch_distributed_train_tpu.config import CheckpointConfig

    mgr = CheckpointManager(CheckpointConfig(dir=ckpt, async_save=False))
    step = mgr.latest_step()
    assert step is not None and step >= 1
    mgr.close()


def test_degraded_mesh_resume_keeps_global_batch(tmp_path, devices8):
    """The training-level half of degraded restart (VERDICT r2 #7): a run
    checkpointed on a 4-device data mesh resumes on a 2-device mesh —
    Orbax reshards the state onto the smaller mesh, the step counter
    continues, the configured GLOBAL batch (and so steps_per_epoch and
    the data order) is unchanged, and training proceeds to the same loss
    trajectory a healthy-world run of equal steps produces."""
    from pytorch_distributed_train_tpu.config import (
        MeshConfig,
        get_preset,
    )
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
    from pytorch_distributed_train_tpu.trainer import Trainer

    def make_cfg(ckpt_dir):
        cfg = get_preset("resnet18_cifar10")
        cfg.apply_overrides([
            *TINY, "data.synthetic_size=128",
            "data.batch_size=32",  # divisible by both world shapes
            f"checkpoint.dir={ckpt_dir}", "checkpoint.save_every_steps=3",
            "eval_every_steps=0", "epochs=0", "obs.log_every_steps=100"])
        return cfg

    def run(cfg, mesh, steps):
        cfg.total_steps = steps
        t = Trainer(cfg, mesh=mesh)
        seen = {}
        orig = t._log_train

        def capture(step, metrics):
            seen[step] = float(np.asarray(metrics["loss"]))
            return orig(step, metrics)

        t._log_train = capture
        cfg.obs.log_every_steps = 1
        t.fit()
        return seen

    # Healthy-world reference: 6 steps on the 4-device mesh.
    ref_cfg = make_cfg(tmp_path / "ref")
    mesh4 = build_mesh(MeshConfig(data=4), devices8[:4])
    ref = run(ref_cfg, mesh4, steps=6)

    # Degraded path: 3 steps on 4 devices (checkpoint at 3), then RESUME
    # on a 2-device mesh for the remaining 3.
    cfg = make_cfg(tmp_path / "deg")
    part1 = run(cfg, mesh4, steps=3)
    mesh2 = build_mesh(MeshConfig(data=2), devices8[:2])
    cfg2 = make_cfg(tmp_path / "deg")
    part2 = run(cfg2, mesh2, steps=6)

    assert max(part1) == 3 and max(part2) == 6
    assert min(part2) == 4, f"resume replayed steps: {sorted(part2)}"
    # Same loss trajectory as the never-degraded run: the global batch,
    # sampler order, and restored state are all world-size independent.
    for s in sorted(set(ref) & set(part2)):
        np.testing.assert_allclose(
            part2[s], ref[s], rtol=1e-4,
            err_msg=f"step {s}: degraded resume diverged")


PREEMPT_RESUME_WORKER = """
import os, sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
from pytorch_distributed_train_tpu.config import TrainConfig
from pytorch_distributed_train_tpu.trainer import Trainer

cfg = TrainConfig()
cfg.model.name = "resnet18"; cfg.model.num_classes = 10
cfg.model.image_size = 8
cfg.data.dataset = "synthetic_images"; cfg.data.synthetic_size = 256
cfg.data.batch_size = 32; cfg.data.num_workers = 1; cfg.data.prefetch = 2
cfg.optim.name = "momentum"; cfg.optim.learning_rate = 0.05
cfg.optim.schedule = "constant"; cfg.optim.warmup_steps = 0
cfg.total_steps = 8
cfg.checkpoint.dir = {ckpt!r}
cfg.checkpoint.save_every_steps = 10**9  # NO cadence saves: only the
# graceful-preemption path can produce the step-5 checkpoint
cfg.checkpoint.async_save = False
cfg.obs.log_every_steps = 1
cfg.obs.jsonl_path = {metrics!r}
cfg.faults.graceful_preemption = True
cfg.faults.inject = ("preempt.sigterm@step=5",)  # gen 0 only (default)
t = Trainer(cfg)
t.fit()
t.close()
sys.exit(cfg.faults.preempt_exit_code if t.preempted else 0)
"""


@pytest.mark.slow
def test_sigterm_preempt_resume_reaches_same_loss(tmp_path):
    """Graceful preemption end-to-end (ISSUE 2 tentpole): SIGTERM (self-
    injected via the fault registry at step 5) must checkpoint AT step 5
    and exit cleanly (rc 0); the restarted generation resumes from 5 —
    one step of loss budget instead of save_every_steps — and reaches
    the same losses as an uninterrupted run (the same-final-loss
    property the hard-kill test pins)."""
    # Uninterrupted reference.
    rc, ref_metrics = _run_worker(tmp_path, "pref", fault=0,
                                  supervised=False)
    assert rc == 0
    ref = _read_metrics(ref_metrics)

    ckpt = str(tmp_path / "ckpt-preempt")
    metrics = str(tmp_path / "metrics-preempt.jsonl")
    script = tmp_path / "worker-preempt.py"
    script.write_text(PREEMPT_RESUME_WORKER.format(
        repo=REPO, ckpt=ckpt, metrics=metrics))

    # Generation 0: preempted at step 5, checkpoints, exits cleanly.
    env = {**os.environ, **CPU_ENV, "RESTART_GENERATION": "0"}
    r = subprocess.run([sys.executable, str(script)], env=env, timeout=600,
                       capture_output=True, text=True)
    assert r.returncode == 0, (r.returncode, r.stderr[-800:])
    assert "[preempt] SIGTERM received" in r.stdout, r.stdout[-800:]
    assert "[preempt] stopping at step 5" in r.stdout, r.stdout[-800:]
    # the chained watchdog handler still dumped diagnostics on the way
    assert "flight recorder" in r.stderr.lower()

    # The ONLY checkpoint is the preemption save at step 5, verified.
    from pytorch_distributed_train_tpu.checkpoint import CheckpointManager
    from pytorch_distributed_train_tpu.config import CheckpointConfig
    from pytorch_distributed_train_tpu.faults import integrity

    mgr = CheckpointManager(CheckpointConfig(dir=ckpt, async_save=False))
    assert mgr.latest_good_step() == 5
    assert integrity.verify_step(mgr.dir, 5)[0] is True
    mgr.close()

    # "tpurun restart": generation 1 resumes from 5 and completes 8.
    env["RESTART_GENERATION"] = "1"
    r2 = subprocess.run([sys.executable, str(script)], env=env, timeout=600,
                        capture_output=True, text=True)
    assert r2.returncode == 0, (r2.returncode, r2.stderr[-800:])
    assert "[resume] restored step 5" in r2.stdout, r2.stdout[-800:]

    got = _read_metrics(metrics)  # jsonl appends across both generations
    assert max(got) == 8 and max(ref) == 8
    # summary rows: gen 0 preempted=1, gen 1 preempted=0
    flags = []
    with open(metrics) as f:
        for line in f:
            row = json.loads(line)
            if row.get("tag") == "summary":
                flags.append(row.get("preempted"))
    assert flags == [1, 0], flags
    for s in sorted(set(ref) & set(got)):
        np.testing.assert_allclose(
            got[s]["loss"], ref[s]["loss"], rtol=1e-4,
            err_msg=f"step {s}: preempt-resume diverged from "
                    "uninterrupted run",
        )
