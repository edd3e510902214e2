"""Orbax-backed checkpoint/resume (SURVEY §5.4, C21/C22).

Replaces both reference paths with one mechanism:
- rank-0 ``torch.save({'model','optim','epoch'})`` (torch:serialization.py:944)
- sharded DCP save/load (torch:distributed/checkpoint/state_dict_saver.py:89)

Orbax writes every host's param shards in parallel via TensorStore, saves
asynchronously (step N+1 trains while N persists — no rank-0 bottleneck or
barrier stall, SURVEY §3.5), and reshards on restore when the mesh changed
(the FSDP→GSPMD resharding requirement, BASELINE.json:11).

``resume='auto'`` restores the latest step when the directory has one — the
default path, because TPU elasticity is whole-job-restart-and-resume
(SURVEY §5.3b), not per-rank recovery.
"""

from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import orbax.checkpoint as ocp

from pytorch_distributed_train_tpu.faults import integrity
from pytorch_distributed_train_tpu.faults import registry as faults_registry
from pytorch_distributed_train_tpu.faults import retry as retry_lib
from pytorch_distributed_train_tpu.obs.spans import span
from pytorch_distributed_train_tpu.train_state import TrainState
from pytorch_distributed_train_tpu.utils import compat


class CheckpointManager:
    def __init__(self, ckpt_cfg, config_json: str = "", *,
                 run_meta: dict | None = None):
        self.cfg = ckpt_cfg
        path = os.path.abspath(ckpt_cfg.dir)
        os.makedirs(path, exist_ok=True)
        self.dir = path
        options = ocp.CheckpointManagerOptions(
            max_to_keep=ckpt_cfg.max_to_keep,
            enable_async_checkpointing=ckpt_cfg.async_save,
        )
        self.mgr = ocp.CheckpointManager(path, options=options)
        self.config_json = config_json
        # Folded into every step's meta JSON. The elastic-reshard plane
        # records {world, global_batch} here so a resumed generation can
        # detect a topology change (trainer emits the reshard event) and
        # refuse a silently-different global batch (docs/elastic.md).
        self.run_meta = dict(run_meta or {})

    # ------------------------------------------------------------------ save
    def save(self, state: TrainState, *, epoch: int = 0, force: bool = False,
             step: int | None = None, overwrite: bool = False,
             extra_meta: dict | None = None) -> bool:
        # Callers that track the step host-side pass it in — int(state.step)
        # is a device sync that would serialize async dispatch (trainer hot
        # loop keeps its own counter for exactly this reason).
        if step is None:
            step = int(state.step)
        if step in self.mgr.all_steps():
            if not overwrite:
                # Cadence already wrote this step — keep it. (force only
                # bypasses Orbax's should_save, never an existing ckpt: the
                # trainer's final force-save must not delete-and-rewrite a
                # checkpoint an async cadence save may still be writing.)
                return False
            # overwrite (BestCheckpointTracker re-improving at a step this
            # manager already holds): Orbax refuses to save over an
            # existing step, so wait out any in-flight write and drop it.
            self.mgr.wait_until_finished()
            self.mgr.delete(step)
        meta = {"epoch": epoch, "config": self.config_json,
                **self.run_meta, **(extra_meta or {})}
        # The span covers the BLOCKING portion only: under async_save the
        # TensorStore writes continue past it (their tail shows up in
        # checkpoint.wait spans) — exactly the host-stall attribution the
        # goodput ckpt bucket wants.
        def _do_save():
            # `ckpt.save_io` fault point: an armed schedule raises an
            # InjectedFault(OSError) here, exercising the same
            # retry/backoff path a real transient write error takes.
            faults_registry.maybe_fire("ckpt.save_io", step=step)
            return self.mgr.save(
                step,
                args=ocp.args.Composite(
                    state=ocp.args.StandardSave(_savable(state)),
                    meta=ocp.args.JsonSave(meta),
                ),
                force=force,
            )

        with span("checkpoint.save", step=step):
            saved = retry_lib.retry_call(_do_save, point="ckpt.save_io")
        # Manifests for steps whose commit already landed (this one, when
        # saving synchronously; earlier ones under async — Orbax waits
        # out the previous in-flight write before starting a new one).
        self._finalize_manifests()
        return bool(saved)

    def maybe_save(self, state: TrainState, *, epoch: int = 0,
                   step: int | None = None) -> bool:
        if step is None:
            step = int(state.step)
        if self.cfg.save_every_steps and step % self.cfg.save_every_steps == 0:
            return self.save(state, epoch=epoch, step=step)
        return False

    # ------------------------------------------------------------ integrity
    def _finalize_manifests(self) -> None:
        """Write manifests for committed-but-unmanifested steps and prune
        manifests of garbage-collected ones. Idempotent and cheap when
        nothing changed; called after save/wait/close so an async commit
        always gets its manifest at the next opportunity."""
        if not getattr(self.cfg, "integrity", True):
            return
        try:
            steps = self.mgr.all_steps()
        except Exception:
            return  # manager already closed — nothing to finalize
        integrity.prune_manifests(self.dir, steps)
        for s in steps:
            # all_steps() lists an in-flight async save whose directory
            # is still tmp-named; skip it — the next call picks it up.
            if integrity.has_manifest(self.dir, s):
                continue
            if not integrity.step_committed(self.dir, s):
                continue
            try:
                integrity.write_manifest(self.dir, s, self.config_json)
            except OSError as e:  # manifest failure must not fail the run
                print(f"[ckpt] manifest write for step {s} failed: {e}",
                      flush=True)

    # --------------------------------------------------------------- restore
    def latest_step(self) -> int | None:
        return self.mgr.latest_step()

    def latest_good_step(self) -> int | None:
        """Newest step that passes integrity verification, falling back
        past partial/corrupt steps (each skip is logged and counted —
        a resume that silently lands N*save_every steps earlier than
        the operator believes is its own kind of fault)."""
        if not getattr(self.cfg, "integrity", True):
            return self.latest_step()
        from pytorch_distributed_train_tpu.obs.registry import get_registry

        for s in sorted(self.mgr.all_steps(), reverse=True):
            if not integrity.step_committed(self.dir, s):
                continue  # in-flight async save, not a corruption
            ok, reason = integrity.verify_step(self.dir, s)
            if ok is None or ok:
                return s  # verified, or pre-manifest (trusted)
            get_registry().counter(
                "ckpt_integrity_failures_total",
                help="checkpoint steps skipped on restore after failing "
                     "manifest verification").inc()
            print(f"[ckpt] step {s} failed integrity check ({reason}); "
                  f"falling back to an earlier checkpoint", flush=True)
        return None

    def restore(self, abstract_state: TrainState, step: int | None = None
                ) -> tuple[TrainState, dict] | None:
        """Restore into the sharding/dtype layout of ``abstract_state``
        (jax.eval_shape + shardings) — reshard-on-restore falls out of
        Orbax restoring to the target sharding. With no explicit step,
        restores the newest INTEGRITY-VERIFIED step (an explicit step is
        restored as asked — the caller is overriding the fallback)."""
        if step is None:
            step = self.latest_good_step()
        if step is None:
            return None
        template = _savable(abstract_state)
        if "ema_params" in template and not self._ckpt_has(step, "ema_params"):
            # ckpt written before EMA was enabled: restore without the
            # mirror, re-seed it from params below
            template.pop("ema_params")
        if "swa_count" in template and not self._ckpt_has(step, "swa_count"):
            template.pop("swa_count")  # pre-SWA ckpt: count restarts at 0
        if ("ema_batch_stats" in template
                and not self._ckpt_has(step, "ema_batch_stats")):
            # ckpt from before the stats mirror existed: re-seed below
            template.pop("ema_batch_stats")
        with span("checkpoint.restore", step=step):
            restored = self.mgr.restore(
                step,
                args=ocp.args.Composite(
                    state=ocp.args.StandardRestore(template),
                    meta=ocp.args.JsonRestore(),
                ),
            )
        state = apply_restored(abstract_state, restored["state"])
        return state, (restored["meta"] or {})

    def restore_partial(self, item: dict,
                        step: int | None = None) -> dict | None:
        """Restore only the named subtrees of a saved TrainState (e.g.
        ``{"params": ..., "batch_stats": ...}``). Template leaves carry
        target shapes/dtypes/shardings, so arrays land directly in the
        caller's mesh layout; every subtree NOT named (opt_state, the EMA
        mirror — 2-3x params for adam at 7B) is never deserialized."""
        if step is None:
            step = self.latest_good_step()
        if step is None:
            return None
        # partial_restore=True returns the TEMPLATE LEAVES UNCHANGED for
        # keys absent from the checkpoint (no error) — refuse up front,
        # otherwise a caller naming e.g. 'ema_params' against a non-EMA
        # checkpoint would get ShapeDtypeStructs where arrays belong.
        saved = self.saved_state_keys(step)
        missing = set(item) - saved if saved is not None else set()
        if missing:
            raise KeyError(
                f"checkpoint step {step} in {self.dir} has no "
                f"{sorted(missing)} (saved keys: {sorted(saved)})")
        item_dir = os.path.join(self.dir, str(step), "state")
        ckptr = ocp.PyTreeCheckpointer()
        # construct_restore_args carries the template's shardings into the
        # deserializer; without it PyTreeRestore silently restores every
        # array single-device (an all-gather-to-chip-0 OOM at 7B).
        restore_args = ocp.checkpoint_utils.construct_restore_args(item)
        return ckptr.restore(
            item_dir,
            args=compat.pytree_restore_args(ocp, item, restore_args),
        )

    def restore_params_only(self, abstract_params: Any,
                            step: int | None = None) -> Any | None:
        """Restore just the ``params`` subtree — the LoRA warm-start path
        (config ``lora.base_checkpoint``), where the source run's
        optimizer state is meaningless to the new run (different optax
        tree once the adapter mask wraps it)."""
        restored = self.restore_partial({"params": abstract_params}, step)
        return None if restored is None else restored["params"]

    def saved_state_keys(self, step: int) -> set[str] | None:
        """Top-level keys of the saved state tree at ``step`` (read from
        the item's own pytree metadata — the manager's item_metadata needs
        a handler registry this codepath doesn't keep), or None when the
        metadata cannot be read."""
        try:
            return compat.pytree_metadata_keys(
                ocp, os.path.join(self.dir, str(step), "state"))
        except Exception:
            return None

    def _ckpt_has(self, step: int, key: str) -> bool:
        """Whether the saved state tree at ``step`` contains ``key``."""
        keys = self.saved_state_keys(step)
        if keys is None:
            return True  # metadata unavailable → assume matching layout
        return key in keys

    def read_meta(self, step: int | None = None) -> dict:
        """Read just the JSON meta of a saved step (no state restore) —
        used to recover the best-metric watermark across restarts."""
        if step is None:
            step = self.latest_step()
        if step is None:
            return {}
        try:
            restored = self.mgr.restore(
                step, args=ocp.args.Composite(meta=ocp.args.JsonRestore()))
            return restored["meta"] or {}
        except Exception:
            return {}

    def wait(self) -> None:
        with span("checkpoint.wait"):
            self.mgr.wait_until_finished()
        self._finalize_manifests()

    def close(self) -> None:
        self.mgr.wait_until_finished()
        self._finalize_manifests()
        self.mgr.close()


class BestCheckpointTracker:
    """`model_best.pth` semantics (reference-genre harnesses: save when the
    validation metric improves). A second Orbax manager under
    ``<dir>/best`` with max_to_keep=1; the watermark survives restarts via
    the meta JSON. Resume-from-latest is untouched — this is an export/eval
    artifact, not the recovery path."""

    def __init__(self, ckpt_cfg, config_json: str = ""):
        import dataclasses as _dc

        self.metric = ckpt_cfg.best_metric
        self.mode = ckpt_cfg.best_mode
        if self.mode not in ("max", "min"):
            raise ValueError(f"best_mode must be max|min, got {self.mode!r}")
        best_cfg = _dc.replace(
            ckpt_cfg, dir=os.path.join(ckpt_cfg.dir, "best"), max_to_keep=1)
        self.mgr = CheckpointManager(best_cfg, config_json)
        # The watermark carries over only on a resuming run AND only if it
        # measures the same thing: resume="none" is a fresh run (a reused
        # dir must not pin the old run's best), and a reconfigured
        # metric/mode must not compare new losses against an old accuracy.
        # Fresh watermark → the first eval overwrites the stale best.
        meta = self.mgr.read_meta() if ckpt_cfg.resume != "none" else {}
        if (meta.get("best_metric"), meta.get("best_mode")) == (
                self.metric, self.mode):
            self.best_value: float | None = meta.get("best_value")
        else:
            self.best_value = None

    def _improved(self, value: float) -> bool:
        if self.best_value is None:
            return True
        return (value > self.best_value if self.mode == "max"
                else value < self.best_value)

    _closed = False

    def update(self, eval_metrics: dict, state: TrainState, *, epoch: int,
               step: int) -> bool:
        """Save iff ``eval_metrics[metric]`` improves. Missing metric is an
        error — a silent typo in best_metric would track nothing."""
        if self.metric not in eval_metrics:
            raise KeyError(
                f"checkpoint.best_metric={self.metric!r} not in eval "
                f"metrics {sorted(eval_metrics)}")
        value = float(eval_metrics[self.metric])
        if not self._improved(value):
            return False
        self.best_value = value
        # One save path (CheckpointManager.save); force=True because a
        # repeat eval can improve at a step number this manager already
        # holds.
        self.mgr.save(
            state, epoch=epoch, step=step, force=True, overwrite=True,
            extra_meta={"best_value": value, "best_metric": self.metric,
                        "best_mode": self.mode})
        return True

    def close(self) -> None:
        # Idempotent: both fit()'s finally and Trainer.close() call this.
        if not self._closed:
            self._closed = True
            self.mgr.close()


def apply_restored(abstract_state: TrainState, sav: dict) -> TrainState:
    """Rebuild a TrainState from a restored ``_savable`` dict, using
    ``abstract_state`` for structure (opt_state treedef, which optional
    mirrors exist). Shared by the Orbax restore above and the hot-tier
    restores in ckpt/manager.py — both hand back the same dict shape,
    so the resume semantics (mirror re-seeding, pre-SWA back-compat)
    cannot drift between tiers."""
    state = abstract_state.replace(
        step=sav["step"],
        params=sav["params"],
        opt_state=_merge_opt_state(abstract_state.opt_state,
                                   sav["opt_state"]),
        batch_stats=sav["batch_stats"],
    )
    if abstract_state.ema_params is not None:
        # Resume with EMA on: restore the mirror; a ckpt written before
        # EMA was enabled has no mirror — re-seed from restored params.
        state = state.replace(
            ema_params=sav.get("ema_params", sav["params"]))
    if getattr(abstract_state, "ema_batch_stats", None) is not None:
        # Stats mirror: older ckpts re-seed from the trajectory stats
        # (the pre-mirror eval behavior, converging under the decay).
        state = state.replace(
            ema_batch_stats=sav.get("ema_batch_stats",
                                    sav["batch_stats"]))
    if getattr(abstract_state, "swa_count", None) is not None:
        # Without this the resumed running mean would weight its next
        # snapshot 1/1 and erase every pre-restart fold.
        state = state.replace(
            swa_count=sav.get("swa_count", jnp.int32(0)))
    if abstract_state.dynamic_scale is not None and "dynamic_scale" in sav:
        state = state.replace(
            dynamic_scale=abstract_state.dynamic_scale.replace(
                **sav["dynamic_scale"]))
    return state


def _savable(state: TrainState) -> dict[str, Any]:
    """TrainState → plain dict pytree (drops the non-pytree tx; keeps a
    stable state_dict-like naming scheme for cross-framework legibility —
    SURVEY §7.4.2). A dict passes through unchanged: the tiered plane
    (ckpt/manager.py) snapshots the savable form once at the step
    boundary and hands the host copy back here for the background Orbax
    persist."""
    if isinstance(state, dict):
        return dict(state)
    d = {
        "step": state.step,
        "params": state.params,
        "opt_state": state.opt_state,
        "batch_stats": state.batch_stats,
    }
    if state.ema_params is not None:
        d["ema_params"] = state.ema_params
    if getattr(state, "ema_batch_stats", None) is not None:
        d["ema_batch_stats"] = state.ema_batch_stats
    if getattr(state, "swa_count", None) is not None:
        d["swa_count"] = state.swa_count
    if state.dynamic_scale is not None:
        d["dynamic_scale"] = {
            "scale": state.dynamic_scale.scale,
            "growth_tracker": state.dynamic_scale.growth_tracker,
        }
    return d


def _merge_opt_state(abstract_opt, restored_opt):
    """Opt state round-trips as nested lists/dicts; rebuild the original
    structure (NamedTuples etc.) from the restored leaves."""
    leaves = jax.tree_util.tree_leaves(restored_opt)
    treedef = jax.tree_util.tree_structure(abstract_opt)
    return jax.tree_util.tree_unflatten(treedef, leaves)


