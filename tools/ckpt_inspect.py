#!/usr/bin/env python
"""Inspect a checkpoint directory across every tier of the checkpoint
plane (docs/checkpointing.md): persistent Orbax steps with their
integrity-manifest verdicts, per-host hot-disk snapshots with their
seal/CRC status, and what the retention policy would (not) evict.

    python tools/ckpt_inspect.py --dir runs/exp1/ckpt
    python tools/ckpt_inspect.py --dir runs/exp1/ckpt --hot-keep 2 --keep-every 1000
    python tools/ckpt_inspect.py --dir runs/exp1/ckpt --mesh data=2,fsdp=3

``--mesh AXIS=N[,AXIS=N...]`` answers the elastic-reshard feasibility
question (docs/elastic.md): can each tier restore onto THAT mesh? The
newest verified persistent step's leaves are checked against the
partition rules of the checkpoint's own saved config (dims the mesh
cannot divide are listed as replication fallbacks — restore still
works, those dims just replicate); hot snapshots are host-side global
leaves, mesh-agnostic by construction; and the report names the tier a
reshard-restore would land on.

Read-only: nothing is deleted, verified-on-read only (the same checks a
restore performs). Exit 0 when the directory parses — an operator
answering "what would a restore land on right now?" should not need a
Python REPL.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{n} B"
        n /= 1024.0
    return f"{n:.1f} GiB"


def _dir_bytes(path: str) -> int:
    total = 0
    for dirpath, _, names in os.walk(path):
        for name in names:
            try:
                total += os.path.getsize(os.path.join(dirpath, name))
            except OSError:
                pass
    return total


def inspect_dir(root: str, *, hot_keep: int = 2, keep_every: int = 0,
                out=None) -> dict:
    """Gather + print the report; returns the structured form (tests).
    ``out`` resolves to sys.stdout at CALL time — an import-time default
    would freeze whatever stream happened to be installed when the
    module loaded (pytest's per-test capture, a redirect)."""
    out = out if out is not None else sys.stdout
    from pytorch_distributed_train_tpu.ckpt import hot_tier, retention
    from pytorch_distributed_train_tpu.faults import integrity

    report: dict = {"dir": root, "persistent": [], "hot": {}}
    print(f"checkpoint dir: {root}", file=out)

    # ---- persistent tier (Orbax step dirs + manifests)
    steps = []
    if os.path.isdir(root):
        for name in os.listdir(root):
            if name.isdigit() and os.path.isdir(os.path.join(root, name)):
                steps.append(int(name))
    newest_verified = None
    print(f"\npersistent tier ({len(steps)} steps):", file=out)
    for s in sorted(steps):
        ok, reason = integrity.verify_step(root, s)
        verdict = ("verified" if ok else
                   "trusted (pre-manifest)" if ok is None else
                   f"CORRUPT: {reason}")
        if ok:
            newest_verified = s
        size = _dir_bytes(integrity.step_dir(root, s))
        report["persistent"].append(
            {"step": s, "verdict": verdict, "bytes": size})
        print(f"  step {s:>10}  {_fmt_bytes(size):>10}  {verdict}",
              file=out)
    if not steps:
        print("  (none)", file=out)

    # ---- hot disk tier(s): <root>/hot/host_<n>
    hot_root = os.path.join(root, "hot")
    hosts = []
    if os.path.isdir(hot_root):
        hosts = sorted(n for n in os.listdir(hot_root)
                       if n.startswith("host_"))
    for host in hosts:
        tier = hot_tier.DiskTier(os.path.join(hot_root, host))
        rows = []
        print(f"\nhot disk tier [{host}] "
              f"({len(tier.steps())} steps):", file=out)
        pins = set()
        if newest_verified is not None:
            pins.add(newest_verified)
        sealed = tier.sealed_steps()
        if sealed:
            pins.add(sealed[-1])
        evict = set(retention.plan_evictions(
            tier.steps(), keep_last=hot_keep, keep_every=keep_every,
            pinned=pins))
        for s in tier.steps():
            ok = tier.load(s) is not None  # CRC-verified read
            header = tier.header(s) or {}
            status = ("sealed+verified" if ok else
                      "sealed but CORRUPT" if header.get("sealed") else
                      "unsealed")
            pin = ("PINNED" if s in pins else
                   "evictable" if s in evict else "kept")
            size = tier.step_nbytes(s)
            rows.append({"step": s, "status": status, "gc": pin,
                         "bytes": size})
            print(f"  step {s:>10}  {_fmt_bytes(size):>10}  "
                  f"{status:<20} gc={pin}", file=out)
        if not tier.steps():
            print("  (none)", file=out)
        report["hot"][host] = rows
    if not hosts:
        print("\nhot disk tier: (none)", file=out)

    # ---- the answer an operator actually wants
    hot_best = max((r["step"] for rows in report["hot"].values()
                    for r in rows if r["status"] == "sealed+verified"),
                   default=None)
    cands = [c for c in (newest_verified, hot_best) if c is not None]
    landing = max(cands) if cands else None
    report["newest_verified_persistent"] = newest_verified
    report["newest_sealed_hot"] = hot_best
    report["restore_would_land_on"] = landing
    print(f"\nnewest verified persistent step: {newest_verified}",
          file=out)
    print(f"newest sealed hot step:          {hot_best}", file=out)
    print(f"a restore now would land on:     {landing}", file=out)
    return report


def parse_mesh(text: str) -> dict[str, int]:
    """``"data=2,fsdp=3"`` → axis-size dict (unnamed axes default 1)."""
    from pytorch_distributed_train_tpu.parallel.mesh import MESH_AXES

    sizes: dict[str, int] = {}
    for part in filter(None, (p.strip() for p in text.split(","))):
        if "=" not in part:
            raise ValueError(
                f"--mesh clause {part!r}: expected AXIS=N "
                f"(axes: {list(MESH_AXES)})")
        ax, _, val = part.partition("=")
        ax = ax.strip()
        if ax not in MESH_AXES:
            raise ValueError(
                f"--mesh names unknown axis {ax!r} (axes: {list(MESH_AXES)})")
        n = int(val)
        if n < 1:
            raise ValueError(f"--mesh {ax}={n}: sizes must be >= 1")
        sizes[ax] = n
    if not sizes:
        raise ValueError("--mesh needs at least one AXIS=N clause")
    return sizes


def mesh_feasibility(root: str, sizes: dict[str, int], *,
                     step: int | None = None, out=None) -> dict:
    """Can each tier of ``root`` restore onto a mesh of ``sizes``?

    Persistent tier: leaf-by-leaf divisibility against the partition
    rules of the checkpoint's OWN saved config (the same
    rules_for_model + validate_spec path a resharded restore takes —
    parallel/partition.py). Hot tiers: host-side global leaves,
    mesh-agnostic. Returns the structured report (tests). ``out``
    resolves to sys.stdout at CALL time (see inspect_dir)."""
    out = out if out is not None else sys.stdout
    from pytorch_distributed_train_tpu import checkpoint as checkpoint_lib
    from pytorch_distributed_train_tpu.config import (
        CheckpointConfig,
        TrainConfig,
    )
    from pytorch_distributed_train_tpu.parallel import partition

    report: dict = {"mesh": dict(sizes), "feasible": None, "leaves": 0,
                    "fallback_leaves": [], "notes": []}
    print(f"\nreshard feasibility onto mesh {sizes}:", file=out)
    mgr = checkpoint_lib.CheckpointManager(
        CheckpointConfig(dir=root, resume="none"), "")
    try:
        if step is None:
            step = mgr.latest_good_step()
        if step is None:
            print("  persistent tier: no verified step — nothing to "
                  "reshard", file=out)
            report["notes"].append("no verified persistent step")
            return report
        report["step"] = int(step)
        meta = mgr.read_meta(step)
        try:
            model_name = TrainConfig.from_json(meta.get("config") or
                                               "{}").model.name
        except Exception:
            model_name = ""
        rules = partition.rules_for_model(model_name or "dense")
        import jax.tree_util as jtu
        import orbax.checkpoint as ocp

        from pytorch_distributed_train_tpu.utils import compat

        # the raw metadata object would flatten as one shapeless leaf
        # and every divisibility check would silently vanish
        try:
            state_meta = compat.pytree_metadata_tree(
                ocp, os.path.join(root, str(step), "state"))
            flat, _ = jtu.tree_flatten_with_path(state_meta)
        except Exception as e:
            # read-only operator tool: an unreadable step is a report
            # line ("exit 0 when the directory parses"), not a crash
            print(f"  persistent step {step}: state metadata unreadable "
                  f"({type(e).__name__}: {e}) — leaf divisibility "
                  "unknown", file=out)
            report["notes"].append("state metadata unreadable")
            flat = None
        fallbacks = []
        n_leaves = 0
        for path, leaf in flat or []:
            shape = tuple(getattr(leaf, "shape", ()) or ())
            name = partition.path_name(path)
            n_leaves += 1
            try:
                spec = rules.spec_for(name, shape)
            except ValueError:
                continue  # no rule matched: restores replicated
            dims = partition.replication_fallback_dims(spec, shape, sizes)
            if dims:
                fallbacks.append({"leaf": name, "shape": list(shape),
                                  "spec": str(spec), "dims": dims})
        report["leaves"] = n_leaves
        report["fallback_leaves"] = fallbacks
        # validate_spec replicates instead of failing, so a readable
        # step is always feasible; unreadable metadata stays None
        report["feasible"] = True if flat is not None else None
        world = meta.get("world")
        gb = meta.get("global_batch")
        print(f"  persistent step {step} (model {model_name or '?'}, "
              f"written on world {world}): {n_leaves} leaves, "
              f"{len(fallbacks)} would fall back to replication", file=out)
        for fb in fallbacks[:10]:
            print(f"    {fb['leaf']} shape {tuple(fb['shape'])} spec "
                  f"{fb['spec']}: dims {fb['dims']} not divisible",
                  file=out)
        if len(fallbacks) > 10:
            print(f"    ... and {len(fallbacks) - 10} more", file=out)
        if gb:
            shards = 1
            for ax in ("data", "fsdp"):
                shards *= sizes.get(ax, 1)
            ok = int(gb) % shards == 0
            report["batch_divisible"] = ok
            print(f"  global batch {gb} over {shards} batch shards "
                  f"(data x fsdp): {'OK' if ok else 'NOT DIVISIBLE'}",
                  file=out)
        # hot tiers: inventory of host-side GLOBAL leaves — a restore
        # device_puts them into whatever shardings the new mesh derives
        hot_root = os.path.join(root, "hot")
        hosts = (sorted(n for n in os.listdir(hot_root)
                        if n.startswith("host_"))
                 if os.path.isdir(hot_root) else [])
        sealed_hot = None
        for host in hosts:
            from pytorch_distributed_train_tpu.ckpt import hot_tier

            tier = hot_tier.DiskTier(os.path.join(hot_root, host))
            good = tier.sealed_steps()
            if good:
                sealed_hot = max(sealed_hot or 0, good[-1])
        if sealed_hot is not None:
            print(f"  hot tier: sealed step {sealed_hot} holds host-side "
                  "global leaves — restorable onto ANY mesh shape "
                  "(device_put reshards at placement)", file=out)
        report["newest_sealed_hot"] = sealed_hot
        landing = max([s for s in (step, sealed_hot) if s is not None])
        report["reshard_would_land_on"] = landing
        tier_name = ("hot" if sealed_hot is not None and sealed_hot >= step
                     else "orbax (reshard-on-restore)")
        print(f"  a reshard-restore would land on step {landing} via the "
              f"{tier_name} tier (peer tier lives on the LIVE launcher "
              "store — not visible to this offline inspection; a running "
              "gang may land on a newer peer-advertised step)", file=out)
        return report
    finally:
        mgr.close()


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        description="Inspect checkpoint tiers, manifest verdicts, and "
                    "retention-pin status.")
    p.add_argument("--dir", required=True, help="checkpoint directory")
    p.add_argument("--hot-keep", type=int, default=2,
                   help="retention keep-last-N to evaluate pins against")
    p.add_argument("--keep-every", type=int, default=0,
                   help="retention keep-every-K to evaluate pins against")
    p.add_argument("--mesh", default="",
                   help="AXIS=N[,AXIS=N...] — report whether each tier "
                        "can restore onto that mesh (reshard "
                        "feasibility; docs/elastic.md)")
    args = p.parse_args(argv)
    if not os.path.isdir(args.dir):
        print(f"ckpt_inspect: no such directory: {args.dir}",
              file=sys.stderr)
        return 1
    inspect_dir(args.dir, hot_keep=args.hot_keep,
                keep_every=args.keep_every)
    if args.mesh:
        try:
            sizes = parse_mesh(args.mesh)
        except ValueError as e:
            print(f"ckpt_inspect: {e}", file=sys.stderr)
            return 2
        mesh_feasibility(args.dir, sizes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
