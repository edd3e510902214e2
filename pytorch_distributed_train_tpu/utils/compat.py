"""The installed spellings of the few JAX / orbax calls the codebase
shares, kept in one place.

``shard_map``: public ``jax.shard_map``; ``check_vma`` / ``axis_names``
are forwarded only when the caller sets them, so JAX's defaults apply.

``pytree_restore_args``: orbax spells partial restore as
``PyTreeRestore(..., partial_restore=True)`` — ``item`` names only the
subtrees to restore and nothing else in the checkpoint is deserialized.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs,
              check_vma: bool | None = None, axis_names=None):
    kw = {}
    if check_vma is not None:
        kw["check_vma"] = check_vma
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def pytree_metadata_tree(ocp, item_dir: str) -> dict:
    """A saved pytree item's metadata TREE (leaves expose .shape/.dtype).
    Raises whatever the underlying reader raises — the caller decides
    whether unreadable metadata is an error or a "trust the layout"
    fallback."""
    meta = ocp.PyTreeCheckpointer().metadata(item_dir)
    return dict(meta.item_metadata.tree)


def pytree_metadata_keys(ocp, item_dir: str) -> set[str]:
    """Top-level keys of a saved pytree item."""
    return set(pytree_metadata_tree(ocp, item_dir).keys())


def pytree_restore_args(ocp, item, restore_args):
    """``ocp.args.PyTreeRestore`` for a PARTIAL restore: ``item`` names
    only the subtrees to restore."""
    return ocp.args.PyTreeRestore(item=item, restore_args=restore_args,
                                  partial_restore=True)
