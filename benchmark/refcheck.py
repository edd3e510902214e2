"""What every configuration's plain reference shares: per-leaf norms, the
worst-leaf comparison the contract asks for, and the lower-precision rounding
the control uses. Imports nothing of the program."""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp


def leaf_norms(tree) -> dict:
    """{path: L2 norm} of every leaf, as float32 device scalars."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in flat}


def leaf_gaps(program: dict, reference: dict,
              skip: frozenset = frozenset()) -> dict:
    """{leaf: |program's norm - reference's norm|, against the reference's
    norm of that leaf or of the median leaf, whichever is larger} (some
    gradients are all but zero)."""
    ref = {k: float(v) for k, v in reference.items()}
    if set(ref) != set(program):
        raise ValueError("program and reference have different leaves: "
                         f"{sorted(set(ref) ^ set(program))[:4]}")
    floor = statistics.median(ref.values())
    return {k: abs(float(program[k]) - r) / max(r, floor, 1e-30)
            for k, r in ref.items() if k not in skip}


def rounder(precision: str):
    """Operand rounding for every matrix product and convolution.
    ``float32``: none (the reference). ``fp8``: each operand scaled by its
    largest magnitude into float8_e4m3fn's range and back, products
    accumulated in float32 — the precision below bfloat16 that a later PR
    would be tempted by; straight-through for the gradient."""
    if precision == "float32":
        return lambda x: x
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}")

    def q(x):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        r = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
        return x + jax.lax.stop_gradient(r - x)

    return q


def noise_leaves(grad_norms_by_step: list[dict], below: float = 1e-3) -> frozenset:
    """Leaves whose gradient the reference finds all but zero at every step
    followed (under ``below`` x the median leaf's): an Adam-style optimizer
    normalises their rounding noise into full-size updates, so the size of
    their change says nothing about the step and is left out of it."""
    top = {k: max(float(g[k]) for g in grad_norms_by_step)
           for k in grad_norms_by_step[0]}
    floor = below * statistics.median(top.values())
    return frozenset(k for k, v in top.items() if v < floor)


def rounding_leaves(change_norms: dict, param_norms: dict,
                    ulps: float = 8.0) -> frozenset:
    """Leaves that moved by no more than a few float32 units in the last
    place of their own values (warm-up steps are that small for some): which
    way each element rounds is then chance, in the program and the reference
    alike, and the size of the change says nothing about the step."""
    eps = 2.0 ** -24
    return frozenset(k for k, c in change_norms.items()
                     if float(c) < ulps * eps * float(param_norms[k]))


def is_matrix(leaf: str) -> bool:
    """Weight matrices and embeddings, by the leaf's name."""
    return leaf.endswith("['kernel']") or leaf.endswith("['embedding']")


def compare_steps(ref: dict, observed: dict, limits: dict) -> list:
    """The numbers a run compares, each beside its limit (None: printed for
    the record, not judged). ``ref`` is a reference's ``follow`` result,
    ``observed`` the program's losses and per-leaf norms. The first gradient
    is judged by its worst leaf over the weight matrices: bias and norm-layer
    gradients are sums over 10^4 to 10^6 positions, unsteady in bfloat16, and
    over all leaves no limit parts sound runs from the control (PERF.md
    section 2); that number is printed beside it."""
    out = [{"name": f"loss_gap_step{i + 1}",
            "value": abs(observed["losses"][i] - ref["losses"][i]),
            "limit": limits["loss_gap"]}
           for i in range(len(ref["losses"]))]

    def worst(table, only=lambda k: True):
        best, where = 0.0, ""
        for k, gap in table.items():
            if only(k) and not gap <= best:  # NaN counts as the worst
                best, where = gap, k
        return best, where

    grads = leaf_gaps(observed["first_grad"], ref["first_grad"])
    change = leaf_gaps(observed["param_change"], ref["param_change"],
                       ref["noise_leaves"])
    for name, (gap, leaf), limit in (
            ("first_grad_worst_leaf", worst(grads), None),
            ("first_grad_worst_matrix_leaf", worst(grads, is_matrix),
             limits["first_grad_worst_matrix_leaf"]),
            ("param_change_worst_leaf", worst(change),
             limits["param_change_worst_leaf"])):
        out.append({"name": name, "value": gap, "limit": limit, "leaf": leaf})
    return out


def optimizer_field(opt_state, field: str):
    """The one sub-tree of an optax state held in a field of that name
    (Adam's ``mu``, momentum's ``trace``)."""
    has = lambda s: field in getattr(s, "_fields", ())  # noqa: E731
    found = [getattr(s, field) for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=has) if has(s)]
    if len(found) != 1:
        raise ValueError(f"expected one {field!r} in the optimizer state, "
                         f"found {len(found)}")
    return found[0]


def warmup_lr(opt: dict, count: int) -> float:
    """Linear warm-up from 0; the steps followed never leave it."""
    if count >= opt["warmup_steps"]:
        raise ValueError("the reference follows the warm-up only")
    return opt["learning_rate"] * count / opt["warmup_steps"]
