"""What a language model's plain reference shares with the next one beyond
``refcheck``: everything that is not a layer's equations. A configuration's
reference subclasses ``LayeredReference`` and brings its weights (``_make``)
and its residual block (``_layer``); from here come the model in one piece
(``_logits``), the programs built once a KIND of layer, the forward sweep
that keeps each layer's input and the backward sweep that calls one layer's
``jax.vjp`` at a time from the host (``_sweep``), the clip and AdamW with the
moments on the host between steps (``follow``), the probes the runner hangs
on the trainer, the bfloat16 routing count, and the numbers compared
(``compare``). Float32 under ``jax.default_matmul_precision("highest")``; it
imports nothing of the program.

A subclass sets, before ``super().__init__()``: ``L`` (layers), ``V`` (the
slice's ids), ``E`` / ``held`` / ``held_first`` (the router's outputs and the
experts held here), ``eps``, ``opt`` (the optimizer's numbers) and ``limits``
(name -> limit), and defines ``kind(i)`` (layers of one kind share their
programs), ``_make(key)`` ({"params": tree} with ``tok_embed``, ``layer<i>``,
``final_norm``, an untied ``lm_head``) and ``_layer(i, p, x, q)`` -> (x', the
tokens' choices over all the router's outputs), ``q`` the operands' rounder.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import refcheck

# every program of a reference runs a handful of times: compile it as fast
# as can be
QUICK = {"exec_time_optimization_effort": -1.0}


def rounder(precision: str):
    """refcheck's rounders, plus ``bfloat16`` (operands rounded to the
    program's compute type): used only to count near-tie routing flips.
    ``reduce_precision`` and not a pair of casts: the TPU compiler drops a
    float32 -> bfloat16 -> float32 round trip as excess precision allowed."""
    if precision == "bfloat16":
        return lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                                  mantissa_bits=7)
    return refcheck.rounder(precision)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def change_numbers(change, mu):
    """(per-leaf norms of the parameters' change, its cosine with Adam's
    first moment over every leaf together): descent reads negative."""
    dot = sum(jnp.sum(c * m) for c, m in zip(jax.tree.leaves(change),
                                             jax.tree.leaves(mu)))
    size = lambda t: jnp.sqrt(sum(jnp.sum(x * x)  # noqa: E731
                                  for x in jax.tree.leaves(t)))
    return refcheck.leaf_norms(change), dot / (size(change) * size(mu))


def is_expert(leaf: str) -> bool:
    return "['experts']" in leaf or "['router']" in leaf


def compare(ref: dict, observed: dict, limits: dict) -> list:
    """The numbers compared, each beside its limit: refcheck's (each step's
    loss, the parameters' change), the first gradient's matrix leaves taken
    apart into the expert layers' routed leaves (their worst; the median and
    every gap printed beside it) and every other matrix (the MEDIAN leaf and
    the worst, every gap printed), and the direction of the update. The
    reference's LIMITS says what each is held against."""
    out = [n for n in refcheck.compare_steps(ref, observed, limits)
           if n["name"] != "first_grad_worst_matrix_leaf"]
    gaps = refcheck.leaf_gaps(observed["first_grad"], ref["first_grad"])
    nan_worst = lambda k: (gaps[k] != gaps[k], gaps[k])  # noqa: E731
    routed = sorted((k for k in gaps if refcheck.is_matrix(k)
                     and is_expert(k)), key=nan_worst)
    plain = sorted((k for k in gaps if refcheck.is_matrix(k)
                    and not is_expert(k)), key=nan_worst)
    out.append({"name": "first_grad_median_matrix_leaf",
                "value": gaps[plain[len(plain) // 2]],
                "limit": limits["first_grad_median_matrix_leaf"],
                "leaf": plain[len(plain) // 2]})
    out.append({"name": "first_grad_worst_matrix_leaf",
                "value": gaps[plain[-1]],
                "limit": limits["first_grad_worst_matrix_leaf"],
                "leaf": plain[-1],
                "gaps": {k: round(gaps[k], 7) for k in plain}})
    out.append({"name": "first_grad_worst_expert_leaf",
                "value": gaps[routed[-1]],
                "limit": limits["first_grad_worst_expert_leaf"],
                "leaf": routed[-1], "median": gaps[routed[len(routed) // 2]],
                "gaps": {k: round(gaps[k], 6) for k in routed}})
    out.append({"name": "update_direction_gap",
                "value": abs(observed["update_direction"]
                             - ref["update_direction"]),
                "limit": limits["update_direction_gap"],
                "program": observed["update_direction"],
                "reference": ref["update_direction"]})
    return out


class LayeredReference:
    check_steps = 3
    no_decay = ("['scale']",)  # the leaves AdamW does not decay, by suffix

    def __init__(self):
        self._init = jax.jit(self._make, compiler_options=QUICK)
        self._jits = {}    # (precision, program) -> its jitted function

    def key(self, seed: int):
        return jax.random.key(seed, impl="rbg")

    def init_variables(self, seed: int) -> dict:
        return self._init(self.key(seed))

    def make_batches(self, seed: int, cell: dict, n: int) -> list:
        """Batches shaped like the cell's, for the control (no program)."""
        import numpy as np

        rng = np.random.default_rng(seed)
        shape = (cell["rehearsal_batch"] if "rehearsal_batch" in cell
                 else cell["batch_size"], cell["seq_len"])
        return [{"input_ids": rng.integers(0, self.V, shape).astype(np.int32)}
                for _ in range(n)]

    # --------------------------------------- probes on the program's state
    def probes(self, seed: int) -> dict:
        b1 = self.opt["beta1"]
        key = self.key(seed)

        grad_fn = jax.jit(lambda mu: refcheck.leaf_norms(
            jax.tree.map(lambda m: m / (1.0 - b1), mu)),
            compiler_options=QUICK)
        delta_fn = jax.jit(lambda p, mu, k: change_numbers(
            jax.tree.map(jnp.subtract, p, self._make(k)["params"]), mu),
            compiler_options=QUICK)
        return {
            # Adam's first moment after one step is (1 - b1) x the gradient
            # the optimizer was handed (after the clip)
            "first_grad": lambda step, st: grad_fn(
                refcheck.optimizer_field(st.opt_state, "mu"))
            if step == 1 else None,
            # (per-leaf norms of the change, its cosine with the moment)
            "param_change": lambda step, st: delta_fn(
                st.params, refcheck.optimizer_field(st.opt_state, "mu"), key)
            if step == self.check_steps else None,
        }

    def _logits(self, params, ids, q):
        """ids (S,) -> (logits (S, V), [choices a layer]): the model in one
        piece, one row. ``follow`` walks the same layers from the host; the
        tests hold its gradient against ``jax.grad`` of this."""
        x = params["tok_embed"]["embedding"][ids]
        chosen = []
        for i in range(self.L):
            x, on = jax.checkpoint(
                lambda p, x, i=i: self._layer(i, p, x, q))(
                    params[f"layer{i}"], x)
            chosen.append(on)
        x = rms(x, params["final_norm"]["scale"], self.eps)
        return q(x) @ q(params["lm_head"]["kernel"]), chosen

    # ------------------------------------- programs, one a kind of layer
    def _functions(self, precision: str) -> dict:
        """name -> function over the whole batch, its rows in turn (each
        sequence by itself, and one row's internals in memory at a time).
        ``fwd_<kind>``: (p, x) -> (x', choices); ``bwd_<kind>``: (p, x, dy)
        -> (dp, dx), the layer's vjp a row, its forward recomputed, dp summed
        over the rows; ``embed`` / ``embed_bwd``; ``head``: (final norm, the
        head, x, ids) -> (summed loss, their gradients and dx)."""
        q = rounder(precision)
        out = {}
        for kind in sorted({self.kind(i) for i in range(self.L)}):
            i = next(j for j in range(self.L) if self.kind(j) == kind)

            def fwd(p, x, i=i):
                return jax.lax.map(lambda row: self._layer(i, p, row, q), x)

            def bwd(p, x, dy, i=i):
                def row(dp, pair):
                    more, dx = jax.vjp(
                        lambda p, r: self._layer(i, p, r, q)[0], p,
                        pair[0])[1](pair[1])
                    return jax.tree.map(jnp.add, dp, more), dx

                return jax.lax.scan(row, jax.tree.map(jnp.zeros_like, p),
                                    (x, dy))

            out["fwd_" + kind], out["bwd_" + kind] = fwd, bwd

        def row_loss(norm, head, x, ids):  # one row: (S, V) logits
            logits = q(rms(x, norm["scale"], self.eps)) @ q(head["kernel"])
            logp = jax.nn.log_softmax(logits[:-1], -1)
            return -jnp.sum(jnp.take_along_axis(logp, ids[1:, None], -1))

        def batch_loss(norm, head, x, ids):  # rows in turn
            return jnp.sum(jax.lax.map(
                lambda r: jax.checkpoint(row_loss)(norm, head, *r),
                (x, ids)))

        out["head"] = jax.value_and_grad(batch_loss, argnums=(0, 1, 2))
        out["embed"] = lambda table, ids: table[ids]
        out["embed_bwd"] = lambda table, ids, dx: jnp.zeros_like(
            table).at[ids].add(dx)
        return out

    def _call(self, precision: str, name: str, *args):
        if (precision, name) not in self._jits:
            self._jits[precision, name] = jax.jit(
                self._functions(precision)[name], compiler_options=QUICK)
        return self._jits[precision, name](*args)

    def _sweep(self, precision: str, params: dict, ids, backward: bool):
        """Forward through the layers keeping each one's input, then (if
        asked) backward a layer at a time: (summed loss, gradients, choices
        (layers, batch, S, E))."""
        call = lambda name, *a: self._call(precision, name, *a)  # noqa: E731
        table = params["tok_embed"]["embedding"]
        xs = [call("embed", table, ids)]
        chosen = []
        for i in range(self.L):
            x, on = call("fwd_" + self.kind(i), params[f"layer{i}"], xs[-1])
            xs.append(x)
            chosen.append(on)
        chosen = jnp.stack(chosen)
        if not backward:
            return None, None, chosen
        loss, (d_norm, d_head, dx) = call(
            "head", params["final_norm"], params["lm_head"], xs.pop(), ids)
        grads = {"final_norm": d_norm, "lm_head": d_head}
        for i in reversed(range(self.L)):
            grads[f"layer{i}"], dx = call(
                "bwd_" + self.kind(i), params[f"layer{i}"], xs.pop(), dx)
        grads["tok_embed"] = {"embedding": call("embed_bwd", table, ids, dx)}
        return loss, grads, chosen

    def held_choices(self, chosen):
        """The choices that fall on the experts held here."""
        return chosen[..., self.held_first:self.held_first + self.held]

    def routing_flips(self, seed: int, ids, chosen) -> float:
        """Share of the (token, held expert) choices of the first batch, at
        the seeded weights, that differ between this float32 forward
        (``chosen``, from the first followed step) and one whose matrix
        operands are rounded to bfloat16: the near-ties of the last chosen
        and the first unchosen score that a bfloat16 program orders the
        other way. Printed unjudged."""
        with jax.default_matmul_precision("highest"):
            params = self.init_variables(seed)["params"]
            rounded = self._sweep("bfloat16", params, ids, False)[2]
            on = self.held_choices(chosen)
            return int(jnp.sum(self.held_choices(rounded) != on)) \
                / max(int(jnp.sum(on)), 1)

    def follow(self, seed: int, batches: list, precision: str = "float32"):
        """The first steps from the seeded weights on the given batches:
        losses, the per-leaf norms of the first gradient as the optimizer
        gets it (after the clip) and of the parameters' change; ``chosen``:
        the choices of the first batch at the seeded weights; ``held_rows``
        (steps, layers): each step's (token, choice) pairs on the held
        experts, which the expert layer's row bound has to hold."""
        import numpy as np

        o = self.opt

        def decayed(path):
            return not jax.tree_util.keystr(path).endswith(self.no_decay)

        def clip(grads, total):  # of the mean over the batch's targets
            grads = jax.tree.map(lambda g: g / total, grads)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                for g in jax.tree.leaves(grads)))
            limit = o["grad_clip_norm"]
            return jax.tree.map(
                lambda g: jnp.where(norm < limit, g, g / norm * limit), grads)

        def update(params, grads, mu, nu, count, lr):
            mu = jax.tree.map(lambda m, g: o["beta1"] * m
                              + (1 - o["beta1"]) * g, mu, grads)
            nu = jax.tree.map(lambda v, g: o["beta2"] * v
                              + (1 - o["beta2"]) * g * g, nu, grads)
            t = count + 1
            c1, c2 = 1 - o["beta1"] ** t, 1 - o["beta2"] ** t

            def step(path, p, m, v):
                u = (m / c1) / (jnp.sqrt(v / c2) + o["eps"])
                if decayed(path):
                    u = u + o["weight_decay"] * p
                return p - lr * u

            return jax.tree_util.tree_map_with_path(step, params, mu, nu), \
                mu, nu

        # the parameters in float32 beside the gradient's own buffers and a
        # head's S x S scores: the state is updated in place (donated), and
        # AdamW's two moments wait on the HOST while the gradient is computed
        clip = jax.jit(clip, donate_argnums=0, compiler_options=QUICK)
        update = jax.jit(update, donate_argnums=(0, 1, 2, 3),
                         compiler_options=QUICK)
        norms = jax.jit(refcheck.leaf_norms, compiler_options=QUICK)
        with jax.default_matmul_precision("highest"):
            params = self.init_variables(seed)["params"]
            mu = nu = jax.tree.map(
                lambda x: np.zeros(x.shape, x.dtype), params)
            losses, grad_norms, first_choices, held_rows = [], [], None, []
            for count, batch in enumerate(batches):
                began = time.perf_counter()
                ids = jnp.asarray(batch["input_ids"])
                total = float(ids.shape[0] * (ids.shape[1] - 1))
                loss, grads, chosen = self._sweep(precision, params, ids,
                                                  True)
                if first_choices is None:
                    first_choices = chosen
                held_rows.append(np.asarray(jax.device_get(jnp.sum(
                    self.held_choices(chosen), (1, 2, 3)))))
                losses.append(float(loss) / total)
                grads = clip(grads, total)
                grad_norms.append(jax.device_get(norms(grads)))
                params, mu, nu = update(
                    params, grads, jax.device_put(mu), jax.device_put(nu),
                    count, refcheck.warmup_lr(o, count))
                del grads, chosen
                mu, nu = jax.device_get(mu), jax.device_get(nu)
                # the first step's time holds the programs' compile
                print(f"[reference] {precision} step {count + 1}: "
                      f"{time.perf_counter() - began:.1f} s", file=sys.stderr,
                      flush=True)
            del nu
            params0 = self.init_variables(seed)["params"]
            size = jax.device_get(norms(params0))
            change, direction = jax.device_get(jax.jit(
                lambda a, b, m: change_numbers(
                    jax.tree.map(jnp.subtract, a, b), m),
                donate_argnums=0, compiler_options=QUICK)(
                    params, params0, jax.device_put(mu)))
        return {"losses": losses, "first_grad": grad_norms[0],
                "chosen": first_choices, "held_rows": np.stack(held_rows),
                "param_change": change, "update_direction": float(direction),
                "noise_leaves": refcheck.noise_leaves(grad_norms)
                | refcheck.rounding_leaves(change, size)}

    def check(self, seed: int, batches: list, observed: dict) -> list:
        ref = self.follow(seed, batches)
        change, direction = observed["param_change"]
        observed = {**observed, "param_change": change,
                    "update_direction": float(direction)}
        flips = self.routing_flips(
            seed, jnp.asarray(batches[0]["input_ids"]), ref["chosen"])
        return compare(ref, observed, self.limits) + [
            {"name": "routing_flips_bf16_share", "value": flips,
             "limit": None},
            # a layer's pairs on the held experts, the fullest of the
            # followed steps: what the expert layer's row bound has to hold
            {"name": "held_rows_fullest_layer",
             "value": float(ref["held_rows"].max()), "limit": None,
             "mean": float(ref["held_rows"].mean())}]
