"""Plain reference for the ``ouro_2_6b_lm_l8`` configuration: one pipeline
stage of Ouro-2.6B's looped language model (ByteDance; its config.json and
arXiv:2510.25741 section 3), its loss over the four exits, gradients and the
AdamW step, in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``. No kernel, no remat policy, no
scan over the passes: TWO NESTED PYTHON LOOPS, ``for t in range(T): for l in
range(L)``, run from the host (``_sweep``), over ONE set of layer weights.
Attention is the S x S score matrix of one head at a time under an explicit
mask. The forward sweep keeps the input of each of the T x L layer
applications and of each exit; the backward sweep calls one application's
``jax.vjp`` at a time and ADDS its weight gradient to that layer's (a
weight's gradient is the sum over its T uses). ``_exits`` is the same model
in one piece; a test holds the sweep's gradient against ``jax.grad`` of it.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_variables(seed)`` here (the runner installs them in
the trainer), the hyper-parameters from the configuration file. The
parameter tree's names and shapes are the interface; the runner refuses a
mismatch.

The model (x in R^{S x d}; RMSNorm with float32 statistics and a learned
scale, eps ``rms_norm_eps``; no bias in any projection, no q/k norm, no
dropout; ``assumed`` in the configuration file lists what config.json does
not say, each with its alternative):

* Layer l, four norm scales (a "sandwich"): ``a = x + N2_l(Attn_l(N1_l(x)))``,
  ``y = a + N4_l(FFN_l(N3_l(a)))``. Attn: q, k, v = W_q h, W_k h, W_v h in
  ``num_attention_heads`` heads of ``head_dim`` (as many KV heads); q, k
  rotated (rope ``rope_theta`` over the whole head, split-half pairs,
  positions from 0); scores q k^T / sqrt(head_dim) over keys j <= i, softmax
  in float32; W_o. FFN: ``W_down(silu(W_gate h) * W_up h)``.
* The loop: ``h_0 = E[ids]``; for t = 1..T (``total_ut_steps``): ``u =
  h_{t-1}``; for l = 1..L: ``u = Layer_l(u)`` (the SAME weights at every t);
  ``h_t = N_f(u)`` (one final norm; its OUTPUT is what pass t + 1 starts
  from); ``logits_t = W_head h_t``; ``g_t = w_g . h_t + b_g``.
* Exit distribution, a token: ``lam_t = sigmoid(g_t)``; ``S_0 = 1``, ``S_t =
  S_{t-1} (1 - lam_t)``; ``p_t = lam_t S_{t-1}`` for t < T, ``p_T =
  S_{T-1}`` (``g_T`` is computed and unused). ``sum_t p_t = 1``.
* Loss: ``ce_{t,i}`` = cross-entropy of ``logits_t`` at position i against
  token i + 1; ``loss = mean_i [sum_t p_{t,i} ce_{t,i} - beta H(p_{.,i})]``,
  ``H = -sum_t p_t log p_t``, beta = ``objective.exit_entropy_beta``. No
  stop-gradient.

The control (``benchmark/control.py``) rounds every matrix product's
operands to float8 except the gate's (a d x 1 product the configuration
states in float32, like the exit distribution); the limits, with the
readings each was set from, are beside LIMITS below and in PERF.md.
"""

from __future__ import annotations

import math
import sys
import time

import jax
import jax.numpy as jnp
import refcheck

# name -> limit, from readings on the chip (my chip runs, PR 35; PERF.md
# section 2): the LARGEST of 39 sound runs on 39 seeds (24 before the
# review, fifteen after it) and the SMALLEST the fp8 control reads on any
# of its fourteen seeds. A limit that parts the two lies between them; the
# control fails first_grad_worst_matrix_leaf and param_change_worst_leaf on
# every seed. The margins are thin: sound and control are 2.2x apart.
# loss_gap (each of 3 steps): sound largest 4.85e-4 (117 readings); the
#   control's largest a seed 7.4e-4...5.2e-3. The precision hardly moves it
#   (1.5x on the control's mildest seed), so its rule is the accepted LM
#   cells' limit where that leaves the sound reading three times of room:
#   1.5e-3 is 3.1x. NOT the limit that catches the control (it fails it on
#   eleven seeds of fourteen): held against a left-out part of the batch, of
#   the loop or of the loss (three passes for four move the loss by 8.7e-3
#   through the entropy alone, a dropped entropy term by 6.1e-2, at the
#   seeded gates).
# first_grad_worst_matrix_leaf (every matrix but the gate's): sound
#   0.075-0.516 % (the worst leaf a q or k projection of an early layer, or
#   the head, whose gradient the kernels round to bfloat16 an exit before
#   the four are summed); control 1.16-6.52 % (1.16, 1.53, 1.53, 1.54,
#   1.64, 1.67, 1.71, 1.80, 2.25, 2.50, 2.93, 3.27, 3.37, 6.52): the limit
#   is their geometric middle, 1.45x above the first and 1.55x below the
#   second.
# exit_gate_grad_gap: the gate's two leaves (2048 + 1 numbers) as ONE
#   vector, || program - reference || over || reference ||, judged apart: at
#   seeded weights the four ce_t are nearly equal (11.18-11.24), so what the
#   gate learns from them is a difference of nearly equal numbers, and only
#   the entropy term is first order. Sound 0.78-4.69 %; NOT a precision
#   number (the control reads 9.7-28.5 %, under the limit on seven seeds of
#   fourteen): held against a wrong exit distribution (a dropped entropy
#   term, an exit left out: some 100 %, the rehearsal's tests), 3.2 times
#   above the first reading and 6.7 below the second.
# param_change_worst_leaf: sound 0.009-0.047 % (the largest on the head's
#   leaf); control 0.108-0.422 % (0.108, 0.167, 0.193, 0.196, 0.198, 0.201,
#   0.208, 0.224, 0.262, 0.306, 0.326, 0.328, 0.392, 0.422): 1.6x above
#   the first and 1.45x below the second (a step that returns its state
#   reads 100 %).
# update_direction_gap: the cosine between the parameters' change after the
#   followed steps and Adam's first moment then, program against reference:
#   both sides read -0.53...-0.56. NOT a precision number: sound reads
#   3.9e-6...3.15e-4 and the control 2.96e-4...2.59e-3, so the two overlap
#   (a limit of 3.3e-4, tried between the first 24 sound runs and six
#   control seeds, stood 5 % over the next sound runs' largest and above
#   the next control's smallest). Held against an update with its sign
#   flipped, which moves it by 1.1: the geometric middle of 3.15e-4 and 1.1.
LIMITS = {
    "loss_gap": 1.5e-3,
    "first_grad_worst_matrix_leaf": 0.0075,
    "exit_gate_grad_gap": 0.15,
    "param_change_worst_leaf": 7.5e-4,
    "update_direction_gap": 0.02,
}

# every program here runs a handful of times: compile it as fast as can be
_QUICK = {"exec_time_optimization_effort": -1.0}

_NO_DECAY = ("['scale']", "['bias']")
_GATE = "['exit_gate']"


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def exit_distribution(g):
    """g (T, S) -> p (T, S), as the docstring's recurrence, written out."""
    lam = jax.nn.sigmoid(g)
    p, stay = [], jnp.ones_like(g[0])
    for t in range(g.shape[0] - 1):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    p.append(stay)  # the last exit takes what is left
    return jnp.stack(p)


def token_loss(ce, g, beta):
    """ce, g (T, S) -> (S,): sum_t p_t ce_t - beta H(p)."""
    p = exit_distribution(g)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.sum(p * ce, axis=0) - beta * entropy


class Reference:
    check_steps = 3

    def __init__(self, config: dict, rehearsal: bool = False):
        c = dict(config)
        if rehearsal:
            c.update(config["rehearsal"])
        self.d, self.L = c["hidden_size"], c["num_hidden_layers"]
        self.T = c["total_ut_steps"]
        self.H, self.Hkv = c["num_attention_heads"], c["num_key_value_heads"]
        assert self.H == self.Hkv, "as many KV heads as query heads"
        self.dh, self.m = c["head_dim"], c["intermediate_size"]
        self.V, self.theta = c["vocab_size"], float(c["rope_theta"])
        self.eps = c["rms_norm_eps"]
        self.beta = c["objective"]["exit_entropy_beta"]
        self.opt = c["optimizer"]  # the rehearsal brings its own
        self._init = jax.jit(self._make, compiler_options=_QUICK)
        self._jits = {}    # (precision, program) -> its jitted function

    def key(self, seed: int):
        return jax.random.key(seed, impl="rbg")

    # ------------------------------------------------------------ weights
    def _make(self, key):
        d, dh, f32 = self.d, self.dh, jnp.float32
        keys = iter(jax.random.split(key, 4 + 8 * self.L))
        n = lambda *shape: {"kernel": 0.02 * jax.random.normal(  # noqa: E731
            next(keys), shape, f32)}
        one = lambda: {"scale": jnp.ones((d,), f32)}  # noqa: E731
        params = {
            "tok_embed": {"embedding": n(self.V, d)["kernel"]},
            "final_norm": one(), "lm_head": n(d, self.V),
            "exit_gate": {**n(d, 1), "bias": jnp.zeros((1,), f32)}}
        for i in range(self.L):
            params[f"layer{i}"] = {
                "input_norm": one(), "attn_out_norm": one(),
                "post_attn_norm": one(), "mlp_out_norm": one(),
                "attn": {"q_proj": n(d, self.H, dh),
                         "k_proj": n(d, self.Hkv, dh),
                         "v_proj": n(d, self.Hkv, dh),
                         "o_proj": n(self.H, dh, d)},
                "mlp": {"gate_proj": n(d, self.m), "up_proj": n(d, self.m),
                        "down_proj": n(self.m, d)}}
        return {"params": params}

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

        def first_grad(mu):  # (every leaf's norm, the gate's leaves whole)
            grad = jax.tree.map(lambda m: m / (1.0 - b1), mu)
            return refcheck.leaf_norms(grad), grad["exit_gate"]

        grad_fn = jax.jit(first_grad, compiler_options=_QUICK)
        delta_fn = jax.jit(lambda p, mu, k: _change_numbers(
            jax.tree.map(jnp.subtract, p, self._make(k)["params"]), mu),
            compiler_options=_QUICK)
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

    # ------------------------------------------------------ the mathematics
    def _attn(self, p, x, q):
        """x (S, d) -> (S, d): causal attention, a head at a time."""
        S, dh = x.shape[0], self.dh
        proj = lambda name: jnp.einsum(  # noqa: E731
            "sc,chd->shd", q(x), q(p[name]["kernel"]))
        inv = self.theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

        def rotate(t):  # split halves: pair (i, i + dh/2)
            a, b = t[..., :dh // 2], t[..., dh // 2:]
            return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

        t = jnp.arange(S)

        def head(qkv):  # one head at a time: the scores are S x S float32
            q1, k1, v1 = qkv
            s = q(q1) @ q(k1).T / math.sqrt(dh)
            keep = t[:, None] >= t[None, :]  # computed, no constant
            w = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
            return q(w) @ q(v1)

        heads = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
        y = jax.lax.map(jax.checkpoint(head), (
            heads(rotate(proj("q_proj"))), heads(rotate(proj("k_proj"))),
            heads(proj("v_proj"))))
        return jnp.einsum("shd,hdc->sc", q(jnp.moveaxis(y, 0, 1)),
                          q(p["o_proj"]["kernel"]))

    def _ffn(self, p, x, q):
        h = jax.nn.silu(q(x) @ q(p["gate_proj"]["kernel"])) \
            * (q(x) @ q(p["up_proj"]["kernel"]))
        return q(h) @ q(p["down_proj"]["kernel"])

    def _layer(self, p, x, q):
        """One application of a layer: x (S, d) -> (S, d)."""
        norm = lambda name, y: _rms(  # noqa: E731
            y, p[name]["scale"], self.eps)
        a = x + norm("attn_out_norm",
                     self._attn(p["attn"], norm("input_norm", x), q))
        return a + norm("mlp_out_norm",
                        self._ffn(p["mlp"], norm("post_attn_norm", a), q))

    def _exit(self, p, u, ids, q):
        """One exit of one row: (final norm, head, gate), u (S, d), ids (S,)
        -> (ce (S,) with the last position 0, g (S,), h (S, d))."""
        h = _rms(u, p["final_norm"]["scale"], self.eps)
        logits = q(h) @ q(p["lm_head"]["kernel"])
        logp = jax.nn.log_softmax(logits[:-1], -1)
        ce = -jnp.take_along_axis(logp, ids[1:, None], -1)[:, 0]
        # the gate is float32 in every precision (a d x 1 product)
        g = (h @ p["exit_gate"]["kernel"])[:, 0] + p["exit_gate"]["bias"][0]
        return jnp.pad(ce, (0, 1)), g, h

    def _exits(self, params, ids, q=lambda x: x):
        """ids (S,) -> (ce (T, S), g (T, S), [logits_t]): the model in one
        piece, one row, the loops written out. ``_sweep`` walks the same
        applications from the host; the tests hold its gradient against
        ``jax.grad`` of this, and the program's logits against these."""
        x = params["tok_embed"]["embedding"][ids]
        ces, gs, logits = [], [], []
        for _ in range(self.T):
            for i in range(self.L):
                x = self._layer(params[f"layer{i}"], x, q)
            ce, g, x = self._exit(params, x, ids, q)
            ces.append(ce)
            gs.append(g)
            logits.append(q(x) @ q(params["lm_head"]["kernel"]))
        return jnp.stack(ces), jnp.stack(gs), logits

    def loss(self, params, ids, q=lambda x: x):
        """The batch's mean loss from ``_exits`` (B, S) -> scalar."""
        total = 0.0
        for row in ids:
            ce, g, _ = self._exits(params, row, q)
            total = total + jnp.sum(token_loss(ce, g, self.beta)[:-1])
        return total / (ids.shape[0] * (ids.shape[1] - 1))

    # ------------------------------------------------ programs, a few kinds
    def _functions(self, precision: str) -> dict:
        """name -> function over the whole batch, its rows in turn.
        ``fwd``: (layer p, x) -> x'; ``bwd``: (layer p, x, dy) -> (dp, dx),
        the application's vjp a row, its forward recomputed, dp summed over
        the rows. ``exit``: (exit p, u, ids) -> (ce, g, h); ``exit_bwd``:
        (exit p, u, ids, dce, dg, dh) -> (dp, du). ``couple``: (ce, g) (T, B,
        S) -> (summed loss, d/dce, d/dg, mean ce_t, mean p_t): the loss over
        the exits and what it hands each of them back."""
        q = refcheck.rounder(precision)
        layer = lambda p, r: self._layer(p, r, q)  # noqa: E731

        def fwd(p, x):
            return jax.lax.map(lambda r: layer(p, r), x)

        def bwd(p, x, dy):
            def row(dp, pair):
                more, dx = jax.vjp(layer, p, pair[0])[1](pair[1])
                return jax.tree.map(jnp.add, dp, more), dx

            return jax.lax.scan(row, jax.tree.map(jnp.zeros_like, p),
                                (x, dy))

        def exit_(p, u, ids):
            return jax.lax.map(lambda r: self._exit(p, *r, q), (u, ids))

        def exit_bwd(p, u, ids, dce, dg, dh):
            # ids are integers: closed over a row, not differentiated
            def row(dp, r):
                u1, i1, cot = r
                more, du = jax.vjp(jax.checkpoint(
                    lambda p, u: self._exit(p, u, i1, q)), p, u1)[1](cot)
                return jax.tree.map(jnp.add, dp, more), du

            return jax.lax.scan(row, jax.tree.map(jnp.zeros_like, p),
                                (u, ids, (dce, dg, dh)))

        def couple(ce, g):
            def total(ce, g):
                per = jax.vmap(lambda c, s: token_loss(c, s, self.beta),
                               in_axes=1)(ce, g)  # (B, S)
                return jnp.sum(per[:, :-1])

            loss, (dce, dg) = jax.value_and_grad(total, argnums=(0, 1))(ce, g)
            p = jax.vmap(exit_distribution, in_axes=1, out_axes=1)(g)
            mean = lambda a: jnp.mean(a[:, :, :-1], axis=(1, 2))  # noqa: E731
            return loss, dce, dg, mean(ce), mean(p)

        return {"fwd": fwd, "bwd": bwd, "exit": exit_, "exit_bwd": exit_bwd,
                "couple": couple,
                "embed": lambda table, ids: table[ids],
                "embed_bwd": lambda table, ids, dx: jnp.zeros_like(
                    table).at[ids].add(dx)}

    def _call(self, precision: str, name: str, *args):
        if (precision, name) not in self._jits:
            self._jits[precision, name] = jax.jit(
                self._functions(precision)[name], compiler_options=_QUICK)
        return self._jits[precision, name](*args)

    def _sweep(self, precision: str, params: dict, ids):
        """Forward through the T x L applications keeping each one's input,
        the loss over the exits, then backward an application at a time:
        (summed loss, gradients, mean ce_t (T,), mean p_t (T,))."""
        call = lambda name, *a: self._call(precision, name, *a)  # noqa: E731
        exit_p = {k: params[k] for k in ("final_norm", "lm_head",
                                         "exit_gate")}
        x = call("embed", params["tok_embed"]["embedding"], ids)
        inputs, exit_inputs, ces, gs = [], [], [], []
        for _ in range(self.T):          # the passes
            for i in range(self.L):      # the layers, the same every pass
                inputs.append(x)
                x = call("fwd", params[f"layer{i}"], x)
            exit_inputs.append(x)
            ce, g, x = call("exit", exit_p, x, ids)  # x: h_t, the next h_0
            ces.append(ce)
            gs.append(g)
        loss, dce, dg, mean_ce, mean_p = call(
            "couple", jnp.stack(ces, 0), jnp.stack(gs, 0))
        del ces, gs
        grads = {f"layer{i}": None for i in range(self.L)}
        add = lambda a, b: b if a is None else jax.tree.map(  # noqa: E731
            jnp.add, a, b)
        d_exit, dx = None, jnp.zeros_like(x)  # nothing reads h_T but exit T
        for t in reversed(range(self.T)):
            more, dx = call("exit_bwd", exit_p, exit_inputs.pop(), ids,
                            dce[t], dg[t], dx)
            d_exit = add(d_exit, more)
            for i in reversed(range(self.L)):
                more, dx = call("bwd", params[f"layer{i}"], inputs.pop(), dx)
                grads[f"layer{i}"] = add(grads[f"layer{i}"], more)
        grads.update(d_exit)
        grads["tok_embed"] = {"embedding": call(
            "embed_bwd", params["tok_embed"]["embedding"], ids, dx)}
        return loss, grads, mean_ce, mean_p

    def follow(self, seed: int, batches: list, precision: str = "float32"):
        """The first steps from the seeded weights on the given batches:
        losses, each exit's mean ce_t and p_t, the per-leaf norms of the
        first gradient as the optimizer gets it (after the clip), the
        gate's leaves of it whole, and the parameters' change."""
        import numpy as np

        o = self.opt

        def decayed(path):
            return not jax.tree_util.keystr(path).endswith(_NO_DECAY)

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

        # 612 M parameters in float32 beside the gradient's own buffers:
        # the state is updated in place (donated), and AdamW's two moments
        # wait on the HOST while the gradient is computed
        clip = jax.jit(clip, donate_argnums=0, compiler_options=_QUICK)
        update = jax.jit(update, donate_argnums=(0, 1, 2, 3),
                         compiler_options=_QUICK)
        norms = jax.jit(refcheck.leaf_norms, compiler_options=_QUICK)
        with jax.default_matmul_precision("highest"):
            params = self.init_variables(seed)["params"]
            mu = nu = jax.tree.map(
                lambda x: np.zeros(x.shape, x.dtype), params)
            losses, grad_norms, exit_ce, exit_share = [], [], [], []
            gate_grad = None
            for count, batch in enumerate(batches):
                began = time.perf_counter()
                ids = jnp.asarray(batch["input_ids"])
                total = float(ids.shape[0] * (ids.shape[1] - 1))
                loss, grads, mean_ce, mean_p = self._sweep(
                    precision, params, ids)
                losses.append(float(loss) / total)
                exit_ce.append([float(x) for x in mean_ce])
                exit_share.append([float(x) for x in mean_p])
                grads = clip(grads, total)
                grad_norms.append(jax.device_get(norms(grads)))
                if gate_grad is None:
                    gate_grad = jax.device_get(grads["exit_gate"])
                params, mu, nu = update(
                    params, grads, jax.device_put(mu), jax.device_put(nu),
                    count, refcheck.warmup_lr(o, count))
                del grads
                mu, nu = jax.device_get(mu), jax.device_get(nu)
                # the first step's time holds the programs' compile
                print(f"[reference] {precision} step {count + 1}: "
                      f"{time.perf_counter() - began:.1f} s", file=sys.stderr,
                      flush=True)
            del nu
            params0 = self.init_variables(seed)["params"]
            size = jax.device_get(norms(params0))
            change, direction = jax.device_get(jax.jit(
                lambda a, b, m: _change_numbers(
                    jax.tree.map(jnp.subtract, a, b), m),
                donate_argnums=0, compiler_options=_QUICK)(
                    params, params0, jax.device_put(mu)))
        return {"losses": losses, "first_grad": grad_norms[0],
                "gate_grad": gate_grad, "exit_ce": exit_ce,
                "exit_share": exit_share,
                "param_change": change, "update_direction": float(direction),
                "noise_leaves": refcheck.noise_leaves(grad_norms)
                | refcheck.rounding_leaves(change, size)}

    def check(self, seed: int, batches: list, observed: dict) -> list:
        ref = self.follow(seed, batches)
        change, direction = observed["param_change"]
        norms, gate = observed["first_grad"]
        return compare(ref, {**observed, "first_grad": norms,
                             "gate_grad": gate, "param_change": change,
                             "update_direction": float(direction)})


def _change_numbers(change, mu):
    """(per-leaf norms of the parameters' change, its cosine with Adam's
    first moment over every leaf together): descent reads negative."""
    dot = sum(jnp.sum(c * m) for c, m in zip(jax.tree.leaves(change),
                                             jax.tree.leaves(mu)))
    size = lambda t: jnp.sqrt(sum(jnp.sum(x * x)  # noqa: E731
                                  for x in jax.tree.leaves(t)))
    return refcheck.leaf_norms(change), dot / (size(change) * size(mu))


def _flat(gate: dict):
    import numpy as np

    return np.concatenate([np.asarray(gate["kernel"], np.float64).ravel(),
                           np.asarray(gate["bias"], np.float64).ravel()])


def compare(ref: dict, observed: dict) -> list:
    """The numbers compared, each beside its limit: refcheck's, with the
    first gradient's worst matrix leaf taken over every matrix BUT the
    gate's, the gate's two leaves judged apart as one vector
    (``exit_gate_grad_gap``), and the direction of the update (LIMITS above
    says what each is held against). Each exit's mean ce_t and p_t are
    printed for the record where ``observed`` brings them (the control
    does; the runner hands a reference's probes the program's state, not
    its step metrics: PERF.md section 7)."""
    import numpy as np

    out = [n for n in refcheck.compare_steps(ref, observed, LIMITS)
           if n["name"] != "first_grad_worst_matrix_leaf"]
    gaps = refcheck.leaf_gaps(observed["first_grad"], ref["first_grad"])
    plain = max((k for k in gaps if refcheck.is_matrix(k)
                 and _GATE not in k), key=lambda k: (gaps[k] != gaps[k],
                                                     gaps[k]))
    out.append({"name": "first_grad_worst_matrix_leaf", "value": gaps[plain],
                "limit": LIMITS["first_grad_worst_matrix_leaf"],
                "leaf": plain})
    want, have = _flat(ref["gate_grad"]), _flat(observed["gate_grad"])
    out.append({"name": "exit_gate_grad_gap",
                "value": float(np.linalg.norm(have - want)
                               / max(np.linalg.norm(want), 1e-30)),
                "limit": LIMITS["exit_gate_grad_gap"],
                "bias_program": float(have[-1]),
                "bias_reference": float(want[-1])})
    out.append({"name": "update_direction_gap",
                "value": abs(observed["update_direction"]
                             - ref["update_direction"]),
                "limit": LIMITS["update_direction_gap"],
                "program": observed["update_direction"],
                "reference": ref["update_direction"]})
    for key in ("exit_ce", "exit_share"):
        have = observed.get(key)
        out.append({"name": f"{key}_worst_gap", "limit": None,
                    "value": None if have is None else float(np.max(np.abs(
                        np.asarray(have) - np.asarray(ref[key])))),
                    "reference": ref[key]})
    return out
