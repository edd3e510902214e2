"""Plain reference for the ``lfm2_8b_a1b_lm_ep4`` configuration: one chip's
share of LFM2-8B-A1B's language model (LiquidAI; config.json of
``LFM2-8B-A1B``, ``model_type: lfm2_moe``), its next-token loss over the
vocabulary slice, gradients, the AdamW step and the selection bias's
balancing update, in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``. No kernel, no sorting of tokens:
the short convolution is the explicit sum of its three shifted products,
attention is the S x S scores of one head at a time with each KV head
repeated for its four query heads, the experts are a scan over the held ones
with masks, each sequence by itself (``lax.map`` over the batch's rows), and
the backward pass is taken LAYER BY LAYER from the host (``follow``): the
forward sweep keeps each layer's input, the backward sweep calls one layer's
``jax.vjp`` at a time. ``_logits`` is the same model in one piece; a test
holds the sweep's gradient against ``jax.grad`` of it.

Time. The driver cuts a run at 360 s. A layer's programs are built once a
KIND of layer (three kinds: the dense conv layer, the attention expert layer,
the conv expert layers), every program of this file asks the compiler for
its least effort (``_QUICK``: each runs a few times), and the causal mask is
an iota comparison inside the program, no S x S constant.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_variables(seed)`` here (the runner installs them in
the trainer), the hyper-parameters from the configuration file. The
parameter tree's names and shapes are the interface; the runner refuses a
mismatch.

The layers (h in R^{S x d} the normed input of a sublayer; pre-norm residual
blocks, RMSNorm with a learned scale and ``norm_eps``, a final RMSNorm, the
head TIED to the input table, no bias anywhere, no dropout, no auxiliary
loss):

* ``conv`` mixer: [B | C | u] = W_in h, three d-wide parts in that order;
  z_t = B_t * u_t; c_t = sum_{j=0..K-1} w_j * z_{t-(K-1)+j} with z zero
  before the sequence's start (K = ``conv_L_cache`` = 3 taps a channel,
  depthwise and causal: w_{K-1} meets the current token); y_t = W_out
  (C_t * c_t). No activation, no norm, no bias (``conv_bias`` false).
* ``full_attention`` mixer: q = W_q h (``num_attention_heads`` x 64), k, v =
  W_k h, W_v h (``num_key_value_heads`` x 64); RMSNorm over each head's 64
  dims of q and of k (one learned vector for q, one for k, shared by the
  heads); all 64 dims rotated as halves (i, i + 32) by position x
  theta^(-2i/64), positions from 0, no scaling; query head h reads KV head
  h // 4; causal softmax of q . k / 8 in float32; W_o. No output gate.
* Dense FFN (layers before ``num_dense_layers``): E(h) = W_down(SiLU(W_gate
  h) * W_up h) at ``intermediate_size``.
* Expert FFN (the others): s = sigmoid(W_r h) over ALL the router's outputs,
  float32; selection by s + b, the ``num_experts_per_tok`` largest; weights
  ``routed_scaling_factor`` x s_e / (sum of s over the chosen + 1e-6); y =
  sum over the chosen experts HELD HERE of w_e E_e(h) at
  ``moe_intermediate_size``. NO shared expert. What the absent experts
  would add is left out, as in the program.
* Head: logits = RMSNorm(x) E^T, E the input table: its gradient is the sum
  of the lookup's and the head's.
* The balancing update (``use_expert_bias``; the rule is DeepSeek-V3's,
  arXiv:2412.19437 2.1.2 and 4.2: ``assumed`` in the configuration file): b
  gets no gradient and no decay; after the optimizer's step, in each expert
  layer, c_e = the step's tokens that chose output e (all outputs, the
  whole batch), b_e <- b_e + rate x sign(mean(c) - c_e).

``assumed`` in the configuration file lists what the published config does
not say. The control (``benchmark/control.py``) rounds every matrix
product's operands, and the convolution's (z and the taps), to float8, except
the router's, which the configuration states in float32; the limits, with
the readings each was set from, are beside LIMITS below and in PERF.md.
"""

from __future__ import annotations

import math
import sys
import time

import jax
import jax.numpy as jnp
import refcheck

# name -> limit, from readings on the chip (my chip runs, PR 45: sixteen
# sound runs on sixteen seeds, fourteen of them from `git archive` of the
# tree; the fp8 control on six; two faults planted through the runner;
# PERF.md section 2 has the table). The per-leaf numbers are gaps between
# NORMS, |program's - reference's| / reference's: bfloat16 moves a leaf's
# norm to first order and either way (a leaf of the one attention layer by
# up to 0.14 %, every other matrix by under 0.015 %), float8 by its square
# and on every leaf, so the MEDIAN leaf parts the two precisions 5.6 times
# and the WORST leaf does not part them at all.
# loss_gap (each of 3 steps): sound 1.4e-5...5.6e-4 (48 readings); fp8
#   control, a seed's largest 7.8e-4...2.63e-3 (six seeds); the `C` gate left
#   out of the conv mixer 4.6e-3...1.1e-2. The accepted LM cells' limit: it
#   leaves the first reading 3.9 times of room and the largest 2.7, fails the
#   control on one seed of six and is not what catches it.
# first_grad_median_matrix_leaf (the median of every matrix but the experts'
#   and routers': 16 leaves): sound 0.002-0.008 % (0.002, 0.003, 0.003,
#   0.004, 0.004, 0.006, 0.008: the seven runs that printed it); fp8 control
#   0.045, 0.052, 0.053 %: the limit is their geometric middle, 2.5 times
#   above the sound runs' largest and 2.25 below the control's smallest. THE
#   number that catches a lower precision. Held after it was set: seven
#   more sound seeds read 0.002-0.010 % and three more control seeds
#   0.051-0.058 % (2.0 times of room above now).
# first_grad_worst_matrix_leaf (the same leaves' worst): sound 0.019-0.139 %
#   (sixteen runs; always a leaf of the one attention layer, ten times its
#   `v_proj`), fp8 control 0.085-0.294 % (no precision number: the two
#   overlap); held against a fault in ONE leaf, which the median cannot see:
#   the head's share of the tied table's gradient dropped reads 6.1 % (on
#   `tok_embed`), the `C` gate left out 11.7 %: 3.6 times above the sound
#   runs' largest, 12 below the smaller fault.
# first_grad_worst_expert_leaf (experts' and routers' kernels): NOT a
#   precision number (1.4-1.7 % of the held choices flip under bfloat16,
#   each moving a whole row to first order): sound 0.066-0.223 %, control
#   0.30-0.56 %; held against a routed expert left out, mis-scaled or fed
#   the wrong rows (reads 100 %).
# param_change_worst_leaf: sound 0.009-0.032 %, control 0.033-0.066 %:
#   between the first reading and a step that returns its state (reads
#   100 %), with the more room above the reading; the `C` gate left out reads
#   18 %, the head's share of the gradient dropped 6.4 %. The routers' BIAS
#   leaves are taken out of it: AdamW does not move them, the balancing
#   update does, by +-rate an entry.
# update_direction_gap: the cosine between the parameters' change after the
#   followed steps and Adam's first moment then (bias leaves apart), program
#   against reference: both read -0.421, 1e-6...5.5e-5 apart (control
#   3.3e-5...2.3e-4); a flipped update reads 0.84.
# router_bias_wrong_way_share: the bias leaves' own number (bias_wrong_way):
#   0 of 262-303 judged entries (of 384) on all sixteen sound runs and on the
#   control; 14 of 278 (5 %) with the `C` gate left out, whose activations
#   differ; the update left out reads 100 % by construction.
LIMITS = {
    "loss_gap": 1.5e-3,
    "first_grad_median_matrix_leaf": 2e-4,
    "first_grad_worst_matrix_leaf": 0.005,
    "first_grad_worst_expert_leaf": 0.05,
    "param_change_worst_leaf": 0.005,
    "update_direction_gap": 0.2,
    "router_bias_wrong_way_share": 0.2,
}
# How far from its layer's mean a router output's count has to stand for its
# bias's move to be judged, in units of sqrt(mean): a count's own standard
# deviation under uniform routing (45 tokens at the cell's mean of 2048). A
# bfloat16 program orders near-ties of the 4th and 5th selection score the
# other way; `router_count_shift_bf16` (printed by every run) is the largest
# move of any count, in the same units, that this file's own
# bfloat16-rounded forward shows at the seeded weights: 0.29-0.46 on the
# chip (nine seeds, my chip runs, PR 45), so the margin stands three times
# clear of it and 262-303 of the 384 entries are judged.
BIAS_MARGIN = 1.5
# a bias's move smaller than this (a hundredth of the rate: float32 rounding
# of b + rate - b is 1e-9) counts as no move
_RATE_FLOOR = 1e-5
# added to the sum of the chosen scores (the family's code; the program
# leaves it out: 4e-7 of a weight, `assumed` `norm_topk_prob`)
_WEIGHT_EPS = 1e-6

# every program here runs a handful of times: compile it as fast as can be
_QUICK = {"exec_time_optimization_effort": -1.0}

_NO_DECAY = ("['scale']", "['bias']", "['taps']")


def _rounder(precision: str):
    """refcheck's rounders, plus ``bfloat16`` (operands rounded to the
    program's compute type): used only to count near-tie routing flips.
    ``reduce_precision`` and not a pair of casts: the TPU compiler drops a
    float32 -> bfloat16 -> float32 round trip as excess precision allowed."""
    if precision == "bfloat16":
        return lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                                  mantissa_bits=7)
    return refcheck.rounder(precision)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _swiglu(x, p, q):
    h = jax.nn.silu(q(x) @ q(p["gate_proj"]["kernel"])) \
        * (q(x) @ q(p["up_proj"]["kernel"]))
    return q(h) @ q(p["down_proj"]["kernel"])


def is_bias(leaf: str) -> bool:
    return leaf.endswith("['router']['bias']")


def _router_biases(params) -> jnp.ndarray:
    """(expert layers, E): every router's selection bias, in layer order."""
    layers = sorted((k for k in params if k.startswith("layer")
                     and "moe" in params[k]), key=lambda k: int(k[5:]))
    return jnp.stack([params[k]["moe"]["router"]["bias"] for k in layers])


class Reference:
    check_steps = 3

    def __init__(self, config: dict, rehearsal: bool = False):
        c = dict(config)
        if rehearsal:
            c.update(config["rehearsal"])
        self.d, self.L = c["hidden_size"], c["num_hidden_layers"]
        self.H, self.Hkv = c["num_attention_heads"], c["num_key_value_heads"]
        self.dh, self.K = c["head_dim"], c["conv_L_cache"]
        self.m, self.F = c["intermediate_size"], c["moe_intermediate_size"]
        self.V, self.P = c["vocab_size"], c["max_position_embeddings"]
        self.E, self.held = c["router_num_experts"], c["num_experts"]
        self.held_first = c["held_expert_first_id"]
        self.top_k = c["num_experts_per_tok"]
        self.route_scale = c["routed_scaling_factor"]
        self.dense_layers = c["num_dense_layers"]
        self.types = tuple(c["layer_types"])
        if len(self.types) != self.L or set(self.types) - {
                "conv", "full_attention"} or c["conv_bias"]:
            raise ValueError("this reference runs bias-free `conv` and "
                             f"`full_attention` layers, one a layer: "
                             f"{self.types} for {self.L} layers")
        self.bias_rate = float(c["router_bias_update_rate"])
        self.theta, self.eps = float(c["rope_theta"]), c["norm_eps"]
        self.opt = c["optimizer"]  # the rehearsal brings its own
        self._init = jax.jit(self._make, compiler_options=_QUICK)
        self._jits = {}    # (precision, program) -> its jitted function

    def kind(self, i: int) -> str:
        """A layer's kind: layers of one kind share their programs."""
        return self.types[i] + ("_mlp" if i < self.dense_layers else "_moe")

    def key(self, seed: int):
        return jax.random.key(seed, impl="rbg")

    # ------------------------------------------------------------ weights
    def _make(self, key):
        d, f32 = self.d, jnp.float32
        keys = iter(jax.random.split(key, 4 + 32 * self.L))
        n = lambda shape, std=0.02: std * jax.random.normal(  # noqa: E731
            next(keys), shape, f32)
        k = lambda *shape: {"kernel": n(shape)}  # noqa: E731
        one = lambda size: {"scale": jnp.ones((size,), f32)}  # noqa: E731
        ffn = lambda width, *lead: {  # noqa: E731
            "gate_proj": k(*lead, d, width), "up_proj": k(*lead, d, width),
            "down_proj": k(*lead, width, d)}
        # no head of its own: the table is read twice
        params = {"tok_embed": {"embedding": n((self.V, d))},
                  "final_norm": one(d)}
        for i in range(self.L):
            layer = {"input_norm": one(d), "post_attn_norm": one(d)}
            if self.types[i] == "conv":
                layer["conv"] = {"in_proj": k(d, 3 * d), "taps": n((self.K, d)),
                                 "out_proj": k(d, d)}
            else:
                layer["gqa"] = {
                    "q_proj": k(d, self.H, self.dh), "q_norm": one(self.dh),
                    "k_proj": k(d, self.Hkv, self.dh), "k_norm": one(self.dh),
                    "v_proj": k(d, self.Hkv, self.dh),
                    "o_proj": k(self.H, self.dh, d)}
            if i < self.dense_layers:
                layer["mlp"] = ffn(self.m)
            else:
                layer["moe"] = {
                    "router": {"kernel": n((d, self.E)),
                               "bias": n((self.E,), 0.01)},
                    "experts": ffn(self.F, self.held)}
            params[f"layer{i}"] = layer
        return {"params": params}

    def init_variables(self, seed: int) -> dict:
        return self._init(self.key(seed))

    def make_batches(self, seed: int, cell: dict, n: int) -> list:
        """Batches shaped like the cell's, for the control (no program)."""
        import numpy as np

        rng = np.random.default_rng(seed)
        shape = (cell["rehearsal_batch"] if "rehearsal_batch" in cell
                 else cell["batch_size"], min(cell["seq_len"], self.P))
        return [{"input_ids": rng.integers(0, self.V, shape).astype(np.int32)}
                for _ in range(n)]

    # --------------------------------------- probes on the program's state
    def probes(self, seed: int) -> dict:
        b1 = self.opt["beta1"]
        key = self.key(seed)

        grad_fn = jax.jit(lambda mu: refcheck.leaf_norms(
            jax.tree.map(lambda m: m / (1.0 - b1), mu)),
            compiler_options=_QUICK)
        delta_fn = jax.jit(lambda p, mu, k: _change_numbers(
            jax.tree.map(jnp.subtract, p, self._make(k)["params"]), mu),
            compiler_options=_QUICK)
        bias_fn = jax.jit(_router_biases, compiler_options=_QUICK)
        out = {
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
        # every router's selection bias after each followed step (a probe
        # keeps its newest value, so one name a step)
        for n in range(1, self.check_steps + 1):
            out[f"router_bias_step{n}"] = lambda step, st, n=n: bias_fn(
                st.params) if step == n else None
        return out

    # ------------------------------------------------------ the mathematics
    def _conv(self, p, x, q):
        S, d, K = x.shape[0], self.d, self.K
        bcu = q(x) @ q(p["in_proj"]["kernel"])
        b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
        z, w = q(b * u), q(p["taps"])
        # tap j meets the token K-1-j back; before the start, zeros
        back = lambda n: jnp.concatenate(  # noqa: E731
            [jnp.zeros((n, d), z.dtype), z[:S - n]], 0) if n else z
        mixed = w[0] * back(K - 1)
        for j in range(1, K):
            mixed = mixed + w[j] * back(K - 1 - j)
        return q(c * mixed) @ q(p["out_proj"]["kernel"])

    def _gqa(self, p, x, q):
        S, dh = x.shape[0], self.dh
        proj = lambda name: jnp.einsum(  # noqa: E731
            "sc,chd->shd", q(x), q(p[name]["kernel"]))
        qh = _rms(proj("q_proj"), p["q_norm"]["scale"], self.eps)
        kh = _rms(proj("k_proj"), p["k_norm"]["scale"], self.eps)
        vh = proj("v_proj")
        inv = self.theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

        def rotate(t):  # (S, heads, dh): dims i and i + dh/2 turn together
            a, b = t[..., :dh // 2], t[..., dh // 2:]
            return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

        qh, kh = rotate(qh), rotate(kh)
        group = self.H // self.Hkv  # query head h reads KV head h // group
        kh, vh = jnp.repeat(kh, group, 1), jnp.repeat(vh, group, 1)
        t = jnp.arange(S)

        def head(qkv):  # one head at a time: the scores are S x S float32
            q1, k1, v1 = qkv
            s = q(q1) @ q(k1).T / math.sqrt(dh)
            causal = t[:, None] >= t[None, :]  # computed, not a constant
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return q(w) @ q(v1)

        heads = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
        y = jax.lax.map(jax.checkpoint(head), (heads(qh), heads(kh),
                                               heads(vh)))
        return jnp.einsum("shd,hdc->sc", q(jnp.moveaxis(y, 0, 1)),
                          q(p["o_proj"]["kernel"]))

    def _route(self, p, x):
        """(weight of each held expert a token (S, held), 0 where it is not
        chosen; chosen (S, E): the token's choices over ALL outputs). The
        product with W_r is float32 in every precision."""
        s = jax.nn.sigmoid(x @ p["kernel"])
        ids = jax.lax.top_k(s + p["bias"], self.top_k)[1]
        chosen = jnp.any(ids[:, :, None] == jnp.arange(self.E), 1)
        picked = jnp.where(chosen, s, 0.0)
        w = self.route_scale * picked \
            / (jnp.sum(picked, -1, keepdims=True) + _WEIGHT_EPS)
        return w[:, self.held_first:self.held_first + self.held], chosen

    def _moe(self, p, x, q):
        w, chosen = self._route(p["router"], x)
        # every held expert in turn on every token, weighted (0 where it
        # is not chosen): a scan over the experts' leading axis
        y, _ = jax.lax.scan(
            lambda y, ew: (y + ew[1][:, None] * _swiglu(x, ew[0], q), None),
            jnp.zeros_like(x), (p["experts"], w.T))
        return y, chosen

    def _layer(self, i, p, x, q):
        """One residual block: (x, the tokens' choices over all the router's
        outputs, or None)."""
        h = _rms(x, p["input_norm"]["scale"], self.eps)
        x = x + (self._conv(p["conv"], h, q) if self.types[i] == "conv"
                 else self._gqa(p["gqa"], h, q))
        h = _rms(x, p["post_attn_norm"]["scale"], self.eps)
        if i < self.dense_layers:
            return x + _swiglu(h, p["mlp"], q), None
        out, chosen = self._moe(p["moe"], h, q)
        return x + out, chosen

    def _logits(self, params, ids, q):
        """ids (S,) -> (logits (S, V), [choices a routed layer]): the model
        in one piece, one row. ``follow`` walks the same layers from the
        host; the tests hold its gradient against ``jax.grad`` of this."""
        table = params["tok_embed"]["embedding"]
        x = table[ids]
        chosen = []
        for i in range(self.L):
            x, on = jax.checkpoint(
                lambda p, x, i=i: self._layer(i, p, x, q))(
                    params[f"layer{i}"], x)
            if on is not None:
                chosen.append(on)
        x = _rms(x, params["final_norm"]["scale"], self.eps)
        return q(x) @ q(table).T, chosen

    # ------------------------------------- programs, one a kind of layer
    def _functions(self, precision: str) -> dict:
        """name -> function over the whole batch, its rows in turn (each
        sequence by itself, and one row's internals in memory at a time).
        ``fwd_<kind>``: (p, x) -> (x', choices or None);
        ``bwd_<kind>``: (p, x, dy) -> (dp, dx), the layer's vjp a row, its
        forward recomputed, dp summed over the rows; ``embed`` /
        ``embed_bwd``; ``head``: (final norm, the table, x, ids) -> (summed
        loss, the norm's gradient, the HEAD's share of the table's, dx)."""
        q = _rounder(precision)
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

        def row_loss(norm, table, x, ids):  # one row: (S, V) logits
            logits = q(_rms(x, norm["scale"], self.eps)) @ q(table).T
            logp = jax.nn.log_softmax(logits[:-1], -1)
            return -jnp.sum(jnp.take_along_axis(logp, ids[1:, None], -1))

        def batch_loss(norm, table, x, ids):  # rows in turn
            return jnp.sum(jax.lax.map(
                lambda r: jax.checkpoint(row_loss)(norm, table, *r),
                (x, ids)))

        out["head"] = jax.value_and_grad(batch_loss, argnums=(0, 1, 2))
        out["embed"] = lambda table, ids: table[ids]
        # the table's gradient: the head's share plus the lookup's
        out["embed_bwd"] = lambda d_head, ids, dx: d_head.at[ids].add(dx)
        return out

    def _call(self, precision: str, name: str, *args):
        if (precision, name) not in self._jits:
            self._jits[precision, name] = jax.jit(
                self._functions(precision)[name], compiler_options=_QUICK)
        return self._jits[precision, name](*args)

    def _sweep(self, precision: str, params: dict, ids, backward: bool):
        """Forward through the layers keeping each one's input, then (if
        asked) backward a layer at a time: (summed loss, gradients, choices
        (routed layers, batch, S, E))."""
        call = lambda name, *a: self._call(precision, name, *a)  # noqa: E731
        table = params["tok_embed"]["embedding"]
        xs = [call("embed", table, ids)]
        chosen = []
        for i in range(self.L):
            x, on = call("fwd_" + self.kind(i), params[f"layer{i}"], xs[-1])
            xs.append(x)
            if on is not None:
                chosen.append(on)
        chosen = jnp.stack(chosen)
        if not backward:
            return None, None, chosen
        loss, (d_norm, d_head, dx) = call(
            "head", params["final_norm"], table, xs.pop(), ids)
        grads = {"final_norm": d_norm}
        for i in reversed(range(self.L)):
            grads[f"layer{i}"], dx = call(
                "bwd_" + self.kind(i), params[f"layer{i}"], xs.pop(), dx)
        grads["tok_embed"] = {"embedding": call("embed_bwd", d_head, ids, dx)}
        return loss, grads, chosen

    def routing_flips(self, seed: int, ids, chosen) -> tuple:
        """(share of the (token, held expert) choices of the first batch,
        at the seeded weights, that differ between this float32 forward
        (``chosen``, from the first followed step) and one whose matrix
        operands are rounded to bfloat16: the near-ties of the 4th and 5th
        score that a bfloat16 program orders the other way; the largest
        move of any router output's count between the two, in units of
        sqrt(mean count): what BIAS_MARGIN has to stand clear of). Printed
        unjudged."""
        with jax.default_matmul_precision("highest"):
            params = self.init_variables(seed)["params"]
            rounded = self._sweep("bfloat16", params, ids, False)[2]
            held = slice(self.held_first, self.held_first + self.held)
            share = int(jnp.sum(rounded[..., held] != chosen[..., held])) \
                / max(int(jnp.sum(chosen[..., held])), 1)
            counts = _counts(chosen)
            shift = jnp.max(jnp.abs(_counts(rounded) - counts)) \
                / jnp.sqrt(jnp.mean(counts))
            return share, float(shift)

    def follow(self, seed: int, batches: list, precision: str = "float32"):
        """The first steps from the seeded weights on the given batches:
        losses, the per-leaf norms of the first gradient as the optimizer
        gets it (after the clip) and of the parameters' change; ``chosen``:
        the choices of the first batch at the seeded weights; ``counts``
        (steps, routed layers, E): each step's tokens on every router
        output, which move the bias after that step's optimizer;
        ``bias_start`` / ``bias_after``: every router's bias at the seeded
        weights and after each step."""
        import numpy as np

        o = self.opt

        def clip(grads, total):  # of the mean over the batch's targets
            grads = jax.tree.map(lambda g: g / total, grads)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                for g in jax.tree.leaves(grads)))
            limit = o["grad_clip_norm"]
            return jax.tree.map(
                lambda g: jnp.where(norm < limit, g, g / norm * limit), grads)

        def update(params, grads, mu, nu, count, lr, counts):
            mu = jax.tree.map(lambda m, g: o["beta1"] * m
                              + (1 - o["beta1"]) * g, mu, grads)
            nu = jax.tree.map(lambda v, g: o["beta2"] * v
                              + (1 - o["beta2"]) * g * g, nu, grads)
            t = count + 1
            c1, c2 = 1 - o["beta1"] ** t, 1 - o["beta2"] ** t
            routed = [i for i in range(self.L) if i >= self.dense_layers]

            def step(path, p, m, v):
                name = jax.tree_util.keystr(path)
                if is_bias(name):
                    # not the optimizer's: the balancing rule's, from this
                    # step's counts on every output of this layer's router
                    c = counts[routed.index(int(path[0].key[len("layer"):]))]
                    return p + self.bias_rate * jnp.sign(jnp.mean(c) - c)
                u = (m / c1) / (jnp.sqrt(v / c2) + o["eps"])
                if not name.endswith(_NO_DECAY):
                    u = u + o["weight_decay"] * p
                return p - lr * u

            new = jax.tree_util.tree_map_with_path(step, params, mu, nu)
            return new, mu, nu

        # half a billion parameters in float32 beside the gradient's own
        # buffers: the state is updated in place (donated), and AdamW's two
        # moments wait on the HOST while the gradient is computed
        clip = jax.jit(clip, donate_argnums=0, compiler_options=_QUICK)
        update = jax.jit(update, donate_argnums=(0, 1, 2, 3),
                         compiler_options=_QUICK)
        norms = jax.jit(refcheck.leaf_norms, compiler_options=_QUICK)
        with jax.default_matmul_precision("highest"):
            params = self.init_variables(seed)["params"]
            mu = nu = jax.tree.map(
                lambda x: np.zeros(x.shape, x.dtype), params)
            losses, grad_norms, first_choices = [], [], None
            counts, bias_after = [], []
            for count, batch in enumerate(batches):
                began = time.perf_counter()
                ids = jnp.asarray(batch["input_ids"])
                total = float(ids.shape[0] * (ids.shape[1] - 1))
                loss, grads, chosen = self._sweep(precision, params, ids,
                                                  True)
                if first_choices is None:
                    first_choices = chosen
                counts.append(_counts(chosen))
                losses.append(float(loss) / total)
                grads = clip(grads, total)
                grad_norms.append(jax.device_get(norms(grads)))
                params, mu, nu = update(
                    params, grads, jax.device_put(mu), jax.device_put(nu),
                    count, refcheck.warmup_lr(o, count), counts[-1])
                bias_after.append(np.asarray(jax.device_get(
                    _router_biases(params))))
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
                lambda a, b, m: _change_numbers(
                    jax.tree.map(jnp.subtract, a, b), m),
                donate_argnums=0, compiler_options=_QUICK)(
                    params, params0, jax.device_put(mu)))
        return {"losses": losses, "first_grad": grad_norms[0],
                "chosen": first_choices,
                "counts": np.asarray(jax.device_get(jnp.stack(counts))),
                "bias_start": np.asarray(jax.device_get(
                    _router_biases(params0))),
                "bias_after": bias_after,
                "param_change": change, "update_direction": float(direction),
                "noise_leaves": refcheck.noise_leaves(grad_norms)
                | refcheck.rounding_leaves(change, size)
                | frozenset(k for k in change if is_bias(k))}

    def check(self, seed: int, batches: list, observed: dict) -> list:
        ref = self.follow(seed, batches)
        change, direction = observed["param_change"]
        observed = {**observed, "param_change": change,
                    "update_direction": float(direction)}
        flips, shift = self.routing_flips(
            seed, jnp.asarray(batches[0]["input_ids"]), ref["chosen"])
        held = ref["counts"][:, :, self.held_first:self.held_first
                             + self.held].sum(-1)
        return compare(ref, observed) + [
            {"name": "routing_flips_bf16_share", "value": flips,
             "limit": None},
            {"name": "router_count_shift_bf16", "value": shift,
             "limit": None, "margin": BIAS_MARGIN},
            # a layer's pairs on the held experts, the fullest of the
            # followed steps: what the expert layer's row bound has to hold
            {"name": "held_rows_fullest_layer", "value": float(held.max()),
             "limit": None, "mean": float(held.mean())}]


def _counts(chosen):
    """(routed layers, batch, S, E) choices -> (routed layers, E) float32:
    the batch's tokens on every router output."""
    return jnp.sum(chosen, (1, 2), dtype=jnp.float32)


def _change_numbers(change, mu):
    """(per-leaf norms of the parameters' change, its cosine with Adam's
    first moment over every leaf the optimizer moves: the routers' biases
    are left out of the cosine, having no moment): descent reads
    negative."""
    pairs = [(c, m) for (path, c), m in zip(
        jax.tree_util.tree_flatten_with_path(change)[0], jax.tree.leaves(mu))
        if not is_bias(jax.tree_util.keystr(path))]
    dot = sum(jnp.sum(c * m) for c, m in pairs)
    size = lambda leaves: jnp.sqrt(sum(jnp.sum(x * x)  # noqa: E731
                                       for x in leaves))
    return refcheck.leaf_norms(change), dot / (
        size([c for c, _ in pairs]) * size([m for _, m in pairs]))


def is_expert(leaf: str) -> bool:
    return "['experts']" in leaf or "['router']" in leaf


def bias_wrong_way(ref: dict, observed: dict) -> dict:
    """The routers' biases, judged apart. An entry is one router output of
    one layer at one followed step; it is judged where its count in the
    reference stands farther than BIAS_MARGIN x sqrt(mean) from its layer's
    mean (nearer, the bfloat16 routing flips decide the sign). Wrong: the
    program's bias moved by another sign than the rule's, sign(mean - c)
    (not at all counts as wrong)."""
    import numpy as np

    c = ref["counts"]                                     # (steps, L, E)
    mean = c.mean(-1, keepdims=True)
    off = mean - c
    judged = np.abs(off) > BIAS_MARGIN * np.sqrt(mean)
    # a program's probes, or (the control: a reference in the program's
    # place) that reference's own biases
    after = observed.get("bias_after") or [
        observed[f"router_bias_step{n + 1}"] for n in range(len(c))]
    after = [np.asarray(a, np.float32) for a in after]
    moves = np.stack(after) - np.stack([ref["bias_start"]] + after[:-1])
    moved = np.sign(np.where(np.abs(moves) < _RATE_FLOOR, 0.0, moves))
    wrong = judged & (moved != np.sign(off))
    return {"value": float(wrong.sum()) / max(int(judged.sum()), 1),
            "judged": int(judged.sum()), "of": int(judged.size),
            "wrong": int(wrong.sum())}


def compare(ref: dict, observed: dict) -> list:
    """The numbers compared, each beside its limit: refcheck's (each step's
    loss, the parameters' change), the first gradient's matrix leaves taken
    apart into the expert layers' routed leaves (their worst) and every
    other matrix (the MEDIAN leaf, which parts the precisions, and the
    worst, which a fault in one leaf moves), the direction of the update,
    and the routers' bias leaves' own number (``follow`` puts them with the
    noise leaves of ``param_change_worst_leaf``). LIMITS above says what
    each is held against."""
    out = []
    for n in refcheck.compare_steps(ref, observed, LIMITS):
        if n["name"] != "first_grad_worst_matrix_leaf":
            out.append(n)
    gaps = refcheck.leaf_gaps(observed["first_grad"], ref["first_grad"])
    nan_worst = lambda k: (gaps[k] != gaps[k], gaps[k])  # noqa: E731
    routed = sorted((k for k in gaps if refcheck.is_matrix(k)
                     and is_expert(k)), key=nan_worst)
    plain = sorted((k for k in gaps if refcheck.is_matrix(k)
                    and not is_expert(k)), key=nan_worst)
    out.append({"name": "first_grad_median_matrix_leaf",
                "value": gaps[plain[len(plain) // 2]],
                "limit": LIMITS["first_grad_median_matrix_leaf"],
                "leaf": plain[len(plain) // 2]})
    out.append({"name": "first_grad_worst_matrix_leaf",
                "value": gaps[plain[-1]],
                "limit": LIMITS["first_grad_worst_matrix_leaf"],
                "leaf": plain[-1],
                "gaps": {k: round(gaps[k], 7) for k in plain}})
    out.append({"name": "first_grad_worst_expert_leaf",
                "value": gaps[routed[-1]],
                "limit": LIMITS["first_grad_worst_expert_leaf"],
                "leaf": routed[-1], "median": gaps[routed[len(routed) // 2]],
                "gaps": {k: round(gaps[k], 6) for k in routed}})
    out.append({"name": "update_direction_gap",
                "value": abs(observed["update_direction"]
                             - ref["update_direction"]),
                "limit": LIMITS["update_direction_gap"],
                "program": observed["update_direction"],
                "reference": ref["update_direction"]})
    out.append({"name": "router_bias_wrong_way_share",
                "limit": LIMITS["router_bias_wrong_way_share"],
                **bias_wrong_way(ref, observed)})
    return out
