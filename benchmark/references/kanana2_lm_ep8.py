"""Plain reference for the ``kanana2_lm_ep8`` configuration: one chip's share
of Kanana-2-30B-A3B's language model (kakaocorp; config.json of
``kanana-2-30b-a3b-instruct-2601``, ``model_type: deepseek_v3``), its
next-token loss over the vocabulary slice, gradients, the AdamW step and the
selection bias's balancing update, in straightforward ``jax.numpy`` float32
under ``jax.default_matmul_precision("highest")``. No kernel, no sorting of
tokens: latent attention is the S x S scores of one head at a time, the
experts are a scan over the held ones with masks, each sequence by itself
(``lax.map`` over the batch's rows), and the backward pass is taken LAYER BY
LAYER from the host (``follow``): the forward sweep keeps each layer's
input, the backward sweep calls one layer's ``jax.vjp`` at a time.
``_logits`` is the same model in one piece; a test holds the sweep's
gradient against ``jax.grad`` of it.

Time. The driver cuts a run at 360 s. A layer's programs are built once a
KIND of layer (two kinds: the dense layer and the expert layers), every
program of this file asks the compiler for its least effort (``_QUICK``:
each runs a few times), and the causal mask is an iota comparison inside
the program, no S x S constant.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_variables(seed)`` here (the runner installs them in
the trainer), the hyper-parameters from the configuration file. The
parameter tree's names and shapes are the interface; the runner refuses a
mismatch.

The layers (x in R^{S x d} the normed input of a sublayer; pre-norm residual
blocks, RMSNorm, final RMSNorm, untied head, no bias anywhere, no dropout,
no auxiliary loss):

* Latent attention (EVERY layer; DeepSeek-V3's plain form, ``q_lora_rank``
  null): q = W_q x, a head [q_nope (128) | q_pe (64)]; c = W_dkv x (512),
  k_pe = W_kr x (64), ONE for all heads (the source's one matrix W_a; two
  leaves here, the same map); [k_nope (128) | v (128)] a head = W_ukv
  RMSNorm(c); R rotates the pairs (x_2i, x_2i+1) by position x
  theta^(-2i/64) (``rope_interleave``; positions from 0, no scaling); q =
  [q_nope | R q_pe], k = [k_nope | R k_pe] with the one rotated k_pe
  repeated; causal softmax of q . k / sqrt(192); W_o over the heads' P v.
  No norm over a head's q or k, no output gate.
  DEPARTURE from the source's code, none from its function: the source
  de-interleaves q_pe and k_pe and rotates halves; the scores are the same
  because q and k are permuted alike. The pairs are rotated in place here.
* Expert FFN (every layer past the leading dense one): s = sigmoid(W_g x)
  over ALL the router's outputs, float32; selection by s + b, the
  ``num_experts_per_tok`` largest (``n_group`` 1: plain top-k); weights
  ``routed_scaling_factor`` x s_e / (sum of s over the chosen + 1e-20); y =
  sum over the chosen experts HELD HERE of w_e E_e(x), plus S(x): ONE
  SwiGLU of ``n_shared_experts`` x ``moe_intermediate_size``; E(x) =
  W_down(SiLU(W_gate x) * W_up x). What the absent experts would add is
  left out, as in the program.
* Dense FFN (the leading layer): the same E at ``intermediate_size``.
* The balancing update (``topk_method: noaux_tc``; arXiv:2412.19437, 2.1.2
  and 4.2): b gets no gradient and no decay; after the optimizer's step,
  in each expert layer, c_e = the step's tokens that chose output e (all
  outputs, the whole batch), b_e <- b_e + rate x sign(mean(c) - c_e).

``assumed`` in the configuration file lists what the published config does
not say. The control (``benchmark/control.py``) rounds every matrix
product's operands to float8 except the router's, which the configuration
states in float32; the limits, with the readings each was set from, are
beside LIMITS below and in PERF.md.
"""

from __future__ import annotations

import math
import sys
import time

import jax
import jax.numpy as jnp
import refcheck

# name -> limit, from readings on the chip (my chip runs, PR 41: sound runs
# on fourteen seeds, the fp8 control on six; PERF.md section 2). The first
# five numbers are the first hybrid configuration's
# (benchmark/references/ling3_flash_lm_ep64.py) and are held against the
# same faults.
# loss_gap (each of 3 steps): sound runs' largest 7.1e-4 (42 readings); the
#   fp8 control reads 1.0e-4...2.4e-3, so about twice the sound reading; held
#   against a left-out part of the batch or of the model (the rotation
#   paired as halves in the program alone moved steps 2 and 3 by 5.7e-3
#   and 4.0e-3).
# first_grad_worst_matrix_leaf (every matrix but the experts' and routers'):
#   sound 0.098-0.306 % on fourteen seeds (a wide tail: 0.14 on the first
#   three, 0.295 on the ninth, 0.306 on the fourteenth; six times the first
#   hybrid cell's: SIX layers of bfloat16 scores, and on eleven runs the
#   leaf is a layer's `kv_down`, whose gradient comes back through the
#   latent's RMSNorm from bfloat16-rounded latents); fp8 control 0.834,
#   0.848, 0.874, 1.01, 1.01, 1.60 %: the limit stands 1.8x above the sound
#   runs' largest and 1.5x below the control's smallest (their geometric
#   middle is 0.505 %; the room is given to the side that has shown a
#   tail); the number that catches a lower precision, and the rotation
#   paired as halves (1.62 %).
# first_grad_worst_expert_leaf (experts' and routers' kernels): NOT a
#   precision number (a held expert sees some 768 of a step's 16384 tokens
#   and 2.2-2.4 % of the held choices flip under bfloat16, each moving a
#   whole row to first order): sound worst 0.065-0.25 %, control 0.70-0.92 %;
#   held against a routed expert left out, mis-scaled or fed the wrong rows
#   (reads 100 %).
# param_change_worst_leaf: sound 0.014-0.034 %, control 0.05-0.11 %: between
#   the sound reading and a step that returns its state (reads 100 %), with
#   the more room above the reading. The routers' BIAS leaves are taken out
#   of it: AdamW does not move them, the balancing update does, by +-rate
#   an entry, and a single near-tie in a count flips a sign and moves a
#   whole entry by 2 x rate.
# update_direction_gap: the cosine between the parameters' change after the
#   followed steps and Adam's first moment then (bias leaves apart, which
#   have no moment), program against reference: both read -0.485, 1e-5 to
#   1e-4 apart; a flipped update reads 0.97.
# router_bias_wrong_way_share: the bias leaves' own number. Of the (step,
#   layer, output) entries whose count in the reference stands farther from
#   the layer's mean than BIAS_MARGIN x sqrt(mean) (1738-1793 of 1920: at
#   the seeded weights the routers' load is far from even), the share whose
#   bias the program moved another way than the reference's rule
#   (sign(mean - c)): fourteen sound runs read 0 (8 of 1765 under the planted
#   rotation, whose activations differ), the fp8 control 0.4-1.1 %; the
#   update left out reads 100 % by construction (no move is a wrong move), a
#   flipped sign 100 %, the held experts' counts alone or another layer's
#   about 50 %. The limit stands between.
LIMITS = {
    "loss_gap": 1.5e-3,
    "first_grad_worst_matrix_leaf": 0.0055,
    "first_grad_worst_expert_leaf": 0.05,
    "param_change_worst_leaf": 0.005,
    "update_direction_gap": 0.2,
    "router_bias_wrong_way_share": 0.2,
}
# How far from its layer's mean a router output's count has to stand for
# its bias's move to be judged, in units of sqrt(mean): a count's own
# standard deviation under uniform routing (27.7 tokens at the cell's mean
# of 768). A bfloat16 program orders near-ties of the 6th and 7th selection
# score the other way; `router_count_shift_bf16` (printed by every run) is
# the largest move of any count, in the same units, that this file's own
# bfloat16-rounded forward shows at the seeded weights: 0.65-1.52 on the chip
# (fourteen seeds, my chip runs, PR 41; one above 1.12). The margin does not
# have to clear every such move: an entry the flips carry across its mean
# costs 1 of some 1760 judged, and the limit leaves room for 350.
BIAS_MARGIN = 1.5
# a bias's move smaller than this (a hundredth of the rate: float32 rounding
# of b + rate - b is 1e-9) counts as no move
_RATE_FLOOR = 1e-5

# every program here runs a handful of times: compile it as fast as can be
_QUICK = {"exec_time_optimization_effort": -1.0}

_NO_DECAY = ("['scale']", "['bias']")


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
        self.H = c["num_attention_heads"]
        self.dn, self.dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
        self.dv, self.rank = c["v_head_dim"], c["kv_lora_rank"]
        self.m, self.F = c["intermediate_size"], c["moe_intermediate_size"]
        self.Fs = c["n_shared_experts"] * self.F
        self.V, self.P = c["vocab_size"], c["max_position_embeddings"]
        self.E, self.held = c["router_num_experts"], c["n_routed_experts"]
        self.held_first = c["held_expert_first_id"]
        self.top_k = c["num_experts_per_tok"]
        if c["n_group"] != 1 or c["topk_group"] != 1:
            raise ValueError("this reference routes over one group")
        self.route_scale = c["routed_scaling_factor"]
        self.dense_layers = c["first_k_dense_replace"]
        self.bias_rate = float(c["router_bias_update_rate"])
        self.theta, self.eps = float(c["rope_theta"]), c["rms_norm_eps"]
        self.opt = c["optimizer"]  # the rehearsal brings its own
        self._init = jax.jit(self._make, compiler_options=_QUICK)
        self._jits = {}    # (precision, program) -> its jitted function

    def kind(self, i: int) -> str:
        """A layer's kind: layers of one kind share their programs."""
        return "mla_mlp" if i < self.dense_layers else "mla_moe"

    def key(self, seed: int):
        return jax.random.key(seed, impl="rbg")

    # ------------------------------------------------------------ weights
    def _make(self, key):
        d, H, f32 = self.d, self.H, jnp.float32
        keys = iter(jax.random.split(key, 4 + 32 * self.L))
        n = lambda shape, std=0.02: std * jax.random.normal(  # noqa: E731
            next(keys), shape, f32)
        k = lambda *shape: {"kernel": n(shape)}  # noqa: E731
        one = lambda size: {"scale": jnp.ones((size,), f32)}  # noqa: E731
        ffn = lambda width, *lead: {  # noqa: E731
            "gate_proj": k(*lead, d, width), "up_proj": k(*lead, d, width),
            "down_proj": k(*lead, width, d)}
        params = {"tok_embed": {"embedding": n((self.V, d))},
                  "final_norm": one(d), "lm_head": k(d, self.V)}
        for i in range(self.L):
            layer = {"input_norm": one(d), "post_attn_norm": one(d),
                     "mla": {
                         "q_proj": k(d, H, self.dn + self.dr),
                         "kv_down": k(d, self.rank),
                         "kv_norm": one(self.rank),
                         "k_rope_proj": k(d, self.dr),
                         "kv_up": k(self.rank, H, self.dn + self.dv),
                         "o_proj": k(H, self.dv, d)}}
            if i < self.dense_layers:
                layer["mlp"] = ffn(self.m)
            else:
                layer["moe"] = {
                    "router": {"kernel": n((d, self.E)),
                               "bias": n((self.E,), 0.01)},
                    "experts": ffn(self.F, self.held),
                    "shared": ffn(self.Fs)}
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
    def _mla(self, p, x, q):
        S, dn, dr = x.shape[0], self.dn, self.dr
        qh = jnp.einsum("sc,chd->shd", q(x), q(p["q_proj"]["kernel"]))
        c = _rms(q(x) @ q(p["kv_down"]["kernel"]), p["kv_norm"]["scale"],
                 self.eps)
        k_pe = q(x) @ q(p["k_rope_proj"]["kernel"])
        kv = jnp.einsum("sr,rhd->shd", q(c), q(p["kv_up"]["kernel"]))
        inv = self.theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
        cos, sin = jnp.cos(ang), jnp.sin(ang)  # (S, dr / 2)

        def rotate(t):  # t (S, ..., dr): the pairs (2i, 2i+1), in place
            shape = (S,) + (1,) * (t.ndim - 2) + (dr // 2,)
            co, si = cos.reshape(shape), sin.reshape(shape)
            a, b = t[..., 0::2], t[..., 1::2]
            return jnp.stack([a * co - b * si, b * co + a * si],
                             -1).reshape(t.shape)

        k_pe = rotate(k_pe)  # once, then repeated for every head
        qh = jnp.concatenate([qh[..., :dn], rotate(qh[..., dn:])], -1)
        kh = jnp.concatenate(
            [kv[..., :dn],
             jnp.broadcast_to(k_pe[:, None, :], (S, self.H, dr))], -1)
        t = jnp.arange(S)

        def head(qkv):  # one head at a time: the scores are S x S float32
            q1, k1, v1 = qkv
            s = q(q1) @ q(k1).T / math.sqrt(dn + dr)
            causal = t[:, None] >= t[None, :]  # computed, not a constant
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return q(w) @ q(v1)

        heads = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
        y = jax.lax.map(jax.checkpoint(head), (
            heads(qh), heads(kh), heads(kv[..., dn:])))
        return jnp.einsum("shd,hdc->sc", q(jnp.moveaxis(y, 0, 1)),
                          q(p["o_proj"]["kernel"]))

    def _route(self, p, x):
        """(weight of each held expert a token (S, held), 0 where it is not
        chosen; chosen (S, E): the token's choices over ALL outputs). The
        product with W_g is float32 in every precision."""
        s = jax.nn.sigmoid(x @ p["kernel"])
        ids = jax.lax.top_k(s + p["bias"], self.top_k)[1]
        chosen = jnp.any(ids[:, :, None] == jnp.arange(self.E), 1)
        picked = jnp.where(chosen, s, 0.0)
        w = self.route_scale * picked \
            / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
        return w[:, self.held_first:self.held_first + self.held], chosen

    def _moe(self, p, x, q):
        w, chosen = self._route(p["router"], x)
        # every held expert in turn on every token, weighted (0 where it
        # is not chosen): a scan over the experts' leading axis
        y, _ = jax.lax.scan(
            lambda y, ew: (y + ew[1][:, None] * _swiglu(x, ew[0], q), None),
            _swiglu(x, p["shared"], q), (p["experts"], w.T))
        return y, chosen

    def _layer(self, i, p, x, q):
        """One residual block: (x, the tokens' choices over all the router's
        outputs, or None)."""
        h = _rms(x, p["input_norm"]["scale"], self.eps)
        x = x + self._mla(p["mla"], h, q)
        h = _rms(x, p["post_attn_norm"]["scale"], self.eps)
        if i < self.dense_layers:
            return x + _swiglu(h, p["mlp"], q), None
        out, chosen = self._moe(p["moe"], h, q)
        return x + out, chosen

    def _logits(self, params, ids, q):
        """ids (S,) -> (logits (S, V), [choices a routed layer]): the model
        in one piece, one row. ``follow`` walks the same layers from the
        host; the tests hold its gradient against ``jax.grad`` of this."""
        x = params["tok_embed"]["embedding"][ids]
        chosen = []
        for i in range(self.L):
            x, on = jax.checkpoint(
                lambda p, x, i=i: self._layer(i, p, x, q))(
                    params[f"layer{i}"], x)
            if on is not None:
                chosen.append(on)
        x = _rms(x, params["final_norm"]["scale"], self.eps)
        return q(x) @ q(params["lm_head"]["kernel"]), chosen

    # ------------------------------------- programs, one a kind of layer
    def _functions(self, precision: str) -> dict:
        """name -> function over the whole batch, its rows in turn (each
        sequence by itself, and one row's internals in memory at a time).
        ``fwd_<kind>``: (p, x) -> (x', choices or None);
        ``bwd_<kind>``: (p, x, dy) -> (dp, dx), the layer's vjp a row, its
        forward recomputed, dp summed over the rows; ``embed`` /
        ``embed_bwd``; ``head``: (final norm, lm head, x, ids) -> (summed
        loss, their gradients and dx)."""
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

        def row_loss(norm, head, x, ids):  # one row: (S, V) logits
            logits = q(_rms(x, norm["scale"], self.eps)) @ q(head["kernel"])
            logp = jax.nn.log_softmax(logits[:-1], -1)
            return -jnp.sum(jnp.take_along_axis(logp, ids[1:, None], -1))

        def batch_loss(norm, head, x, ids):  # rows in turn
            return jnp.sum(jax.lax.map(
                lambda r: jax.checkpoint(row_loss)(norm, head, *r), (x, ids)))

        out["head"] = jax.value_and_grad(batch_loss, argnums=(0, 1, 2))
        out["embed"] = lambda table, ids: table[ids]
        out["embed_bwd"] = lambda table, ids, dx: jnp.zeros_like(
            table).at[ids].add(dx)
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
        xs = [call("embed", params["tok_embed"]["embedding"], ids)]
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
            "head", params["final_norm"], params["lm_head"], xs.pop(), ids)
        grads = {"final_norm": d_norm, "lm_head": d_head}
        for i in reversed(range(self.L)):
            grads[f"layer{i}"], dx = call(
                "bwd_" + self.kind(i), params[f"layer{i}"], xs.pop(), dx)
        grads["tok_embed"] = {"embedding": call(
            "embed_bwd", params["tok_embed"]["embedding"], ids, dx)}
        return loss, grads, chosen

    def routing_flips(self, seed: int, ids, chosen) -> tuple:
        """(share of the (token, held expert) choices of the first batch,
        at the seeded weights, that differ between this float32 forward
        (``chosen``, from the first followed step) and one whose matrix
        operands are rounded to bfloat16: the near-ties of the 6th and 7th
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

            def step(path, p, m, v):
                name = jax.tree_util.keystr(path)
                if is_bias(name):
                    # not the optimizer's: the balancing rule's, from this
                    # step's counts on every output of this layer's router
                    layer = int(path[0].key[len("layer"):])
                    c = counts[layer - self.dense_layers]
                    return p + self.bias_rate * jnp.sign(jnp.mean(c) - c)
                u = (m / c1) / (jnp.sqrt(v / c2) + o["eps"])
                if not name.endswith(_NO_DECAY):
                    u = u + o["weight_decay"] * p
                return p - lr * u

            new = jax.tree_util.tree_map_with_path(step, params, mu, nu)
            return new, mu, nu

        # 687 M parameters in float32 beside the gradient's own buffers:
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
        return compare(ref, observed) + [
            {"name": "routing_flips_bf16_share", "value": flips,
             "limit": None},
            {"name": "router_count_shift_bf16", "value": shift,
             "limit": None, "margin": BIAS_MARGIN}]


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
    """The numbers compared, each beside its limit: the first hybrid
    configuration's (refcheck's, with the first gradient's worst matrix leaf
    taken apart into the expert layers' routed leaves and every other
    matrix, and the direction of the update), the routers' bias leaves out
    of ``param_change_worst_leaf`` (``follow`` puts them with the noise
    leaves), and their own number, ``router_bias_wrong_way_share``
    (LIMITS above says what each is held against)."""
    out = []
    for n in refcheck.compare_steps(ref, observed, LIMITS):
        if n["name"] != "first_grad_worst_matrix_leaf":
            out.append(n)
    gaps = refcheck.leaf_gaps(observed["first_grad"], ref["first_grad"])
    nan_worst = lambda k: (gaps[k] != gaps[k], gaps[k])  # noqa: E731
    routed = sorted((k for k in gaps if refcheck.is_matrix(k)
                     and is_expert(k)), key=nan_worst)
    plain = max((k for k in gaps if refcheck.is_matrix(k)
                 and not is_expert(k)), key=nan_worst)
    out.append({"name": "first_grad_worst_matrix_leaf", "value": gaps[plain],
                "limit": LIMITS["first_grad_worst_matrix_leaf"],
                "leaf": plain})
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
