"""Plain reference for the ``solar_open2_lm_ep40_tp8`` configuration: one
chip's share of Solar-Open2-250B's language model (upstage; its
config.json), its next-token loss over the vocabulary slice, gradients and
the AdamW step, in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``. No kernel, no chunking of the
recurrence, no tiling, no sorting of tokens: the KDA layers run TOKEN BY
TOKEN (``lax.scan`` over t, checkpointed in blocks so that the backward pass
fits; the decay of a token is exp(g_t) itself, so no difference of sums is
ever formed), the attention layer is the S x S score matrix of ONE head at a
time under an explicit mask, the experts are a scan over the held ones with
masks, each sequence by itself (the batch's rows in turn), and the backward
pass is taken LAYER BY LAYER from the host (``follow``): the forward sweep
keeps each layer's input, the backward sweep calls one layer's ``jax.vjp``
at a time. ``_logits`` is the same model in one piece (a test holds the
program's logits against it, and ``jax.grad`` of the program's loss, leaf
by leaf, against the sweep's gradient).

The SHARE. The configuration's file says what this chip holds of a layer:
``num_attention_heads`` query/KDA heads from ``held_head_first_id`` on (8 of
64), ``num_key_value_heads`` KV heads (1 of 8: the one the published
grouping gives those query heads), ``num_experts`` experts from
``held_expert_first_id`` on (8 of the router's ``router_num_experts`` 320),
``vocab_size`` ids (24576 of 196608). The mixers here take their head counts
from the WEIGHTS they are handed, so the same functions compute a share or,
given whole weights, the uncut layer (a test adds the shares up to it). What
the absent heads and experts would add is left out, as in the program; no
exchange is imitated.

Time. The driver cuts a run at 360 s, and such a file's COMPILE costs more
than its arithmetic (PERF.md, PR 26). So a layer's programs are built once a
KIND of layer (two kinds: attention with experts, KDA with experts), every
program asks the compiler for its least effort (``_QUICK``), and the masks
are iota comparisons inside the program, no S x S constant.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_variables(seed)`` here (the runner installs them in
the trainer), the hyper-parameters from the configuration file. The
parameter tree's names and shapes are the interface; the runner refuses a
mismatch.

The layers (x in R^{S x D}; D 4096, heads of d = 128; pre-norm residual
blocks x += mixer(norm x), x += moe(norm x); RMSNorm eps 1e-5, final
RMSNorm, untied head, no dropout, no bias, no auxiliary loss; layer i is
attention where i is in ``gqa_layers`` (i % 4 == 0), else KDA):

* KDA (Kimi Linear, arXiv:2510.26692): q~, k~, v~ = W x a head, each
  through a causal depthwise conv (kernel 4) and SiLU; q, k L2-normalised a
  head (eps 1e-6 under the root), q times d^-1/2;
  g_t = -exp(A_log_h) softplus(W_a2 (W_a1 x_t) + dt_bias) a key channel
  (``kda_use_full_proj`` false: through 128 features; NO lower bound);
  beta_t = 2 sigmoid(W_beta x_t) a head (``kda_allow_neg_eigval``);
  S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,
  o_t = S_t^T q_t; out = W_o [RMSNorm_head(o) * sigmoid(W_g2 (W_g1 x))], the
  gate one value a channel, through 128 features.
* Attention (``use_rope`` false, ``use_gqa_gate``): q = W_q x, k = W_k x,
  v = W_v x; query head h reads KV head h // (64 / 8); NO rotation; scores
  q k^T / sqrt(d), softmax in float32 over the keys j <= i; the output times
  sigmoid(W_gate x), one value a channel; W_o. No q/k norm.
* Experts (every layer: ``first_k_dense_replace`` 0): p = softmax(x W_r)
  over ALL 320, float32; the ``num_experts_per_tok`` largest are chosen;
  weights = the chosen p over their own sum, times ``routed_scaling_factor``
  (1); y = sum over the chosen experts HELD HERE of w_e E_e(x), plus the
  shared expert; E(x) = W_down(SiLU(W_gate x) * W_up x).

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

# name -> limit, from readings on the chip (my chip runs, PR 39; PERF.md
# section 2 has the table with every reading):
# loss_gap (each of 3 steps): the accepted LM cells' limit; the precision
#   hardly moves it; held against a left-out part of the batch or model.
# first_grad_worst_matrix_leaf (every matrix but the experts' and routers'):
#   the number that catches a lower precision (the fp8 control) and another
#   family's mixer (beta left in (0, 1) reads 50 %): between the sound
#   runs' largest and the fp8 control's smallest. NOT a recurrent state
#   carried in bfloat16: planted on the chip it read 0.034 %, a sound run's
#   number (a gap of leaf NORMS, which zero-mean rounding moves to second
#   order; the kernels hand the state to its products as bfloat16 operands
#   anyway). tests/test_kda.py and tools/kda_chip_check.py hold the state.
# first_grad_worst_expert_leaf (experts' and routers' kernels): NOT a
#   precision number (a held expert sees some 205 of a step's 8192 tokens
#   and a few per cent of the held choices flip under bfloat16); its second
#   reading is a routed expert left out (100 %).
# param_change_worst_leaf: between the first reading and 1 (a step that
#   returns its state), with the more room above the reading.
# update_direction_gap: the cosine between the parameters' change after
#   the followed steps and Adam's first moment then, program against
#   reference; an update with its sign flipped changes the cosine's sign.
LIMITS = {
    "loss_gap": 1.5e-3,
    "first_grad_worst_matrix_leaf": 0.0020,
    "first_grad_worst_expert_leaf": 0.05,
    "param_change_worst_leaf": 0.005,
    "update_direction_gap": 0.2,
}

# every program here runs a handful of times: compile it as fast as can be
_QUICK = {"exec_time_optimization_effort": -1.0}

_NO_DECAY = ("['scale']", "['bias']", "_conv']", "['A_log']", "['dt_bias']")


def _rounder(precision: str):
    """refcheck's rounders, plus ``bfloat16`` (operands rounded to the
    program's compute type): used only to count near-tie routing flips.
    ``reduce_precision`` and not a pair of casts: the TPU compiler drops a
    float32 -> bfloat16 -> float32 round trip as excess precision."""
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


class Reference:
    check_steps = 3

    def __init__(self, config: dict, rehearsal: bool = False):
        c = dict(config)
        if rehearsal:
            c.update(config["rehearsal"])
        lin = c["linear_attn_config"]
        self.d, self.L = c["hidden_size"], c["num_hidden_layers"]
        self.dh = c["head_dim"]
        # the share held here (the module docstring)
        self.H, self.Hkv = c["num_attention_heads"], c["num_key_value_heads"]
        self.Hlin = lin["num_heads"]
        self.F = c["moe_intermediate_size"]
        self.V = c["vocab_size"]
        self.E, self.held = c["router_num_experts"], c["n_routed_experts"]
        self.held_first = c["held_expert_first_id"]
        self.top_k = c["num_experts_per_tok"]
        self.route_scale = c["routed_scaling_factor"]
        self.attn_layers = set(c["gqa_layers"])
        self.K, self.rank = lin["short_conv_kernel_size"], c["kda_gate_rank"]
        self.eps = c["rms_norm_eps"]
        if lin["head_dim"] != self.dh or c["first_k_dense_replace"] \
                or c["use_rope"] or not c["kda_allow_neg_eigval"]:
            raise ValueError("this reference is Solar-Open2's: one head "
                             "width, no dense layer, no rotation, beta to 2")
        self.opt = c["optimizer"]  # the rehearsal brings its own
        self.time_block = 128  # tokens between saved states of the scan
        self._init = jax.jit(self._make, compiler_options=_QUICK)
        self._jits = {}    # (precision, program) -> its jitted function

    def kind(self, i: int) -> str:
        """A layer's kind: layers of one kind share their programs."""
        return "gqa" if i in self.attn_layers else "kda"

    def key(self, seed: int):
        return jax.random.key(seed, impl="rbg")

    # ------------------------------------------------------------ weights
    def _make(self, key):
        d, dh, f32 = self.d, self.dh, jnp.float32
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
            layer = {"input_norm": one(d), "post_attn_norm": one(d)}
            if self.kind(i) == "gqa":
                H = self.H
                layer["gqa"] = {
                    "q_proj": k(d, H, dh), "k_proj": k(d, self.Hkv, dh),
                    "v_proj": k(d, self.Hkv, dh), "gc_proj": k(d, H, dh),
                    "o_proj": k(H, dh, d)}
            else:
                H = self.Hlin
                # the public fla layer's rule (`assumed` in the file):
                # exp(A_log) uniform in (1, 16); the step softplus(dt_bias)
                # log-uniform in (1e-3, 0.1), dt_bias its inverse softplus
                dt = jnp.maximum(jnp.exp(
                    jax.random.uniform(next(keys), (H, dh), f32)
                    * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3)),
                    1e-4)
                layer["kda"] = {
                    "q_proj": k(d, H, dh), "k_proj": k(d, H, dh),
                    "v_proj": k(d, H, dh),
                    "a_down": k(d, self.rank), "a_proj": k(self.rank, H, dh),
                    "q_conv": n((self.K, H, dh)), "k_conv": n((self.K, H, dh)),
                    "v_conv": n((self.K, H, dh)),
                    "A_log": jnp.log(jax.random.uniform(
                        next(keys), (H,), f32, 1.0, 16.0)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "beta_proj": k(d, H),
                    "gc_down": k(d, self.rank),
                    "gc_proj": k(self.rank, H, dh),
                    "o_norm": one(dh), "o_proj": k(H, dh, d)}
            layer["moe"] = {
                "router": {"kernel": n((d, self.E))},
                "experts": ffn(self.F, self.held),
                "shared": ffn(self.F)}
            params[f"layer{i}"] = layer
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

        grad_fn = jax.jit(lambda mu: refcheck.leaf_norms(
            jax.tree.map(lambda m: m / (1.0 - b1), mu)),
            compiler_options=_QUICK)
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
    def _kda(self, p, x, q):
        """x (S, d) -> (S, d): the recurrence, token by token, over the
        heads ``p`` holds (a share's, or all of them)."""
        S, dh, K = x.shape[0], self.dh, self.K
        H = p["q_proj"]["kernel"].shape[1]
        proj = lambda name: jnp.einsum(  # noqa: E731
            "sc,chd->shd", q(x), q(p[name]["kernel"]))
        through = lambda down, up: jnp.einsum(  # noqa: E731
            "sr,rhd->shd", q(q(x) @ q(p[down]["kernel"])),
            q(p[up]["kernel"]))

        def conv_silu(y, w):  # y_t = sum_j w[j] y_{t-(K-1-j)}
            pad = jnp.concatenate([jnp.zeros((K - 1, H, dh)), y], 0)
            return jax.nn.silu(sum(pad[j:j + S] * w[j] for j in range(K)))

        unit = lambda y: y * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(y * y, -1, keepdims=True) + 1e-6)
        qs = unit(conv_silu(proj("q_proj"), p["q_conv"])) * dh ** -0.5
        ks = unit(conv_silu(proj("k_proj"), p["k_conv"]))
        vs = conv_silu(proj("v_proj"), p["v_conv"])
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            through("a_down", "a_proj") + p["dt_bias"])
        beta = 2.0 * jax.nn.sigmoid(q(x) @ q(p["beta_proj"]["kernel"]))

        def token(state, t):  # state (H, dk, dv)
            q_t, k_t, v_t, g_t, b_t = t
            state = state * jnp.exp(g_t)[:, :, None]
            read = jnp.einsum("hkv,hk->hv", q(state), q(k_t))
            state = state + b_t[:, None, None] * jnp.einsum(
                "hk,hv->hkv", q(k_t), q(v_t - read))
            return state, jnp.einsum("hkv,hk->hv", q(state), q(q_t))

        block = math.gcd(S, self.time_block)
        blocks = jax.tree.map(
            lambda a: a.reshape(S // block, block, *a.shape[1:]),
            (qs, ks, vs, g, beta))
        _, o = jax.lax.scan(
            jax.checkpoint(lambda s, ts: jax.lax.scan(token, s, ts)),
            jnp.zeros((H, dh, dh)), blocks)
        o = _rms(o.reshape(S, H, dh), p["o_norm"]["scale"], self.eps)
        o = o * jax.nn.sigmoid(through("gc_down", "gc_proj"))
        return jnp.einsum("shd,hdc->sc", q(o), q(p["o_proj"]["kernel"]))

    def _gqa(self, p, x, q):
        """x (S, d) -> (S, d): one query head at a time over the heads
        ``p`` holds; query head h reads KV head h // (heads / KV heads),
        which for a share is the published grouping's own."""
        S, dh = x.shape[0], self.dh
        proj = lambda name: jnp.einsum(  # noqa: E731
            "sc,chd->hsd", q(x), q(p[name]["kernel"]))
        qh, kh, vh = proj("q_proj"), proj("k_proj"), proj("v_proj")
        group = qh.shape[0] // kh.shape[0]
        t = jnp.arange(S)

        def head(args):  # the scores are S x S float32
            q1, kv = args
            s = q(q1) @ q(kh[kv]).T / math.sqrt(dh)
            causal = t[:, None] >= t[None, :]  # computed, not a constant
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return q(w) @ q(vh[kv])

        y = jax.lax.map(jax.checkpoint(head),
                        (qh, jnp.arange(qh.shape[0]) // group))
        y = jnp.moveaxis(y, 0, 1) * jax.nn.sigmoid(jnp.einsum(
            "sc,chd->shd", q(x), q(p["gc_proj"]["kernel"])))
        return jnp.einsum("shd,hdc->sc", q(y), q(p["o_proj"]["kernel"]))

    def _route(self, p, x, held_first=None, held=None):
        """(weight of each held expert a token (S, held), 0 where it is not
        chosen). The product with W_r is float32 in every precision."""
        first = self.held_first if held_first is None else held_first
        held = self.held if held is None else held
        s = jax.nn.softmax(x @ p["kernel"], axis=-1)
        ids = jax.lax.top_k(s, self.top_k)[1]
        chosen = jnp.any(ids[:, :, None] == jnp.arange(s.shape[-1]), 1)
        w = self.route_scale * jnp.where(chosen, s, 0.0) \
            / jnp.sum(jnp.where(chosen, s, 0.0), -1, keepdims=True)
        return w[:, first:first + held]

    def _moe(self, p, x, q, held_first=None):
        w = self._route(p["router"], x, held_first,
                        p["experts"]["gate_proj"]["kernel"].shape[0])
        # every held expert in turn on every token, weighted (0 where it
        # is not chosen): a scan over the experts' leading axis
        y, _ = jax.lax.scan(
            lambda y, ew: (y + ew[1][:, None] * _swiglu(x, ew[0], q), None),
            _swiglu(x, p["shared"], q), (p["experts"], w.T))
        return y, w > 0

    def _layer(self, i, p, x, q):
        """One residual block: (x, the held experts' choices)."""
        h = _rms(x, p["input_norm"]["scale"], self.eps)
        x = x + (self._gqa(p["gqa"], h, q) if self.kind(i) == "gqa"
                 else self._kda(p["kda"], h, q))
        h = _rms(x, p["post_attn_norm"]["scale"], self.eps)
        out, on_held = self._moe(p["moe"], h, q)
        return x + out, on_held

    def _logits(self, params, ids, q):
        """ids (S,) -> (logits (S, V), [held-expert choices a layer]): the
        model in one piece, one row. ``follow`` walks the same layers from
        the host."""
        x = params["tok_embed"]["embedding"][ids]
        chosen = []
        for i in range(self.L):
            x, on_held = jax.checkpoint(
                lambda p, x, i=i: self._layer(i, p, x, q))(
                    params[f"layer{i}"], x)
            chosen.append(on_held)
        x = _rms(x, params["final_norm"]["scale"], self.eps)
        return q(x) @ q(params["lm_head"]["kernel"]), chosen

    # ------------------------------------- programs, one a kind of layer
    def _functions(self, precision: str) -> dict:
        """name -> function over the whole batch, its rows in turn (each
        sequence by itself, and one row's internals in memory at a time).
        ``fwd_<kind>``: (p, x) -> (x', held choices); ``bwd_<kind>``:
        (p, x, dy) -> (dp, dx), the layer's vjp a row, its forward
        recomputed, dp summed over the rows; ``embed`` / ``embed_bwd``;
        ``head``: (final norm, lm head, x, ids) -> (summed loss, their
        gradients and dx)."""
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
        asked) backward a layer at a time: (summed loss, gradients, held
        choices (layers, batch, S, held))."""
        call = lambda name, *a: self._call(precision, name, *a)  # noqa: E731
        xs = [call("embed", params["tok_embed"]["embedding"], ids)]
        chosen = []
        for i in range(self.L):
            x, on_held = call("fwd_" + self.kind(i), params[f"layer{i}"],
                              xs[-1])
            xs.append(x)
            chosen.append(on_held)
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

    def routing_flips(self, seed: int, ids, chosen) -> float:
        """Share of the (token, held expert) choices of the first batch,
        at the seeded weights, that differ between this float32 forward
        (``chosen``, from the first followed step) and one whose matrix
        operands are rounded to bfloat16: the near-ties of the 8th and 9th
        score that a bfloat16 program orders the other way. Printed
        unjudged."""
        with jax.default_matmul_precision("highest"):
            params = self.init_variables(seed)["params"]
            rounded = self._sweep("bfloat16", params, ids, False)[2]
            return int(jnp.sum(rounded != chosen)) \
                / max(int(jnp.sum(chosen)), 1)

    def follow(self, seed: int, batches: list, precision: str = "float32"):
        """The first steps from the seeded weights on the given batches:
        losses, the per-leaf norms of the first gradient as the optimizer
        gets it (after the clip) and of the parameters' change; ``chosen``:
        the held choices of the first batch at the seeded weights."""
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

        # 841 M parameters in float32 beside the gradient's own buffers:
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
            for count, batch in enumerate(batches):
                began = time.perf_counter()
                ids = jnp.asarray(batch["input_ids"])
                total = float(ids.shape[0] * (ids.shape[1] - 1))
                loss, grads, chosen = self._sweep(precision, params, ids,
                                                  True)
                if first_choices is None:
                    first_choices = chosen
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
                lambda a, b, m: _change_numbers(
                    jax.tree.map(jnp.subtract, a, b), m),
                donate_argnums=0, compiler_options=_QUICK)(
                    params, params0, jax.device_put(mu)))
        return {"losses": losses, "first_grad": grad_norms[0],
                "chosen": first_choices,
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
        return compare(ref, observed) + [
            {"name": "routing_flips_bf16_share", "value": flips,
             "limit": None}]


def _change_numbers(change, mu):
    """(per-leaf norms of the parameters' change, its cosine with Adam's
    first moment over every leaf together): descent reads negative."""
    dot = sum(jnp.sum(c * m) for c, m in zip(jax.tree.leaves(change),
                                             jax.tree.leaves(mu)))
    size = lambda t: jnp.sqrt(sum(jnp.sum(x * x)  # noqa: E731
                                  for x in jax.tree.leaves(t)))
    return refcheck.leaf_norms(change), dot / (size(change) * size(mu))


def is_expert(leaf: str) -> bool:
    return "['experts']" in leaf or "['router']" in leaf


def compare(ref: dict, observed: dict) -> list:
    """The numbers compared, each beside its limit: refcheck's, with the
    first gradient's worst matrix leaf taken apart into the expert layers'
    routed leaves (experts and routers; their median and every gap printed
    beside the worst) and every other matrix, and the direction of the
    update (LIMITS above says what each is held against)."""
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
    return out
