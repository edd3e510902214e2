"""Plain reference for the ``laguna_s_lm_ep32`` configuration: one chip's
share of Laguna-S-2.1's language model (poolside; its config.json), its
next-token loss over the vocabulary slice, gradients and the AdamW step, in
straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``. No kernel, no tiling, no
sorting of tokens: attention is the S x S score matrix of ONE head at a
time under an explicit mask (``lax.map`` over the query heads, each KV head
repeated for the query heads that read it), the experts are a scan over the
held ones with masks, each sequence by itself (the batch's rows in turn),
and the backward pass is taken LAYER BY LAYER from the host (``follow``):
the forward sweep keeps each layer's input, the backward sweep calls one
layer's ``jax.vjp`` at a time. ``_logits`` is the same model in one piece;
a test holds the sweep's gradient against ``jax.grad`` of it.

Time. The driver cuts a run at 360 s, and such a file's COMPILE costs more
than its arithmetic (PERF.md, PR 26). So a layer's programs are built once
a KIND of layer (three kinds: a full-attention layer with the dense FFN, a
window layer with experts, a full layer with experts), every program asks
the compiler for its least effort (``_QUICK``), and the masks are iota
comparisons inside the program, no S x S constant.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_variables(seed)`` here (the runner installs them in
the trainer), the hyper-parameters from the configuration file. The
parameter tree's names and shapes are the interface; the runner refuses a
mismatch.

The layers (x in R^{S x d}; pre-norm residual blocks, RMSNorm eps 1e-6,
final RMSNorm, untied head, no dropout, no bias, no auxiliary loss):

* Mixer of layer l: H_l = ``num_attention_heads_per_layer[l]`` query heads
  (48 on ``full_attention`` layers, 72 on ``sliding_attention`` layers) over
  ``num_key_value_heads`` KV heads of ``head_dim``: q = W_q x, k = W_k x,
  v = W_v x; query head h reads KV head h // (H_l / 8). Rotation by the
  layer's type (``rope_parameters``): full: YaRN on the first
  ``partial_rotary_factor`` x head_dim dims of each head, the rest pass
  (pair i of the R/2 rotated pairs has f_i = theta^(-2i/R); the correction
  dims are floor / ceiling of R ln(original / (beta 2 pi)) / (2 ln theta)
  at beta_fast / beta_slow, clamped to [0, R - 1]; ramp_i = clip((i - low)
  / (high - low), 0, 1); inv_freq_i = f_i / factor * ramp_i + f_i (1 -
  ramp_i); cos and sin times ``attention_factor``); window: plain rope at
  its own theta over the whole head. Split-half pairs. Scores q k^T /
  sqrt(head_dim), softmax in float32 over the keys j <= i, on window
  layers also i - j < ``sliding_window``. o_h = sigmoid(x W_g)_h attn_h
  (``gating`` per-head), out = concat_h(o_h) W_o.
* FFN of the ``dense`` layers: W_down(SiLU(W_gate x) * W_up x) at
  ``intermediate_size``. Of the ``sparse`` layers: logits x W_r over ALL
  the layer's experts, float32; scores = softmax over them; the
  ``num_experts_per_tok`` largest are chosen; weights = the chosen scores
  over their own sum, times ``moe_routed_scaling_factor``; y = sum over the
  chosen experts HELD HERE of w_e E_e(x), plus the shared expert (no gate
  on it). What the absent experts would add is left out, as in the program.

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

# name -> limit, from readings on the chip (my chip runs, PR 30: twelve sound
# runs on twelve seeds, the fp8 control on six; PERF.md section 2):
# loss_gap (each of 3 steps): sound runs' largest 7.2e-4 (36 readings, root
#   mean square 2.9e-4); the fp8 control's largest a seed reads
#   1.7e-3...4.6e-3, over the limit on every seed tried. The hybrid cell's
#   limit: 5.8 times the first reading, five sigma of the sound readings;
#   it is held against a left-out part of the batch or of the model.
# first_grad_worst_matrix_leaf (every matrix but the experts' and routers'):
#   sound 0.068-0.118 % (the full layers' q and k projections, whose
#   rotation tables the program applies in bfloat16, times YaRN's 1.485);
#   fp8 control 0.269, 0.271, 0.313, 0.341, 0.397, 0.400 %: the limit is
#   their geometric middle, 1.5x from each: the number that catches a
#   lower precision.
# first_grad_worst_expert_leaf (experts' and routers' kernels): NOT a
#   precision number (a held expert sees some 320 of a step's 8192 tokens
#   and 3.1-3.6 % of the held choices flip under bfloat16, a near-tie of
#   the 10th and 11th score, each flip moving a whole row to first order):
#   sound worst 0.08-0.20 %, control worst 0.32-1.11 %. Its two readings
#   are the sound runs' largest and the fault it is there for, a routed
#   expert left out, mis-scaled or fed the wrong rows (100 %; one expert of
#   eight zeroed at the rehearsal's size reads over the limit: the test).
# param_change_worst_leaf: sound 0.010-0.029 %; the control hardly moves it
#   (0.044-0.074 %); against a step that returns its state (reads 100 %):
#   between the first reading (0.015 %) and 1, the hybrid cell's limit.
# update_direction_gap: the cosine between the parameters' change after
#   the followed steps and Adam's first moment then, program against
#   reference: both sides read -0.400, 2.8e-5...4.7e-5 apart; an update
#   with its sign flipped changes the cosine's sign: a gap of 0.80.
# Over ALL leaves the first gradient's worst is a router or an expert
# (0.10-0.20 %; control 0.34-1.11 %): printed unjudged.
LIMITS = {
    "loss_gap": 1.5e-3,
    "first_grad_worst_matrix_leaf": 0.0018,
    "first_grad_worst_expert_leaf": 0.05,
    "param_change_worst_leaf": 0.005,
    "update_direction_gap": 0.2,
}

# every program here runs a handful of times: compile it as fast as can be
_QUICK = {"exec_time_optimization_effort": -1.0}

_NO_DECAY = ("['scale']",)


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


class Reference:
    check_steps = 3

    def __init__(self, config: dict, rehearsal: bool = False):
        c = dict(config)
        if rehearsal:
            c.update(config["rehearsal"])
        self.d, self.L = c["hidden_size"], c["num_hidden_layers"]
        self.heads = list(c["num_attention_heads_per_layer"])
        self.types = list(c["layer_types"])
        self.ffns = list(c["mlp_layer_types"])
        assert len(self.heads) == len(self.types) == len(self.ffns) == self.L
        self.Hkv, self.dh = c["num_key_value_heads"], c["head_dim"]
        self.window = c["sliding_window"]
        self.rope = c["rope_parameters"]
        self.m, self.F = c["intermediate_size"], c["moe_intermediate_size"]
        self.Fs = c["shared_expert_intermediate_size"]
        self.V = c["vocab_size"]
        self.E, self.held = c["router_num_experts"], c["num_experts"]
        self.held_first = c["held_expert_first_id"]
        self.top_k = c["num_experts_per_tok"]
        self.route_scale = c["moe_routed_scaling_factor"]
        self.eps = c["rms_norm_eps"]
        self.opt = c["optimizer"]  # the rehearsal brings its own
        self._init = jax.jit(self._make, compiler_options=_QUICK)
        self._jits = {}    # (precision, program) -> its jitted function

    def is_window(self, i: int) -> bool:
        return self.types[i] == "sliding_attention"

    def mixer_name(self, i: int) -> str:
        """The parameter tree's name for layer i's mixer."""
        return "swa" if self.is_window(i) else "gqa"

    def kind(self, i: int) -> str:
        """A layer's kind: layers of one kind share their programs."""
        return ("window" if self.is_window(i) else "full") + "_" \
            + self.ffns[i] + f"_h{self.heads[i]}"

    def key(self, seed: int):
        return jax.random.key(seed, impl="rbg")

    # ------------------------------------------------------------ weights
    def _make(self, key):
        d, dh, f32 = self.d, self.dh, jnp.float32
        keys = iter(jax.random.split(key, 4 + 16 * self.L))
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
            H = self.heads[i]
            layer = {"input_norm": one(d), "post_attn_norm": one(d),
                     self.mixer_name(i): {
                         "q_proj": k(d, H, dh), "k_proj": k(d, self.Hkv, dh),
                         "v_proj": k(d, self.Hkv, dh), "g_proj": k(d, H),
                         "o_proj": k(H, dh, d)}}
            if self.ffns[i] == "dense":
                layer["mlp"] = ffn(self.m)
            else:
                layer["moe"] = {"router": {"kernel": n((d, self.E))},
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
    def _angles(self, window: bool, S: int):
        """(cos, sin) of shape (S, 1, R/2) and the rotated width R."""
        r = self.rope["sliding_attention" if window else "full_attention"]
        R = int(self.dh * r["partial_rotary_factor"])
        theta = float(r["rope_theta"])
        inv = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
        scale = 1.0
        if r["rope_type"] == "yarn":
            def correction(turns):
                return R * math.log(r["original_max_position_embeddings"]
                                    / (turns * 2 * math.pi)) \
                    / (2 * math.log(theta))

            low = max(math.floor(correction(r["beta_fast"])), 0)
            high = min(math.ceil(correction(r["beta_slow"])), R - 1)
            ramp = jnp.clip((jnp.arange(R // 2, dtype=jnp.float32) - low)
                            / max(high - low, 1e-3), 0.0, 1.0)
            inv = inv / r["factor"] * ramp + inv * (1.0 - ramp)
            scale = r["attention_factor"]
        else:
            assert r["rope_type"] == "default", r["rope_type"]
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
        return (scale * jnp.cos(ang)[:, None], scale * jnp.sin(ang)[:, None],
                R)

    def _mix(self, i, p, x, q):
        """x (S, d) -> (S, d): layer i's attention, a head at a time."""
        S, H, dh = x.shape[0], self.heads[i], self.dh
        window = self.window if self.is_window(i) else 0
        proj = lambda name: jnp.einsum(  # noqa: E731
            "sc,chd->shd", q(x), q(p[name]["kernel"]))
        cos, sin, R = self._angles(self.is_window(i), S)

        def rotate(t):  # the first R dims in split halves, the rest pass
            a, b = t[..., :R // 2], t[..., R // 2:R]
            return jnp.concatenate(
                [a * cos - b * sin, b * cos + a * sin, t[..., R:]], -1)

        # query head h reads KV head h // (H / Hkv)
        shared = lambda t: jnp.repeat(t, H // self.Hkv, axis=1)  # noqa: E731
        t = jnp.arange(S)

        def head(qkv):  # one head at a time: the scores are S x S float32
            q1, k1, v1 = qkv
            s = q(q1) @ q(k1).T / math.sqrt(dh)
            ahead = t[:, None] - t[None, :]  # i - j, computed, no constant
            keep = ahead >= 0
            if window:
                keep = keep & (ahead < window)
            w = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
            return q(w) @ q(v1)

        heads = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
        y = jax.lax.map(jax.checkpoint(head), (
            heads(rotate(proj("q_proj"))),
            heads(shared(rotate(proj("k_proj")))),
            heads(shared(proj("v_proj")))))
        y = jnp.moveaxis(y, 0, 1) \
            * jax.nn.sigmoid(q(x) @ q(p["g_proj"]["kernel"]))[:, :, None]
        return jnp.einsum("shd,hdc->sc", q(y), q(p["o_proj"]["kernel"]))

    def _route(self, p, x):
        """(weight of each held expert a token (S, held), 0 where it is not
        chosen). The product with W_r is float32 in every precision."""
        s = jax.nn.softmax(x @ p["kernel"], axis=-1)
        ids = jax.lax.top_k(s, self.top_k)[1]
        chosen = jnp.any(ids[:, :, None] == jnp.arange(self.E), 1)
        w = self.route_scale * jnp.where(chosen, s, 0.0) \
            / jnp.sum(jnp.where(chosen, s, 0.0), -1, keepdims=True)
        return w[:, self.held_first:self.held_first + self.held]

    def _moe(self, p, x, q):
        w = self._route(p["router"], x)
        # every held expert in turn on every token, weighted (0 where it
        # is not chosen): a scan over the experts' leading axis
        y, _ = jax.lax.scan(
            lambda y, ew: (y + ew[1][:, None] * _swiglu(x, ew[0], q), None),
            _swiglu(x, p["shared"], q), (p["experts"], w.T))
        return y, w > 0

    def _layer(self, i, p, x, q):
        """One residual block: (x, the held experts' choices or None)."""
        h = _rms(x, p["input_norm"]["scale"], self.eps)
        x = x + self._mix(i, p[self.mixer_name(i)], h, q)
        h = _rms(x, p["post_attn_norm"]["scale"], self.eps)
        if self.ffns[i] == "dense":
            return x + _swiglu(h, p["mlp"], q), None
        out, on_held = self._moe(p["moe"], h, q)
        return x + out, on_held

    def _logits(self, params, ids, q):
        """ids (S,) -> (logits (S, V), [held-expert choices a layer]): the
        model in one piece, one row. ``follow`` walks the same layers from
        the host; the tests hold its gradient against ``jax.grad`` of
        this."""
        x = params["tok_embed"]["embedding"][ids]
        chosen = []
        for i in range(self.L):
            x, on_held = jax.checkpoint(
                lambda p, x, i=i: self._layer(i, p, x, q))(
                    params[f"layer{i}"], x)
            if on_held is not None:
                chosen.append(on_held)
        x = _rms(x, params["final_norm"]["scale"], self.eps)
        return q(x) @ q(params["lm_head"]["kernel"]), chosen

    # ------------------------------------- programs, one a kind of layer
    def _functions(self, precision: str) -> dict:
        """name -> function over the whole batch, its rows in turn (each
        sequence by itself, and one row's internals in memory at a time).
        ``fwd_<kind>``: (p, x) -> (x', held choices or None);
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
        asked) backward a layer at a time: (summed loss, gradients, held
        choices (routed layers, batch, S, held))."""
        call = lambda name, *a: self._call(precision, name, *a)  # noqa: E731
        xs = [call("embed", params["tok_embed"]["embedding"], ids)]
        chosen = []
        for i in range(self.L):
            x, on_held = call("fwd_" + self.kind(i), params[f"layer{i}"],
                              xs[-1])
            xs.append(x)
            if on_held is not None:
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
        operands are rounded to bfloat16: the near-ties of the 10th and
        11th score that a bfloat16 program orders the other way. Printed
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

        # 811 M parameters in float32 beside the gradient's own buffers:
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
