"""Plain reference for the ``mellum2_12b_a2_5b_lm_ep4`` configuration: one
chip's share of Mellum2-12B-A2.5B's language model (JetBrains; config.json of
``Mellum2-12B-A2.5B-Instruct``, ``model_type: mellum``), its next-token loss
over the vocabulary slice, gradients and the AdamW step, in straightforward
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``. No
kernel, no tiling, no sorting of tokens: attention is the S x S score matrix
of ONE head at a time under an explicit mask (``lax.map`` over the query
heads, each KV head repeated for the eight query heads that read it), the
experts are a scan over the held ones with masks, each sequence by itself
(the batch's rows in turn).

This file holds the model's EQUATIONS and the limits of its cell: the weights'
draw, the rotation's tables, the mixer, the router, the experts, the residual
block. Everything else is ``benchmark/reflayers.py``'s ``LayeredReference``,
which the next configuration's reference shares: the model in one piece
(``_logits``), the backward pass taken LAYER BY LAYER from the host
(``follow``: the forward sweep keeps each layer's input, the backward sweep
calls one layer's ``jax.vjp`` at a time; a test holds the sweep's gradient
against ``jax.grad`` of ``_logits``), the clip and AdamW, the probes, the
numbers compared. A layer's programs are built once a KIND of layer (two
kinds: a window layer, a full layer, both with experts) at the compiler's
least effort, and the masks are iota comparisons inside the program, no S x S
constant: the driver cuts a run at 360 s.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_variables(seed)`` (the runner installs them in
the trainer), the hyper-parameters from the configuration file. The
parameter tree's names and shapes are the interface; the runner refuses a
mismatch.

The layers (x in R^{S x d}; pre-norm residual blocks, RMSNorm with a learned
scale and ``rms_norm_eps``, a final RMSNorm, an untied head, no bias
anywhere, no dropout, no auxiliary loss; every layer has an attention mixer
and an expert FFN: no dense layer, no shared expert):

* Mixer: q = W_q h (``num_attention_heads`` x ``head_dim``), k, v = W_k h,
  W_v h (``num_key_value_heads`` x ``head_dim``); an RMSNorm over each head's
  q and each head's k (one learned vector for q, one for k, shared by the
  heads; ``assumed`` in the configuration file) BEFORE the rotation; all
  ``head_dim`` dims rotated as split halves (i, i + head_dim / 2), positions
  from 0, by the layer's type (``rope_parameters``): ``sliding_attention``
  plain, f_i = theta^(-2i / R); ``full_attention`` YaRN as transformers'
  ``_compute_yarn_parameters`` gives it: the correction dims are floor /
  ceiling of R ln(original / (beta 2 pi)) / (2 ln theta) at ``beta_fast`` /
  ``beta_slow``, clamped to [0, R - 1]; ramp_i = clip((i - low) / (high -
  low), 0, 1); inv_freq_i = f_i / factor * ramp_i + f_i (1 - ramp_i); cos and
  sin times ``attention_factor``. Query head h reads KV head h // (heads /
  KV heads); scores q k^T / sqrt(head_dim), softmax in float32 over the keys
  j <= i, on sliding layers also i - j < ``sliding_window``; no gate; out =
  concat_h(attn_h) W_o.
* Expert FFN: s = softmax(h W_r) over ALL the router's outputs, float32; the
  ``num_experts_per_tok`` largest are chosen; weights = the chosen scores over
  their own sum (``norm_topk_prob``), no further scale, no bias; y = sum over
  the chosen experts HELD HERE of w_e E_e(h), E_e(h) = W_down(SiLU(W_gate h)
  * W_up h) at ``moe_intermediate_size``. What the absent experts would add
  is left out, as in the program. With every expert held (``num_experts`` =
  ``router_num_experts``) this is the uncut layer: the shares test's.
* Head: logits = RMSNorm(x) W_head over the slice's ids.

``assumed`` in the configuration file lists what the published config does
not say (the head norms, no auxiliary loss, the left-out MTP head). The
control (``benchmark/control.py``) rounds every matrix product's operands to
float8 except the router's, which the configuration states in float32; the
limits, with the readings each was set from, are beside LIMITS below and in
PERF.md.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import reflayers

# name -> limit, from readings on the chip (my chip runs, PR 48, call B: eight
# sound runs on eight seeds under the seeded draw below, the fp8 control on
# three, two faults planted through the runner; held in call C by six more
# sound seeds and two more control seeds from `git archive` of the tree, whose
# largest sound readings were 0.0223 % (median leaf), 0.661 % (worst leaf)
# and 0.125 % (expert leaf), the control's smallest median 0.141 %; PERF.md
# section 2 has the table). The per-leaf numbers are gaps between NORMS,
# |program's - reference's| / reference's.
# loss_gap (each of 3 steps): sound 5e-6...1.97e-4 (24 readings); fp8 control,
#   a seed's largest 5.3e-4, 8.3e-4, 1.29e-3 (hardly moves). The accepted LM
#   cells' limit, as the contract has it for a loss that precision hardly
#   moves: it leaves the first reading 19 times of room (three are asked) and
#   the largest 7.6. It has no upper reading here: every control and both
#   planted faults pass it.
# first_grad_median_matrix_leaf (the median of the 18 matrices that are not
#   experts' or routers'): sound 0.0089-0.0198 % (0.89, 0.90, 1.01, 1.11,
#   1.13, 1.34, 1.62, 1.98 e-4); fp8 control 0.152, 0.155, 0.168 %: the limit
#   is their geometric middle, 2.8 times above the sound runs' largest and
#   2.8 below the control's smallest. THE number that catches a lower
#   precision; a window layer run without its window reads 30 %, the two
#   kinds' rotary tables swapped 84 %.
# first_grad_worst_matrix_leaf (the same leaves' worst): NOT a precision
#   number. Sound 0.637-0.650 % on EVERY seed, always the full layer's q or
#   k projection (its v and o 0.43-0.44 %, every window layer's leaf under
#   0.014 %): the program's rotary tables are bfloat16 (`apply_rope` casts
#   them to the activations' type, as the family's own code does), and
#   YaRN's factor 1.27726 rounds to 1.27344 there, -0.30 % on q and on k, so
#   the full layer's softmax runs 0.6 % cooler on the pairs whose angle stays
#   near 0 (half of them at factor 16). The fp8 control, whose tables are
#   float32, reads 0.22-0.25 %. Held against a fault in one kind of layer,
#   which reads 31.9 % (no window) and 161 % (tables swapped): the limit
#   stands 3.1 times above the first and 16 below the second.
# first_grad_worst_expert_leaf (experts' and routers' kernels): sound
#   0.023-0.067 % (a router's kernel; the leaves' median 0.004-0.016 %),
#   control 0.22-0.28 %; held against a held expert left out, mis-scaled or
#   fed the wrong rows (reads 100 %): the hybrid cells' limit.
# param_change_worst_leaf: set between its two readings. Sound 0.0084-0.0315 %
#   (fourteen seeds, calls B and C; the largest on a router's kernel); the
#   least of what it is held against is the kept test's fault at the cell's
#   size, a window layer run without its window, 0.49 % (call B; the tables
#   swapped 0.81 %, a step that returns its state 100 %): 15.6 times the
#   sound largest. The limit, 0.1 %, stands 3.2 times above the first and 4.9
#   below the second. NOT a precision number: the fp8 control reads
#   0.019-0.023 %, inside the sound band. The hybrid cells' 0.5 % would
#   stand ABOVE the second reading here.
# update_direction_gap: the cosine between the parameters' change after the
#   followed steps and Adam's first moment then, program against reference:
#   both read -0.555, 3.0e-5...5.8e-5 apart (control 1.6e-4...1.9e-4); a
#   flipped update reads 1.1.
LIMITS = {
    "loss_gap": 1.5e-3,
    "first_grad_median_matrix_leaf": 5.5e-4,
    "first_grad_worst_matrix_leaf": 0.02,
    "first_grad_worst_expert_leaf": 0.05,
    "param_change_worst_leaf": 0.001,
    "update_direction_gap": 0.2,
}

# The seeded draw (`assumed` `init` in the configuration file): every matrix
# N(0, 0.02) but the input table, N(0, EMBED_STD), and the mixers' output
# projections, N(0, OUT_STD). Drawn flat at 0.02 the table's rows (RMS 0.02)
# are smaller than what the first attention layer adds (the softmax's mean
# of some hundreds of values through W_o: 0.04-0.1, the SAME for every token
# of a window), so every norm hands the routers the window's common vector:
# on the chip three quarters of a sequence's tokens chose one expert, a
# layer's pairs on the held experts swung 19 k to 59 k by the step (mean
# 32768) and the step's time with them (my chip runs, PR 48). A model in a
# long-context stage is TRAINED: its routing is near-uniform. These two
# numbers give the seeded weights that property (the rows of the table carry
# the token; a mixer's common part grows by a quarter a layer, not sixty
# times): on the chip a layer's held pairs read 32.3-33.6 k on every step of
# eight seeds, the fullest expert 1.07 times the mean, and two seeds' steps
# stand 0.05 % apart (my chip runs, PR 48, call B). No public source gives
# the two numbers (a sandbox forward after call A found them); the nearest
# public rules are Megatron-LM's sigma / sqrt(2 L) for output projections,
# 0.00267 at the model's 28 layers, and torch's N(0, 1) for an embedding.
EMBED_STD = 0.5
OUT_STD = 0.002

_rms = reflayers.rms


def _swiglu(x, p, q):
    h = jax.nn.silu(q(x) @ q(p["gate_proj"]["kernel"])) \
        * (q(x) @ q(p["up_proj"]["kernel"]))
    return q(h) @ q(p["down_proj"]["kernel"])


class Reference(reflayers.LayeredReference):
    check_steps = 3

    def __init__(self, config: dict, rehearsal: bool = False):
        c = dict(config)
        if rehearsal:
            c.update(config["rehearsal"])
        self.d, self.L = c["hidden_size"], c["num_hidden_layers"]
        self.H, self.Hkv = c["num_attention_heads"], c["num_key_value_heads"]
        self.dh, self.window = c["head_dim"], c["sliding_window"]
        self.rope = c["rope_parameters"]
        self.F, self.V = c["moe_intermediate_size"], c["vocab_size"]
        self.E, self.held = c["router_num_experts"], c["num_experts"]
        self.held_first = c["held_expert_first_id"]
        self.top_k = c["num_experts_per_tok"]
        self.eps = c["rms_norm_eps"]
        self.types = tuple(c["layer_types"])
        if len(self.types) != self.L or set(self.types) - {
                "sliding_attention", "full_attention"} \
                or set(c["mlp_layer_types"]) != {"sparse"} \
                or not c["norm_topk_prob"] or c["attention_bias"]:
            raise ValueError("this reference runs bias-free sliding and full "
                             "attention layers with sparse FFNs whose chosen "
                             f"scores are renormalised: {self.types}, "
                             f"{c['mlp_layer_types']}")
        self.opt = c["optimizer"]  # the rehearsal brings its own
        self.limits = LIMITS
        super().__init__()

    def is_window(self, i: int) -> bool:
        return self.types[i] == "sliding_attention"

    def mixer_name(self, i: int) -> str:
        """The parameter tree's name for layer i's mixer."""
        return "swa" if self.is_window(i) else "gqa"

    def kind(self, i: int) -> str:
        """A layer's kind: layers of one kind share their programs."""
        return "window" if self.is_window(i) else "full"

    # ------------------------------------------------------------ weights
    def _make(self, key):
        d, dh, f32 = self.d, self.dh, jnp.float32
        keys = iter(jax.random.split(key, 4 + 16 * self.L))
        n = lambda shape, std=0.02: std * jax.random.normal(  # noqa: E731
            next(keys), shape, f32)
        k = lambda *shape: {"kernel": n(shape)}  # noqa: E731
        one = lambda size: {"scale": jnp.ones((size,), f32)}  # noqa: E731
        params = {"tok_embed": {"embedding": n((self.V, d), EMBED_STD)},
                  "final_norm": one(d), "lm_head": k(d, self.V)}
        for i in range(self.L):
            params[f"layer{i}"] = {
                "input_norm": one(d), "post_attn_norm": one(d),
                self.mixer_name(i): {
                    "q_proj": k(d, self.H, dh), "q_norm": one(dh),
                    "k_proj": k(d, self.Hkv, dh), "k_norm": one(dh),
                    "v_proj": k(d, self.Hkv, dh),
                    "o_proj": {"kernel": n((self.H, dh, d), OUT_STD)}},
                "moe": {"router": {"kernel": n((d, self.E))},
                        "experts": {
                            "gate_proj": k(self.held, d, self.F),
                            "up_proj": k(self.held, d, self.F),
                            "down_proj": k(self.held, self.F, d)}}}
        return {"params": params}

    # ------------------------------------------------------ the mathematics
    def _angles(self, window: bool, S: int):
        """(cos, sin) of shape (S, 1, R / 2) for positions 0..S-1, R the
        whole head (the config has no ``partial_rotary_factor``)."""
        r = self.rope["sliding_attention" if window else "full_attention"]
        R, theta = self.dh, float(r["rope_theta"])
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
        elif r["rope_type"] != "default":
            raise ValueError(f"rope_type {r['rope_type']!r}")
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
        return scale * jnp.cos(ang)[:, None], scale * jnp.sin(ang)[:, None]

    def _mix(self, i, p, x, q):
        """x (S, d) -> (S, d): layer i's attention, a head at a time."""
        S, dh = x.shape[0], self.dh
        window = self.window if self.is_window(i) else 0
        proj = lambda name: jnp.einsum(  # noqa: E731
            "sc,chd->shd", q(x), q(p[name]["kernel"]))
        cos, sin = self._angles(self.is_window(i), S)

        def rotate(t):  # (S, heads, dh): dims i and i + dh/2 turn together
            a, b = t[..., :dh // 2], t[..., dh // 2:]
            return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

        qh = rotate(_rms(proj("q_proj"), p["q_norm"]["scale"], self.eps))
        kh = rotate(_rms(proj("k_proj"), p["k_norm"]["scale"], self.eps))
        # query head h reads KV head h // (H / Hkv)
        shared = lambda t: jnp.repeat(  # noqa: E731
            t, self.H // self.Hkv, axis=1)
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
            heads(qh), heads(shared(kh)), heads(shared(proj("v_proj")))))
        return jnp.einsum("shd,hdc->sc", q(jnp.moveaxis(y, 0, 1)),
                          q(p["o_proj"]["kernel"]))

    def _route(self, p, x):
        """(weight of each held expert a token (S, held), 0 where it is not
        chosen; chosen (S, E): the token's choices over ALL outputs). The
        product with W_r is float32 in every precision."""
        s = jax.nn.softmax(x @ p["kernel"], axis=-1)
        ids = jax.lax.top_k(s, self.top_k)[1]
        chosen = jnp.any(ids[:, :, None] == jnp.arange(self.E), 1)
        picked = jnp.where(chosen, s, 0.0)
        w = picked / jnp.sum(picked, -1, keepdims=True)
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
        outputs)."""
        h = _rms(x, p["input_norm"]["scale"], self.eps)
        x = x + self._mix(i, p[self.mixer_name(i)], h, q)
        h = _rms(x, p["post_attn_norm"]["scale"], self.eps)
        out, chosen = self._moe(p["moe"], h, q)
        return x + out, chosen


def compare(ref: dict, observed: dict) -> list:
    """The control's entry: ``reflayers.compare`` under this cell's limits."""
    return reflayers.compare(ref, observed, LIMITS)
