"""Mixture-of-Experts layer with expert parallelism (SURVEY §2.3 EP row).

Expert parallelism is absent from the reference and from torch core (the
ecosystem supplies it via DeepSpeed-MoE/Megatron); its torch primitive is
`all_to_all` (torch:distributed/distributed_c10d.py:5145). The TPU-native
design is the GShard/Switch recipe, shaped for the MXU and GSPMD:

- **Static capacity dispatch.** Top-k routing with a fixed per-expert
  capacity C = ceil(k·N/E · capacity_factor). Dispatch/combine are dense
  one-hot tensors contracted with einsum — no gather/scatter with dynamic
  shapes, so XLA tiles everything onto the MXU and the program never
  recompiles. Overflow tokens are dropped (pass through the residual),
  the standard Switch behavior.
- **Expert sharding.** Expert FFN params are stacked on a leading E dim
  sharded ``P('expert')``; the (E, C, D) expert batch inherits that
  sharding, and GSPMD inserts the token all-to-alls between the
  batch-sharded and expert-sharded layouts — the compiler-placed
  equivalent of DeepSpeed's hand-written `all_to_all` dispatch.
- **Aux losses** (load-balance + router z-loss) leave the layer through
  flax's ``sow`` into the 'losses' collection; the train step adds every
  sown scalar to the objective (steps.apply_model).

Two layers live here (ROADMAP design debt "two dispatches in ops/moe.py"):
:class:`MoeMLP` above, which holds every expert and drops overflow, and
:class:`HeldExpertsMLP`, one chip's share of an expert-parallel layer: it
routes over ALL the layer's experts (sigmoid scores with a selection-only
bias, or softmax scores with none; group-limited top-k, weights normalised
over every chosen expert), is told which experts it holds, and computes
their part of the result with one grouped product over the (token, choice)
pairs that fall on them, by expert, beside the shared expert(s) at a width
of their own (or none, where the family has none). Where the spec gives
the selection bias a rate, the layer also counts the tokens on EVERY router
output and sows them, and the train step moves the bias by them after the
optimizer (:func:`balance_routers`).
No token is dropped silently: pairs past its static row bound are counted,
and the model hands the count to the train step as ``update_invalid``, so
such a step keeps its old state and reports ``update_skipped``.
"""

from __future__ import annotations

import dataclasses
import math
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu.ops import attention, grouped_matmul


@dataclasses.dataclass(frozen=True)
class MoeSpec:
    """MoE knobs threaded from ModelConfig into the block stack."""

    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    zloss_weight: float = 1e-3
    every: int = 1  # MoE every n-th block (others keep the dense MLP)
    # "topk" (GShard/Switch: tokens choose experts, overflow drops) or
    # "expert_choice" (Zhou et al. 2022: experts choose tokens — perfect
    # load balance by construction, no balance loss needed).
    router: str = "topk"

    def active_for_layer(self, i: int) -> bool:
        return self.num_experts > 1 and (i + 1) % self.every == 0


def expert_capacity(n_tokens: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Static per-expert slot count; ≥1 so tiny probe batches still trace."""
    return max(1, math.ceil(n_tokens * top_k / num_experts * capacity_factor))


def topk_dispatch(gates: jnp.ndarray, top_k: int, capacity: int):
    """Top-k token→expert assignment with capacity truncation.

    Args:
      gates: (N, E) fp32 router probabilities (softmax output).
    Returns:
      dispatch: (N, E, C) 0/1 — token n occupies slot c of expert e.
      combine:  (N, E, C) fp32 — dispatch · renormalized gate weight.
    Slot assignment is choice-major (all 1st choices queue before any 2nd
    choice) then token-major — earlier tokens win ties, the GShard priority
    rule.
    """
    N, E = gates.shape
    vals, idx = jax.lax.top_k(gates, top_k)  # (N, k)
    # Renormalize the selected gates so combine weights sum to 1 per token.
    vals = vals / jnp.maximum(jnp.sum(vals, axis=-1, keepdims=True), 1e-9)

    counts = jnp.zeros((E,), jnp.int32)  # places taken per expert so far
    dispatch = jnp.zeros((N, E, capacity), jnp.float32)
    combine = jnp.zeros((N, E, capacity), jnp.float32)
    for s in range(top_k):
        oh = jax.nn.one_hot(idx[:, s], E, dtype=jnp.int32)  # (N, E)
        pos = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]  # queue position
        keep = (pos < capacity) & (oh > 0)
        counts = counts + jnp.sum(keep.astype(jnp.int32), axis=0)
        slot = jax.nn.one_hot(jnp.where(keep, pos, -1), capacity,
                              dtype=jnp.float32)  # (N, E, C); -1 → all-zero
        dispatch = dispatch + slot
        combine = combine + slot * vals[:, s][:, None, None]
    return dispatch, combine


def expert_choice_dispatch(gates: jnp.ndarray, capacity: int):
    """Expert-choice routing (Zhou et al. 2022): each EXPERT takes its
    top-``capacity`` tokens by gate score. Every expert is exactly full —
    perfect load balance with no auxiliary loss; a token may be served by
    0..E experts (unchosen tokens pass through the residual, like
    dropped-overflow tokens under top-k).

    CAUSALITY CAVEAT: selection ranks over ALL flattened batch tokens, so
    in a decoder-only LM whether position t gets served depends on later
    positions (and on other sequences in the batch). Training loss is
    therefore mildly non-causal and batch-dependent — the known
    Zhou et al. limitation for autoregressive LMs. Best suited to
    encoder/MLM-style models; for causal LMs treat perplexity
    comparisons against top-k with care.

    Returns (dispatch, combine) of shape (N, E, min(capacity, N)) —
    same contract as topk_dispatch except the capacity axis clamps to N
    (an expert cannot take more tokens than exist); combine carries the
    raw gate score of each selection (the paper's weighted sum — no
    per-token renormalization)."""
    N, E = gates.shape
    cap = min(capacity, N)
    vals, idx = jax.lax.top_k(gates.T, cap)  # (E, C): each expert's picks
    sel = jax.nn.one_hot(idx, N, dtype=jnp.float32)  # (E, C, N)
    dispatch = sel.transpose(2, 0, 1)  # (N, E, C)
    combine = dispatch * vals[None, :, :]
    return dispatch, combine


def load_balance_loss(gates: jnp.ndarray, dispatch: jnp.ndarray) -> jnp.ndarray:
    """Switch-Transformer load-balance loss: E · Σ_e f_e · p_e, minimized at
    uniform routing. f_e = fraction of dispatched places on expert e (not
    differentiable), p_e = mean router prob (differentiable)."""
    E = gates.shape[1]
    f = jnp.mean(jnp.sum(dispatch, axis=2), axis=0)  # (E,) tokens kept per e / N
    p = jnp.mean(gates, axis=0)  # (E,)
    return E * jnp.sum(f * p)


def router_z_loss(logits: jnp.ndarray) -> jnp.ndarray:
    """ST-MoE z-loss: penalizes large router logits for numeric stability."""
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)


class MoeMLP(nn.Module):
    """Drop-in replacement for the dense transformer MLP.

    Param tree: router/kernel (D, E); experts/<proj>/kernel with a leading
    (E,) dim from nn.vmap — sharded P('expert', ...) by the partition rules.
    """

    spec: MoeSpec
    mlp_module: type  # the dense MLP class to replicate per expert
    mlp_dim: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        B, S, D = x.shape
        N = B * S
        spec = self.spec
        E = spec.num_experts
        C = expert_capacity(N, E, spec.top_k, spec.capacity_factor)
        xf = x.reshape(N, D)

        # Router in fp32 — small matmul, numerics matter (ST-MoE practice).
        logits = nn.Dense(
            E, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.02), name="router",
        )(xf.astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)
        if spec.router == "expert_choice":
            dispatch, combine = expert_choice_dispatch(gates, C)
            # balance is structural; only the z-loss remains useful
            aux = spec.zloss_weight * router_z_loss(logits)
        elif spec.router == "topk":
            dispatch, combine = topk_dispatch(gates, spec.top_k, C)
            aux = (spec.aux_weight * load_balance_loss(gates, dispatch)
                   + spec.zloss_weight * router_z_loss(logits))
        else:
            raise ValueError(
                f"unknown moe router {spec.router!r}; "
                "have topk | expert_choice")
        self.sow("losses", "moe_aux", aux)

        # (N, E, C) × (N, D) → (E, C, D): the token all-to-all happens here
        # (GSPMD re-lays batch-sharded tokens out over the 'expert' axis).
        expert_in = jnp.einsum(
            "nec,nd->ecd", dispatch.astype(self.dtype), xf.astype(self.dtype)
        )
        experts = nn.vmap(
            self.mlp_module,
            in_axes=0, out_axes=0,
            variable_axes={"params": 0},
            split_rngs={"params": True},
        )(self.mlp_dim, self.dtype, self.param_dtype, name="experts")
        expert_out = experts(expert_in)  # (E, C, D)

        # Combine back to token layout (the return all-to-all).
        yf = jnp.einsum(
            "nec,ecd->nd", combine.astype(self.dtype), expert_out
        )
        return yf.reshape(B, S, D)


# ------------------------------------------- one chip's share of the experts

@dataclasses.dataclass(frozen=True)
class HeldExpertsSpec:
    """A routed layer as ONE chip of an expert-parallel deployment sees it:
    the router's width and rule are the whole layer's, ``held`` experts
    from id ``held_first`` on live here."""

    num_experts: int          # the router's width: every expert of the layer
    top_k: int
    n_groups: int = 1         # experts are split into this many groups ...
    topk_groups: int = 1      # ... of which a token may use this many
    routed_scale: float = 1.0
    # the router's score rule: "sigmoid" (an expert's own score, and a bias
    # that only selection sees) or "softmax" (over every expert, no bias)
    score: str = "sigmoid"
    held_first: int = 0
    held: int = 0             # 0 -> all of them
    # rows of the grouped product = this x the pairs expected on the held
    # experts under uniform routing (tokens x top_k x held / num_experts)
    capacity_factor: float = 4.0
    # the shared expert's width: ONE SwiGLU every chip computes alike (a
    # family's n shared experts of width w are one of n x w); 0 -> the
    # routed experts' width; -1 -> the family has NO shared expert (no
    # ``shared`` subtree, nothing added to the routed sum)
    shared_mlp_dim: int = 0
    # the balancing update's rate (:func:`balance_routers`); 0 leaves the
    # selection bias where it was drawn and the layer sows no counts
    bias_rate: float = 0.0

    @property
    def n_held(self) -> int:
        return self.held or self.num_experts

    def row_bound(self, n_tokens: int) -> int:
        """Static rows of the grouped product: never more than the worst
        case (every token on every held expert it can choose), whole
        sublane tiles otherwise."""
        worst = n_tokens * min(self.top_k, self.n_held)
        want = math.ceil(self.capacity_factor * n_tokens * self.top_k
                         * self.n_held / self.num_experts)
        return min(worst, -(-want // 8) * 8)

    def mean_rows(self, n_tokens: int) -> int:
        """The pairs a held expert has on average (static): what the
        grouped product's row tile follows."""
        return max(1, n_tokens * self.top_k // self.num_experts)


def group_limited_topk(scores, bias, spec: HeldExpertsSpec):
    """DeepSeek-V3's rule (arXiv:2412.19437, 2.1.2) on float32 ``scores``
    (N, E) in (0, 1): the selection score is scores + bias (the scores
    alone where ``bias`` is None); a group's score is the sum of its two
    largest; the ``topk_groups`` best groups stay; of their experts the
    ``top_k`` largest are chosen (one group: plain top-k). The weights use
    the scores WITHOUT the bias, normalised over all chosen, times
    ``routed_scale``. Returns (ids (N, k) int32, weights (N, k) float32);
    the ids carry no gradient, so the bias gets none."""
    N, E = scores.shape
    select = scores if bias is None else scores + bias
    if spec.n_groups > 1:
        per = E // spec.n_groups
        grouped = select.reshape(N, spec.n_groups, per)
        group_score = jnp.sum(jax.lax.top_k(grouped, min(2, per))[0], -1)
        _, keep = jax.lax.top_k(group_score, spec.topk_groups)
        kept = jnp.any(keep[:, :, None] == jnp.arange(spec.n_groups), 1)
        select = jnp.where(kept[:, :, None], grouped,
                           -jnp.inf).reshape(N, E)
    _, ids = jax.lax.top_k(select, spec.top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=1)
    weights = spec.routed_scale * chosen / jnp.sum(chosen, -1, keepdims=True)
    return ids.astype(jnp.int32), weights


# Flags are counted in blocks of this many: the TPU's lane width, so a block
# is one row of a vector register's tile (a constant of the chip, no knob).
_BLOCK = 128


def _nth_set(flags, ranks):
    """Where the ``ranks[r]``-th (from 1) set entry of ``flags`` (L,) bool
    sits, (R,) int32; L where the rank is past the count. Two levels and no
    dependent chain: the rank's block of 128 flags from the blocks' running
    counts (one compare-and-reduce over (R, blocks)), that block's flags as
    a row (a one-hot product, exact on 0/1), the lane from the running
    count along the row (a product with a 128 x 128 triangle). A binary
    search of the running counts is ceil(log2(L + 1)) passes of R scalar
    gathers, each waiting for the one before: 19 x 0.35 ms a call in the
    all-latent cell on a v5e (PERF.md section 6, PR 42)."""
    L = flags.shape[0]
    nb = -(-L // _BLOCK)
    tbl = jnp.pad(flags, (0, nb * _BLOCK - L)).reshape(nb, _BLOCK)
    in_block = jnp.sum(tbl, 1, dtype=jnp.int32)
    upto = jnp.cumsum(in_block)
    ranks = ranks.astype(jnp.int32)[:, None]
    passed = upto < ranks                             # (R, nb) whole blocks
    block = jnp.sum(passed, 1, dtype=jnp.int32)       # nb: past the count
    before = jnp.sum(jnp.where(passed, in_block, 0), 1)
    row = jnp.dot(jax.nn.one_hot(block, nb, dtype=jnp.bfloat16),
                  tbl.astype(jnp.bfloat16),
                  preferred_element_type=jnp.float32)  # (R, 128) of 0 / 1
    lanes = jnp.arange(_BLOCK)
    incl = jnp.dot(row.astype(jnp.bfloat16),
                   (lanes[:, None] <= lanes).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    lane = jnp.sum(incl < (ranks - before[:, None]), 1, dtype=jnp.int32)
    return jnp.minimum(block * _BLOCK + lane, L)


def held_rows(ids, weights, spec: HeldExpertsSpec, rows: int):
    """The (token, choice) pairs that fall on held experts, by expert and
    inside an expert by token: (token (rows,) int32, weight (rows,) float32
    of that pair, group_sizes (held,) int32 rows of each held expert inside
    the bound, counts (held,) int32 pairs on each before the bound, over:
    pairs past the bound). Rows past sum(group_sizes) belong to no expert
    (token 0, weight 0): the one layout the bank's grouped products read.

    A token chooses an expert at most once, so the pairs are the set
    entries of an (expert, token) table, and the r-th row is the r-th set
    entry (:func:`_nth_set`): block counts and two small products, no sort
    (a stable argsort of the 131072 pairs of the hybrid cell took the TPU
    compiler 14 s a layer: sandbox compile, PR 26) and no search. The
    program's map names all of it ``held_rows``."""
    with jax.named_scope("held_rows"):
        N = ids.shape[0]
        mine = spec.held_first + jnp.arange(spec.n_held)
        hit = ids[:, :, None] == mine                 # (N, k, held)
        on = jnp.any(hit, 1)                          # (N, held)
        counts = jnp.sum(on, 0)
        table = on.T.reshape(-1)                      # (expert, token)
        ends = jnp.minimum(jnp.cumsum(counts), rows)
        sizes = jnp.diff(ends, prepend=0)
        over = jnp.sum(counts) - ends[-1]
        at = _nth_set(table, jnp.arange(1, rows + 1, dtype=jnp.int32))
        real = at < table.shape[0]                    # the r-th entry exists
        token = jnp.where(real, at % N, 0).astype(jnp.int32)
        expert = jnp.where(real, at // N, 0)
        held_weight = jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), 1)
        weight = jnp.where(real, held_weight[token, expert], 0.0)
        return token, weight, sizes.astype(jnp.int32), \
            counts.astype(jnp.int32), over


_moe_logged: set[tuple] = set()


def _log_plan(spec: HeldExpertsSpec, n_tokens: int, rows: int,
              shared: int, d_model: int, mlp_dim: int) -> None:
    """Once a shape, at trace time, on stderr: what this chip holds, and
    where the Pallas kernels take its grouped products
    ``bank=grouped-kernel`` with the row kernel's tiles (rows x contraction
    x columns, for gate / up and for down) and the most grid steps a pass
    over the rows can take."""
    key = (spec, n_tokens, shared, d_model, mlp_dim)
    if key in _moe_logged:
        return
    _moe_logged.add(key)
    bank = ""
    if grouped_matmul.unsupported(d_model, mlp_dim) is None:
        mean_rows = spec.mean_rows(n_tokens)
        up = grouped_matmul.tile_sizes(d_model, mlp_dim, mean_rows)
        down = grouped_matmul.tile_sizes(mlp_dim, d_model, mean_rows)
        bank = (f" bank=grouped-kernel tiles={up.m}x{d_model}x{up.n},"
                f"{down.m}x{mlp_dim}x{down.n} "
                f"steps<={-(-rows // up.m) + spec.n_held - 1}")
    last = spec.held_first + spec.n_held - 1
    print(f"[moe] experts={spec.num_experts} held={spec.n_held} "
          f"ids={spec.held_first}-{last} top_k={spec.top_k} "
          f"groups={spec.n_groups}/{spec.topk_groups} score={spec.score} "
          f"tokens={n_tokens} row_bound={rows}{bank}"
          + f" shared={'none' if shared is None else shared}"
          + (f" bias_rate={spec.bias_rate:g}" if spec.bias_rate else ""),
          file=sys.stderr, flush=True)


class _Kernel(nn.Module):
    """One ``kernel`` leaf under its own name, like a Dense's."""

    shape: tuple
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.normal(0.02), self.shape,
                          self.param_dtype)


def _interpret() -> bool:
    """Mosaic on a TPU; elsewhere the interpreter, which only a test that
    steers ``grouped_matmul.unsupported``'s gate ever reaches."""
    return not attention._on_tpu()


def _grouped_bank(rows, sizes, kernels, dtype, mean_rows):
    """Rows sorted by expert through three grouped products, under scope
    ``grouped_product``: the Pallas kernels of ops/grouped_matmul.py, whose
    row tile follows ``mean_rows`` (the pairs an expert has on average,
    static). Rows past sum(sizes) belong to no group. The kernels read none
    of them, forward or backward, and leave what the buffer held in the
    results' rows there (a v5e's stale rows once gave gradients 36 000
    times too large, PERF.md PR 26): the rows are zeroed ONCE on the way in
    (its transpose keeps a stale ``d rows`` out of the scatter-add behind
    ``xf[token]``) and the result once on the way out. Where the kernels do
    not take the call (``grouped_matmul.unsupported``: no TPU, matrices
    that are not whole tiles) the products are ``jax.lax.ragged_dot``, whose
    TPU kernel reads and leaves stale rows: there the rows are zeroed
    between the products too. ``kernels``: three thunks, (held, D, F) gate
    and up, (held, F, D) down."""
    in_group = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]
    held = lambda a: jnp.where(in_group, a, 0)  # noqa: E731
    gate_w, up_w, down_w = (k() for k in kernels)  # (casts: not the scope's)
    if grouped_matmul.unsupported(*gate_w.shape[1:]) is None:
        between = lambda a: a  # noqa: E731
        gdot = lambda a, w: grouped_matmul.grouped_matmul(  # noqa: E731
            a, w, sizes, mean_rows=mean_rows, interpret=_interpret())
    else:
        between = held
        gdot = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
            a, w, sizes, preferred_element_type=jnp.float32)
    rows = held(rows)
    with jax.named_scope("grouped_product"):
        gate, up = gdot(rows, gate_w), gdot(rows, up_w)
    hidden = between((nn.silu(between(gate)) * between(up)).astype(dtype))
    with jax.named_scope("grouped_product"):
        out = gdot(hidden, down_w)
    return held(out)


class _ExpertBank(nn.Module):
    """The held experts' SwiGLU weights, stacked, applied to rows sorted by
    expert through the grouped product."""

    held: int
    d_model: int
    mlp_dim: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    def setup(self):
        D, F = self.d_model, self.mlp_dim
        self.gate_proj = _Kernel((self.held, D, F), self.param_dtype)
        self.up_proj = _Kernel((self.held, D, F), self.param_dtype)
        self.down_proj = _Kernel((self.held, F, D), self.param_dtype)

    def __call__(self, rows, sizes, mean_rows):
        kernels = tuple(lambda k=k: jnp.asarray(k(), self.dtype) for k in (
            self.gate_proj, self.up_proj, self.down_proj))
        return _grouped_bank(rows, sizes, kernels, self.dtype, mean_rows)


class _Router(nn.Module):
    """Scores over every expert of the layer, float32 throughout, by the
    spec's rule. ``sigmoid``: each expert's own, and the bias that only
    selection sees (drawn at std 0.01; it gets no gradient, so the
    optimizer leaves it as it is: what moves it is the balancing update,
    :func:`balance_routers`, where the spec gives it a rate, and nothing
    where the rate is 0). ``softmax``: over all the experts, and no bias
    (no such leaf)."""

    num_experts: int
    score: str = "sigmoid"

    @nn.compact
    def __call__(self, x):
        if self.score not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown router score {self.score!r}; "
                             "have sigmoid | softmax")
        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (x.shape[-1], self.num_experts), jnp.float32)
        bias = self.param("bias", nn.initializers.normal(0.01),
                          (self.num_experts,), jnp.float32) \
            if self.score == "sigmoid" else None
        logits = jnp.matmul(x.astype(jnp.float32), kernel,
                            precision=jax.lax.Precision.HIGHEST)
        if self.score == "softmax":
            return jax.nn.softmax(logits, axis=-1), None
        return jax.nn.sigmoid(logits), bias


class HeldExpertsMLP(nn.Module):
    """(B, S, D) -> ((B, S, D), stats): the held experts' part of the
    routed sum plus the shared expert, ONE SwiGLU of ``spec.shared_mlp_dim``
    (0: the routed experts' ``mlp_dim``), which every chip of the layer
    computes alike; at -1 the routed part alone (the family has no shared
    expert: no ``shared`` subtree). ``stats`` is
    float32 (4,): pairs on the fullest held expert, on the mean one, and
    past the row bound, and the grouped product's row-tile visits over the
    whole tiles its real rows would fill (1.0: the experts' boundaries cost
    no visit; ``grouped_matmul.tile_visits_ratio``). With
    ``spec.bias_rate`` > 0 the tokens that chose each of ALL the router's
    outputs, float32 (E,), are sown as ``counts`` into the ``router_load``
    collection, for the step's balancing update.

    Param tree: router/{kernel (D, E), bias (E,): the sigmoid rule's};
    experts/<proj>/kernel
    with a leading (held,) dim; shared/<proj>/kernel where there is one.
    """

    spec: HeldExpertsSpec
    mlp_module: type  # the dense SwiGLU class, for the shared expert
    mlp_dim: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        B, S, D = x.shape
        N, spec, F = B * S, self.spec, self.mlp_dim
        rows = spec.row_bound(N)
        shared_dim = None if spec.shared_mlp_dim < 0 \
            else spec.shared_mlp_dim or F
        mean_rows = spec.mean_rows(N)
        _log_plan(spec, N, rows, shared_dim, D, F)
        xf = x.reshape(N, D)
        scores, bias = _Router(spec.num_experts, spec.score,
                               name="router")(xf)
        ids, weights = group_limited_topk(scores, bias, spec)
        token, weight, sizes, counts, over = held_rows(ids, weights, spec,
                                                       rows)
        with jax.named_scope("held_gather"):
            in_rows = xf[token].astype(self.dtype)
        out_rows = _ExpertBank(spec.n_held, D, F, self.dtype,
                               self.param_dtype, name="experts")(
            in_rows, sizes, mean_rows)
        # (rows of no expert: the bank zeroed them)
        with jax.named_scope("held_combine"):
            routed = jnp.zeros((N, D), jnp.float32).at[token].add(
                out_rows * weight[:, None])
        if spec.bias_rate:
            if bias is None:
                raise ValueError("moe bias_rate: a softmax router has no "
                                 "selection bias to balance")
            load = jnp.sum(ids[:, :, None] == jnp.arange(spec.num_experts),
                           (0, 1), dtype=jnp.float32)
            self.sow("router_load", "counts", load,
                     reduce_fn=lambda _, new: new, init_fn=lambda: 0.0)
        shared = None if shared_dim is None else self.mlp_module(
            shared_dim, self.dtype, self.param_dtype, name="shared")(x)
        y = routed.reshape(B, S, D).astype(self.dtype)
        if shared is not None:
            y = y + shared
        visits = grouped_matmul.tile_visits_ratio(
            sizes, rows, grouped_matmul.tile_sizes(D, F, mean_rows).m)
        stats = jnp.stack([jnp.max(counts), jnp.mean(counts), over, visits]
                          ).astype(jnp.float32)
        return y, stats


# --------------------------------------------- the selection bias's update

def _is_router_bias(path) -> bool:
    return tuple(getattr(k, "key", None) for k in path[-2:]) \
        == ("router", "bias")


def balance_routers(params, moved, load, rate: float):
    """DeepSeek-V3's auxiliary-loss-free balancing (arXiv:2412.19437, 2.1.2
    and 4.2): after the optimizer, every router whose layer sowed its
    ``counts`` c (tokens of the step's whole batch that chose each output)
    has its selection bias moved by b_e <- b_e + rate x sign(mean(c) - c_e).
    ``params`` is the tree before the optimizer, ``moved`` the tree after
    it, ``load`` the step's ``router_load`` collection (a layer's counts sit
    where its ``router`` does). The bias is taken from ``params``: no
    gradient reaches it, and whatever else an optimizer would do to it
    (decay) is not part of its rule. Every other leaf is ``moved``'s."""

    def move(path, old, new):
        if not _is_router_bias(path):
            return new
        layer = load
        for k in path[:-2]:  # the layer's counts sit beside its router
            layer = layer[k.key]
        counts = layer["counts"]
        return old + rate * jnp.sign(jnp.mean(counts) - counts)

    return jax.tree_util.tree_map_with_path(move, params, moved)


def router_load_metrics(load, params) -> dict:
    """The step's metrics of the balancing update: tokens on the fullest
    and on the mean router output (each a mean over the layers that sowed)
    and the largest |bias| of those layers in ``params``."""
    counts = jax.tree_util.tree_leaves(load)
    biases = [b for path, b in jax.tree_util.tree_flatten_with_path(params)[0]
              if _is_router_bias(path)]
    return {
        "moe_load_fullest": jnp.mean(jnp.stack([jnp.max(c) for c in counts])),
        "moe_load_mean": jnp.mean(jnp.stack([jnp.mean(c) for c in counts])),
        "moe_bias_abs_max": jnp.max(jnp.stack(
            [jnp.max(jnp.abs(b)) for b in biases])),
    }
