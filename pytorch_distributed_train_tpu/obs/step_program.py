"""What the compiled train step is made of: a map from every instruction of
the OPTIMIZED module to the scope that made it.

A device trace names an operation by its HLO instruction (``%fusion.399``),
and the number after ``fusion.`` moves whenever the program does. The
instruction's ``op_name`` does not: ``jit(train_step)/transpose(jvp(forward))
/HybridLM/layer3/mlp/...`` carries the step's named scopes (steps.py:
``forward loss grad_reduce optimizer``; ``lm_head``, ``exit_head``,
``kda_chunk``, ``loop_pass``), every Flax module's own name and
``checkpoint/rematted_computation`` for what ``model.remat`` runs again.
``scope_map`` reads those out of ``compiled.as_text()``, ``classify`` turns a
path into (phase, component, recompute) over two closed vocabularies, and
``record`` keeps the newest map process-wide, like the span ring, so that a
reader finds it after the trainer is gone (benchmark/scope_readers.py joins
it with a trace's self times; obs/profiler.py prints it beside a capture's
top operations). The record holds neither the executable nor the text.
"""

from __future__ import annotations

import re
import time
from dataclasses import dataclass, field

PHASES = ("forward", "backward", "recompute", "head_loss", "grad_reduce",
          "optimizer", "other")
COMPONENTS = ("attention", "ffn", "experts", "norm", "embed", "other")
MODEL_PHASES = ("forward", "backward", "recompute")

# The module names models/gpt2.py, models/hybrid.py and models/llama.py give
# their parts, as the cells' compiled steps spell them (a trailing
# number dropped: ``ln_1`` -> ``ln_``). The FIRST segment of a path that the
# table knows decides, so a mixer's inner norm (``kda/o_norm``,
# ``mla/q_norm``) is the mixer's and GPT-2's ``attn/c_proj`` is not its
# feed-forward ``c_proj``.
COMPONENT_OF = {
    # mixers whole: projections, rotation, gates, the kernel
    "attn": "attention",                     # gpt2.py, llama.py
    "kda": "attention", "mla": "attention",  # hybrid.py, a kind a layer
    "gqa": "attention", "swa": "attention", "conv": "attention",
    # dense feed-forward blocks (GPT-2's has no module of its own)
    "c_fc": "ffn", "c_proj": "ffn", "mlp": "ffn",
    # expert layers whole: router, dispatch, grouped products, combine,
    # the shared expert
    "moe": "experts",
    # norms outside a mixer (``ln_1 ln_2 ln_f``; llama.py's sandwich)
    "ln_": "norm", "ln_f": "norm", "input_norm": "norm",
    "post_attn_norm": "norm", "attn_out_norm": "norm",
    "mlp_out_norm": "norm", "final_norm": "norm",
    # the token table's lookup and its scatter-add
    "wte": "embed", "wpe": "embed", "tok_embed": "embed",
}
HEAD_SCOPES = frozenset({"lm_head", "exit_head", "jvp(loss)",
                         "transpose(jvp(loss))"})
RECOMPUTE = "rematted_computation"
# The order in which a step runs its phases: an operation cannot run before
# what it reads (``_borrow``; ``other`` has no place in it).
_RUNS_AFTER = {"forward": 0, "head_loss": 1, "recompute": 2, "backward": 3,
               "grad_reduce": 4, "optimizer": 5}

_NOT_TIMED = (" parameter(", " constant(", " get-tuple-element(", " tuple(",
              " bitcast(")
_REDUCES = (" all-reduce(", " all-reduce-start(", " all-reduce-done(",
            " reduce-scatter(")
_NAME = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERANDS = re.compile(r" [a-z][a-z0-9-]*\((.*?)\)(?:[,\s]|$)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_SEGMENTS = re.compile(r"[/;]")
_TRAILING_DIGITS = re.compile(r"\d+$")


def instruction_name(traced: str) -> str:
    """``fusion.399`` of a trace's ``%fusion.399 fusion`` (or of the whole
    instruction text, or of the bare name): the map's key."""
    return traced.split(" ", 1)[0].lstrip("%")


def classify(op_name: str) -> tuple[str, str, bool]:
    """(phase, component, recompute) of one ``op_name`` path, by its
    segments alone. ``recompute`` is true under ``rematted_computation``
    (the phase is then ``recompute``, not ``backward``). The component is
    ``other`` outside the three model phases."""
    parts = _SEGMENTS.split(op_name)
    recompute = RECOMPUTE in parts
    if not HEAD_SCOPES.isdisjoint(parts):
        phase = "head_loss"
    elif "transpose(jvp(forward))" in parts:
        phase = "recompute" if recompute else "backward"
    elif "jvp(forward)" in parts:
        phase = "recompute" if recompute else "forward"
    elif "grad_reduce" in parts:
        phase = "grad_reduce"
    elif "optimizer" in parts:
        phase = "optimizer"
    else:
        phase = "other"
    component = "other"
    if phase in MODEL_PHASES:
        for part in parts:
            known = COMPONENT_OF.get(_TRAILING_DIGITS.sub("", part))
            if known is not None:
                component = known
                break
    return phase, component, recompute


@dataclass
class ProgramMap:
    """``scopes``: {instruction name, no ``%``: op_name}; ``instructions`` /
    ``fusions``: the timed instructions the text held (fusions among them),
    with or without an ``op_name``. Three sets of names qualify the map:
    ``mixed``, the fusions whose fused computation holds instructions of
    more than one phase; ``borrowed``, the instructions whose own
    ``op_name`` named no scope and that carry an operand's; ``reduces``,
    the reducing collectives and the fusions that hold one."""

    module: str = ""
    scopes: dict = field(default_factory=dict)
    mixed: frozenset = frozenset()
    borrowed: frozenset = frozenset()
    reduces: frozenset = frozenset()
    instructions: int = 0
    fusions: int = 0
    text_bytes: int = 0
    build_s: float = 0.0
    step: int | None = None

    def place(self, instruction: str):
        """(phase, component, recompute) of an instruction as a trace names
        it (``instruction_name``), or None where the map does not hold it.
        A reducing collective of the backward pass is ``grad_reduce``:
        under ``jit`` the partitioner makes the gradient's all-reduces and
        names each by the product whose result it reduces, so no scope of
        the step's is traced round them."""
        name = instruction_name(instruction)
        op_name = self.scopes.get(name)
        if op_name is None:
            return None
        phase, component, recompute = classify(op_name)
        if phase == "backward" and name in self.reduces:
            return "grad_reduce", "other", False
        return phase, component, recompute

    def describe(self, instruction: str) -> str:
        """``backward/ffn`` (``recompute/...`` under remat; ``+mixed`` for a
        fusion over more than one phase), or ``""`` where the map does not
        hold the instruction."""
        placed = self.place(instruction)
        if placed is None:
            return ""
        phase, component, _ = placed
        text = f"{phase}/{component}" if phase in MODEL_PHASES else phase
        mixed = instruction_name(instruction) in self.mixed
        return text + ("+mixed" if mixed else "")


def scope_map(hlo_text: str) -> ProgramMap:
    """Every instruction of every computation of an optimized module's text
    that carries ``metadata={op_name="..."}``, ``while`` bodies and called
    computations included. A fusion is named by its own metadata; it is
    ``mixed`` where the instructions of its fused computation lie in more
    than one phase (``other`` says nothing and does not count). A timed
    instruction that names no scope itself borrows a neighbour's
    (``_borrow``): the compiler's own data movement (``copy-done``,
    ``slice-done``, layout copies and fusions: no metadata at all) takes
    the scope of what reads it, and an operation the compiler rewrote under
    a bare name (``ragged_dot`` becomes the custom call ``ragged-dot-none``)
    the scope of what it reads."""
    out = ProgramMap(text_bytes=len(hlo_text))
    first = hlo_text[:hlo_text.find("\n")].split()
    if len(first) > 1 and first[0] == "HloModule":
        out.module = first[1].rstrip(",")
    scopes = out.scopes
    phases_in: dict[str, set] = {}   # computation -> phases of its own lines
    reduces_in: set[str] = set()     # computations that hold a collective
    fused: dict[str, str] = {}       # fusion instruction -> its computation
    phase_of: dict[str, str] = {}    # op_name -> phase, as seen so far
    reads: dict[str, list] = {}      # unscoped instruction -> its operands
    users: dict[str, list] = {}      # unscoped instruction -> what reads it
    rewritten: list[str] = []        # timed, under a bare op_name
    moved: list[str] = []            # timed, with no op_name at all
    reduces: set[str] = set()
    current = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            # `%fused_computation.7 (param_0: ...) -> ... {`, `ENTRY %main ...`
            current = None
            if line.endswith("{"):
                words = line.split(None, 2)
                current = (words[1] if words[0] == "ENTRY"
                           else words[0]).lstrip("%")
            continue
        named = _NAME.match(line) if current is not None else None
        if named is None:
            continue
        name = named.group(1)
        timed = not any(word in line for word in _NOT_TIMED)
        found = _OP_NAME.search(line) if timed else None
        op_name = found.group(1) if found else ""
        operands = _OPERANDS.search(line, named.end() - 1)
        operands = _OPERAND.findall(operands.group(1)) if operands else ()
        for operand in operands:
            if operand in reads:
                users.setdefault(operand, []).append(name)
        if "/" not in op_name:
            reads[name] = operands
        if not timed:
            continue
        out.instructions += 1
        if " fusion(" in line:
            out.fusions += 1
            calls = _CALLS.search(line)
            if calls:
                fused[name] = calls.group(1)
        if any(word in line for word in _REDUCES):
            reduces.add(name)
            reduces_in.add(current)
        if op_name:
            scopes[name] = op_name
        if "/" not in op_name:
            (rewritten if op_name else moved).append(name)
            continue
        phase = phase_of.get(op_name)
        if phase is None:
            phase = phase_of[op_name] = classify(op_name)[0]
        if phase != "other":
            phases_in.setdefault(current, set()).add(phase)
    # what the compiler rewrote runs where its operands were made; what it
    # added to move data, where the data is wanted (a rewritten operation
    # may be among the readers: those first)
    from_operands, from_users = (reads, True), (users, False)
    borrowed = {}
    for names, ways in ((rewritten, (from_operands, from_users)),
                        (moved, (from_users, from_operands))):
        for name in names:
            for towards, latest in ways:
                op_name = _borrow(name, towards, latest, scopes, phase_of)
                if op_name is not None:
                    borrowed[name] = op_name
                    break
        scopes.update(borrowed)
    out.borrowed = frozenset(borrowed)
    out.mixed = frozenset(name for name, comp in fused.items()
                          if len(phases_in.get(comp, ())) > 1)
    out.reduces = frozenset(reduces | {name for name, comp in fused.items()
                                       if comp in reduces_in})
    return out


def _borrow(name: str, towards: dict, latest: bool, scopes: dict,
            phase_of: dict, depth: int = 8):
    """The ``op_name`` of a neighbour of ``name``: followed along
    ``towards`` (its operands, or its users) through instructions that name
    no scope themselves (tuples, bitcasts, copies; at most ``depth`` deep),
    the scoped one whose phase runs LATEST in a step among operands (an
    operation cannot run before what it reads, and a backward product reads
    the recomputed activation beside the incoming gradient) or EARLIEST
    among users (a transfer waits for its first reader). ``other`` counts
    only where nothing else is found; None where no neighbour names a
    scope."""
    no_place = -1 if latest else len(_RUNS_AFTER)
    best, best_rank = None, None
    seen = {name}
    frontier = [name]
    for _ in range(depth):
        following = []
        for at in frontier:
            for other in towards.get(at, ()):
                if other in seen:
                    continue
                seen.add(other)
                op_name = scopes.get(other, "")
                if "/" not in op_name:
                    following.append(other)
                    continue
                rank = _RUNS_AFTER.get(phase_of[op_name], no_place)
                if (best_rank is None or
                        (rank > best_rank if latest else rank < best_rank)):
                    best, best_rank = op_name, rank
        frontier = following
        if not frontier:
            break
    return best


_LATEST: ProgramMap | None = None


def record(compiled, step: int | None = None) -> ProgramMap:
    """Build the map of a compiled step (``jax.stages.Compiled``) and keep
    it as the process's newest: one text dump and one pass over it."""
    global _LATEST
    t0 = time.perf_counter()
    built = scope_map(compiled.as_text())
    built.step = step
    built.build_s = time.perf_counter() - t0
    _LATEST = built
    return built


def latest() -> ProgramMap | None:
    """The newest recorded map, or None where no step left one."""
    return _LATEST


def clear() -> None:
    global _LATEST
    _LATEST = None
