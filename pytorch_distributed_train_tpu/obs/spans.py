"""Host-side trace spans: a ring buffer of timed regions + Chrome export.

The device side of "where does wall-clock go" is already covered by the
xplane profiler window (utils/xplane.py); what was missing is the HOST
side — compile vs step vs input stall vs checkpoint vs eval vs request
handling. ``span("checkpoint.save")`` costs three clock reads, one ring
slot and one histogram observation (a few microseconds, PERF.md), cheap
enough for per-step use; the ring holds the last ``capacity`` completed
spans so the watchdog can dump "what was the host doing" on abort
(utils/watchdog.py attaches the recorder next to the FlightRecorder
event ring).

Clocks: a span's start is ``time.time_ns()`` (``t0_ns``; ``t0`` is the
same instant in float seconds), its length ``perf_counter_ns`` (``dur_ns``
/ ``dur_s``). An xplane trace's ``start_ns`` counts from the profiler
session's start, whose own epoch time is the ``profile_start_time`` stat
of its ``Task Environment`` plane: add that and the trace is on this
clock (checked on a v5e; docs/observability.md "Correlating with xplane
device traces"), so ``cover_names`` can name a device gap by the span
that covers it.

Parents: every span gets a process-unique ``seq`` when it opens and
keeps the ``seq`` of the span that was open round it on its thread
(``parent_seq``), traced or not, so a reader can take a span's self time
(its length less its children's; benchmark/span_readers.py does).

Export is the Chrome ``trace.json`` array format (``ph: "X"`` complete
events, microsecond timestamps) — load it in chrome://tracing or
Perfetto alongside the xplane-derived device trace.

Thread model: completed spans append under the GIL (list assignment into
a preallocated ring is atomic enough, same design as FlightRecorder);
the nesting stack is thread-local so producer threads and HTTP handler
threads nest independently. Each span records its thread name — the
Chrome export maps it to ``tid`` rows.

Distributed tracing (obs/tracing.py, docs/observability.md): inside a
``trace_scope`` every completed span additionally carries
``trace_id`` / ``span_id`` / ``parent_id`` — the Dapper-style causal
identity a request keeps across router → replica → batcher hops — and
is forwarded to the registered trace sink (the tail-based sampler).
Spans outside a scope pay nothing new. Process-wide *correlation tags*
(``set_correlation_tags``: the trainer's (gen, step), a replica's
weight version) ride every span as a separate ``corr`` dict so the
cross-process merge can line serving traces up against what the
co-resident trainer was doing.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time

from pytorch_distributed_train_tpu.obs.registry import get_registry

# ---------------------------------------------------------- trace context
# The active (trace_id, parent_span_id) of the calling thread, None when
# untraced. obs/tracing.py owns the wire format + sampling; this module
# only stamps ids so the hot span path stays import-light.
_TL_TRACE = threading.local()

# Process-wide correlation tags stamped (as Span.corr, NOT merged into
# args) on every completed span: {"gen": ..., "step": ...} from the
# trainer, {"weight_version": ...} from a serving replica.
_CORR: dict = {}

# Completed spans carrying a trace_id are handed here (obs/tracing.py
# registers the tail sampler at import). Kept as a late-bound global so
# spans.py never imports tracing.
_TRACE_SINK = None


def _rand_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def current_trace() -> tuple[str, str | None] | None:
    """The calling thread's (trace_id, open span id) or None. The second
    element is what an outbound hop / explicit ``record()`` call should
    parent to."""
    return getattr(_TL_TRACE, "ctx", None)


@contextlib.contextmanager
def trace_scope(trace_id: str, parent_id: str | None):
    """Install a trace context on the calling thread: spans opened inside
    get real trace/span/parent ids (nested spans parent to each other)."""
    prev = getattr(_TL_TRACE, "ctx", None)
    _TL_TRACE.ctx = (trace_id, parent_id)
    try:
        yield
    finally:
        _TL_TRACE.ctx = prev


def set_correlation_tags(**tags) -> None:
    """Merge process-wide correlation tags stamped on every span
    (``None`` value removes a tag). The trainer sets ``gen``/``step`` at
    step cadence; serving sets ``weight_version`` — ROADMAP-4's weight
    swap updates it and becomes traceable day one."""
    for k, v in tags.items():
        if v is None:
            _CORR.pop(k, None)
        else:
            _CORR[k] = v


def correlation_tags() -> dict:
    return dict(_CORR)


def set_trace_sink(fn) -> None:
    global _TRACE_SINK
    _TRACE_SINK = fn


class Span:
    """One completed timed region."""

    __slots__ = ("name", "t0", "dur_s", "thread", "depth", "args",
                 "trace_id", "span_id", "parent_id", "corr",
                 "t0_ns", "dur_ns", "seq", "parent_seq")

    def __init__(self, name: str, t0: float, dur_s: float, thread: str,
                 depth: int, args: dict, trace_id: str | None = None,
                 span_id: str | None = None, parent_id: str | None = None,
                 corr: dict | None = None, *, t0_ns: int | None = None,
                 dur_ns: int | None = None, seq: int | None = None,
                 parent_seq: int | None = None):
        self.name = name
        self.t0 = t0  # epoch seconds (time.time clock)
        self.dur_s = dur_s
        self.thread = thread
        self.depth = depth
        self.args = args
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.corr = corr
        # the same start and length as whole nanoseconds (time.time_ns /
        # perf_counter_ns), for joins with a profiler trace
        self.t0_ns = int(t0 * 1e9) if t0_ns is None else t0_ns
        self.dur_ns = int(dur_s * 1e9) if dur_ns is None else dur_ns
        self.seq = seq  # open order, unique in the recorder
        self.parent_seq = parent_seq  # seq of the enclosing span, if any

    def to_chrome(self, pid: int) -> dict:
        ev = {
            "name": self.name,
            "ph": "X",
            "ts": self.t0 * 1e6,  # Chrome wants microseconds
            "dur": self.dur_s * 1e6,
            "pid": pid,
            "tid": self.thread,
        }
        args = dict(self.corr) if self.corr else {}
        args.update(self.args or {})
        if self.trace_id is not None:
            args["trace_id"] = self.trace_id
            args["span_id"] = self.span_id
            if self.parent_id is not None:
                args["parent_id"] = self.parent_id
        if args:
            ev["args"] = args
        return ev


class _OpenSpan:
    """What ``SpanRecorder.span`` returns: the context manager, and while
    the region is open (and after) a handle on its clock reads, so a
    caller that accounts the same boundary elsewhere (the trainer's
    goodput buckets) reads no second clock: ``start_s`` is the
    ``perf_counter`` reading at entry, ``dur_s`` the length once closed.
    ``args`` is the dict the span will carry: a caller may add to it until
    the region closes."""

    __slots__ = ("_rec", "name", "args", "seq", "parent_seq", "t0_ns",
                 "_p0", "dur_s", "_tr", "_span_id")

    def __init__(self, rec: "SpanRecorder", name: str, args: dict):
        self._rec = rec
        self.name = name
        self.args = args
        self.dur_s = None

    @property
    def start_s(self) -> float:
        return self._p0 * 1e-9

    def __enter__(self) -> "_OpenSpan":
        stack = self._rec._stack()
        self.parent_seq = stack[-1].seq if stack else None
        self.seq = next(self._rec._seq)
        stack.append(self)
        self._tr = tr = getattr(_TL_TRACE, "ctx", None)
        self._span_id = None
        if tr is not None:
            self._span_id = _rand_id(8)
            _TL_TRACE.ctx = (tr[0], self._span_id)
        self.t0_ns = time.time_ns()
        self._p0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur_ns = time.perf_counter_ns() - self._p0
        self.dur_s = dur_ns * 1e-9
        tr = self._tr
        if tr is not None:
            _TL_TRACE.ctx = tr
        stack = self._rec._stack()
        depth = len(stack) - 1
        stack.pop()
        args = self.args
        if exc_type is not None:
            args = {**args, "error": True}
        self._rec._commit(Span(
            self.name, self.t0_ns * 1e-9, self.dur_s,
            threading.current_thread().name, depth, args,
            trace_id=tr[0] if tr is not None else None,
            span_id=self._span_id,
            parent_id=tr[1] if tr is not None else None,
            corr=dict(_CORR) if _CORR else None,
            t0_ns=self.t0_ns, dur_ns=dur_ns, seq=self.seq,
            parent_seq=self.parent_seq))
        return False


class SpanRecorder:
    """Fixed-capacity ring of completed spans + thread-local nest stacks."""

    def __init__(self, capacity: int = 4096, feed_registry: bool = True):
        self.capacity = capacity
        self.buf: list[Span | None] = [None] * capacity
        self.n = 0  # total spans ever completed
        self._seq = itertools.count()  # next() is one bytecode: GIL-atomic
        self._local = threading.local()
        self._feed_registry = feed_registry
        # slot-claim + n++ is a read-modify-write pair; concurrent
        # completions (producer thread vs step loop vs HTTP handlers)
        # could otherwise double-write a slot and leave a None hole
        # that crashes chrome_trace. Held for two assignments only.
        self._commit_lock = threading.Lock()
        # thread-name -> that thread's open-span stack. The stack is
        # only MUTATED by its own thread; the dict gives other threads
        # (watchdog abort dump) read access the pure thread-local
        # couldn't — a wedged main-thread checkpoint.save must be
        # visible from the heartbeat thread.
        self._stacks: dict[str, list] = {}

    # ------------------------------------------------------------- record
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
            self._stacks[threading.current_thread().name] = st
        return st

    def span(self, name: str, **args) -> _OpenSpan:
        """Time a region: ``with rec.span("x") as sp``. Nesting is
        tracked per thread (``depth``, ``parent_seq``); exceptions
        propagate — the span still records, flagged ``error=True`` so
        an aborted checkpoint save is visible in the dump. Under an
        active ``trace_scope`` the span gets trace ids and becomes the
        parent of spans nested inside it."""
        return _OpenSpan(self, name, args)

    def record(self, name: str, t0_wall: float, dur_s: float, *,
               trace: tuple[str, str | None] | None = None,
               thread: str | None = None, **args) -> str | None:
        """Commit a span with EXPLICIT timing — for phases measured by a
        different thread than the one that owns them (the serving
        scheduler records each request's queue / prefill / per-quantum
        decode spans from the step loop). ``trace`` is
        ``(trace_id, parent_span_id)``; None reads the calling thread's
        active scope. Without ``thread`` the span belongs to the calling
        thread and nests under the span open there (a compile reported
        by JAX while ``train.compile`` is open). Returns the new span id
        (None when untraced)."""
        if trace is None:
            trace = current_trace()
        trace_id = parent_id = span_id = None
        if trace is not None:
            trace_id, parent_id = trace
            span_id = _rand_id(8)
        stack = self._stack() if thread is None else ()
        sp = Span(name, t0_wall, dur_s,
                  thread or threading.current_thread().name, len(stack),
                  args, trace_id=trace_id, span_id=span_id,
                  parent_id=parent_id,
                  corr=dict(_CORR) if _CORR else None,
                  seq=next(self._seq),
                  parent_seq=stack[-1].seq if stack else None)
        self._commit(sp)
        return span_id

    def _commit(self, sp: Span) -> None:
        with self._commit_lock:
            self.buf[self.n % self.capacity] = sp
            self.n += 1
        if sp.trace_id is not None and _TRACE_SINK is not None:
            _TRACE_SINK(sp)
        if self._feed_registry:
            # every span is scrape-visible as a labeled histogram —
            # the decode-wait / ckpt-time numbers come for free
            get_registry().histogram(
                "span_seconds", labels={"name": sp.name},
                help="duration of host trace spans by span name",
            ).observe(sp.dur_s)

    # -------------------------------------------------------------- read
    def events(self) -> list[Span]:
        """Completed spans, oldest first (ring order). None-filtered: a
        reader racing an in-flight commit may see a not-yet-filled slot."""
        if self.n <= self.capacity:
            snap = self.buf[: self.n]
        else:
            i = self.n % self.capacity
            snap = self.buf[i:] + self.buf[:i]
        return [s for s in snap if s is not None]

    def active(self) -> list[str]:
        """This thread's currently-open span names, outermost first."""
        return [sp.name for sp in self._stack()]

    def active_all(self) -> dict[str, list[str]]:
        """EVERY thread's open spans (non-empty stacks only) — the abort
        dump runs on the heartbeat thread, where ``active()`` is vacuous."""
        return {t: [sp.name for sp in list(st)]
                for t, st in list(self._stacks.items()) if st}

    def clear(self) -> None:
        self.buf = [None] * self.capacity
        self.n = 0

    # ------------------------------------------------------------ export
    def chrome_trace(self) -> dict:
        pid = os.getpid()
        return {
            "traceEvents": [s.to_chrome(pid) for s in self.events()],
            "displayTimeUnit": "ms",
        }

    def dump_chrome_trace(self, path: str) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def write_text(self, out) -> None:
        """Human dump (watchdog abort path): last spans, one per line."""
        evs = self.events()
        out.write(f"=== trace spans: last {len(evs)} "
                  f"(of {self.n} total) ===\n")
        for s in evs:
            out.write(f"{s.t0:.3f} {'  ' * s.depth}{s.name} "
                      f"{s.dur_s * 1e3:.2f}ms thread={s.thread} {s.args}\n")
        out.flush()


_GLOBAL: SpanRecorder | None = None
_GLOBAL_LOCK = threading.Lock()


def get_recorder() -> SpanRecorder:
    """The process-wide recorder: trainer, checkpoint, data producers and
    HTTP handlers all record into one ring, so the exported trace shows
    their interleaving on a single timeline."""
    global _GLOBAL
    if _GLOBAL is None:
        with _GLOBAL_LOCK:
            if _GLOBAL is None:
                _GLOBAL = SpanRecorder()
    return _GLOBAL


def span(name: str, **args):
    """``with span("trainer.eval"): ...`` against the global recorder."""
    return get_recorder().span(name, **args)


# ------------------------------------------------------------ JAX compiles
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_LISTENER = False


def _on_jax_duration(event: str, duration_secs: float, fun_name: str = "",
                     **_kw) -> None:
    if event != COMPILE_EVENT:
        return
    args = {"fun": fun_name}
    if "step" in _CORR:
        args["step"] = _CORR["step"]
    get_recorder().record("jax.compile", time.time() - duration_secs,
                          duration_secs, **args)


def install_compile_listener() -> None:
    """Every XLA compile (or load from the persistent cache) JAX reports
    becomes a ``jax.compile`` span of the thread that asked for it, with
    JAX's own duration, the function's name and the trainer's ``step``
    tag: which step recompiled, and what ``train.compile`` and the first
    ``train.log`` spend compiling. Once a process: JAX keeps listeners
    for good."""
    global _COMPILE_LISTENER
    with _GLOBAL_LOCK:
        if _COMPILE_LISTENER:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
        _COMPILE_LISTENER = True


# ----------------------------------------------------- reading the ring
BETWEEN_SPANS = "between_spans"


def cover_names(intervals, spans, thread: str | None = None) -> list[str]:
    """For each ``(start_s, end_s)`` (epoch seconds, the spans' clock): the
    name of the innermost span of ``thread`` (the main thread unless given)
    that covers more than half of it, else ``"between_spans"`` — what the
    host was doing while, say, the device sat idle for that interval."""
    thread = thread or threading.main_thread().name
    mine = [s for s in spans if s.thread == thread]
    names = []
    for a, b in intervals:
        best = None
        for s in mine:
            lo, hi = s.t0_ns * 1e-9, (s.t0_ns + s.dur_ns) * 1e-9
            if 2.0 * (min(b, hi) - max(a, lo)) > b - a and (
                    best is None or s.depth > best.depth):
                best = s
        names.append(best.name if best is not None else BETWEEN_SPANS)
    return names
