#!/usr/bin/env python
"""HTTP serving endpoint over the continuous batcher — the end-user
service surface (torch-ecosystem analogue: TGI / vLLM's OpenAI-style
server, scoped to stdlib http.server: zero extra dependencies).

    python tools/serve_http.py --config llama2_7b \
        --safetensors model.st --tokenizer /models/llama2-tok \
        --port 8000 --slots 8 [--quantize int8]

    curl -s localhost:8000/v1/completions -d '{
        "prompt": "The capital of France is",
        "max_tokens": 32, "temperature": 0.7}'

API (JSON over POST, one object per request):
- ``POST /v1/completions``: {prompt, max_tokens?, temperature?, keep?,
  session?, stop?} → {text, finish_reason, session,
  usage:{prompt_tokens, completion_tokens}}. ``stop`` is a list of
  strings: generation CANCELS at the first occurrence (the match is
  excluded from the text, finish_reason "stop"); streamed responses
  hold back any tail that could still become a stop match. stop+keep
  is refused (a canceled request parks no session). ``keep: true`` parks the
  request's KV cache and returns a ``session`` id; posting that id as
  ``session`` continues the conversation from the resident cache (the
  prompt is then just the NEW turn — no resend of history). Sessions
  evict LRU under slot pressure (a resume then 404s in-band with
  finish_reason "session_evicted").
  ``top_p``/``min_p`` are PER-REQUEST (traced per-row operands — the
  OpenAI fields; out-of-range disables; server flags give the default);
  ``top_k`` stays a SERVER-wide flag (a static jit arg — per-request
  values would recompile). ``seed`` (OpenAI field) makes a sampled
  request REPRODUCIBLE independent of batch composition: seeded rows
  draw from their own fold_in(PRNGKey(seed), n_generated) chain, so the
  same request returns the same tokens no matter what else is in
  flight.
  ``logprobs: true`` adds each generated token's log-probability under
  the raw model distribution. ``n: k`` returns k INDEPENDENT sampled
  completions as ``choices`` (the prompt prefills once — a temporary
  prefix template forks k ways — so extra completions cost decode
  only); requires temperature > 0 (greedy duplicates are refused) and
  composes with logprobs but not stream/keep/session/stop.
- ``POST /v1/preload``: {prompt} → {session} — prefill a shared prefix
  (system prompt) once and park it; completions posted with
  ``prefix: <session>`` FORK it (the template survives, so one preload
  serves any number of requests). With ``--auto-prefix-min N`` the
  server forks AUTOMATICALLY whenever a prompt starts with a preloaded
  template of >= N tokens (longest match wins; explicit
  ``prefix``/``session`` always take precedence) — preload once, then
  every client that resends the system prompt verbatim gets the cached
  prefill without knowing the feature exists.
- ``POST /v1/chat/completions``: OpenAI chat schema — {messages:
  [{role, content}...], max_tokens?, temperature?, n?, stop?, stream?,
  logprobs?, penalties, logit_bias?} → {object: "chat.completion",
  choices: [{index, message: {role, content}, finish_reason}], usage}.
  Messages render through the tokenizer's own chat template when it
  ships one (HF ``apply_chat_template`` with the generation prompt),
  else a ChatML-ish `<|role|>` fallback. Streaming emits OpenAI
  ``chat.completion.chunk`` deltas. Stateless by definition (full
  history per call) — keep/session/prefix are refused here; resident-KV
  conversations live on ``/v1/completions``.
- ``GET /healthz``: {status, reliability, stats, weights} — liveness +
  batcher counters + the reliability section (queue depth, slot
  occupancy, admission state ``ok|shedding|draining``, SLO snapshot)
  the router's probe and balancing read, plus the MUTABLE weight state
  (current version/step, lag vs the trainer's newest published step,
  swap count) the fleet console's weight-sync panel reads.
- ``POST /admin/drain``: trigger the graceful drain over HTTP (same
  path as SIGTERM; what the router's rolling restart walks).
- ``POST /admin/weights``: live weight swap (online/,
  docs/online_training.md) — {version?} fetches that sealed version
  (default newest) from the launcher store, CRC-verifies + places it,
  and the scheduler flips params BETWEEN decode quanta: in-flight
  requests finish at the version they were admitted under (responses
  carry ``weight_version``, so stale completions are observable, never
  errors). Any fetch/verify/placement failure rejects the swap and the
  replica keeps serving its current version.

Reliability plane (serving_plane/, docs/serving_reliability.md):
per-request deadlines (``deadline_s`` field or ``--deadline-default``;
expiry cancels in the batcher — the KV slot frees NOW — and answers
504), admission control (``--max-queue-depth`` / ``--shed-ttft`` →
429 + ``Retry-After``), SLO metrics (TTFT / inter-token / queue-wait
percentiles through /healthz and the obs registry), a goodput split of
the scheduler loop (prefill/decode/stalled/idle), and a median+MAD
tail-latency detector that journals ``serve`` events and can fire the
managed profiler (``--profile-on-tail``).

Distributed tracing (obs/tracing.py, docs/observability.md): every
request continues the router's inbound ``traceparent`` (or roots a new
trace), the SLO phases — admission, queue wait, prefill, each decode
quantum, stream delivery — become spans in its tree, and a tail-based
sampler retains slow/failed/hedged/shed trees (plus a random baseline)
to per-host JSONL beside the event journal
(``--trace-dir`` / ``--trace-sample-pct`` / ``--trace-keep-slow-ms``;
``tools/timeline_report.py --trace <id>`` merges the cross-process
tree). Spans carry the replica's ``--weight-version`` correlation tag.

Threading model: request handler threads (ThreadingHTTPServer) enqueue
into the batcher under a lock and wait on a per-request event; ONE
scheduler thread drives ``batcher.step()`` — all device work stays on a
single thread, handlers only block on Python events. Requests admit into
free slots mid-stream, so concurrent callers batch together on the chip
without knowing about each other.
"""

from __future__ import annotations

import argparse
import json
import os
import queue as queue_mod
import signal
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# PDTT_SANITIZE=1: patch threading BEFORE the plane imports below run —
# they create module-global locks (events._LOCK, this file's
# _PROFILER_LOCK) at import time, and an activation from main() would
# leave those singletons unsanitized/invisible to the runtime graph.
from pytorch_distributed_train_tpu.utils import syncdbg  # noqa: E402

syncdbg.maybe_activate()

from pytorch_distributed_train_tpu.obs import events as events_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs import spans as spans_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs import tracing  # noqa: E402
from pytorch_distributed_train_tpu.obs.exposition import (  # noqa: E402
    CONTENT_TYPE as _METRICS_CONTENT_TYPE,
    render_metrics,
)
from pytorch_distributed_train_tpu.faults import (  # noqa: E402
    InjectedFault,
    maybe_fire as _maybe_fire_fault,
)
from pytorch_distributed_train_tpu.obs.registry import get_registry  # noqa: E402
from pytorch_distributed_train_tpu.obs.spans import span  # noqa: E402
from pytorch_distributed_train_tpu.online.swap import (  # noqa: E402
    PendingSwap,
    WeightState,
)
from pytorch_distributed_train_tpu.serving import trim_at_eos  # noqa: E402
from pytorch_distributed_train_tpu.serving_plane import (  # noqa: E402
    DeadlineExceeded,
    OverloadShed,
    ReliabilityPlane,
    TailLatencyMonitor,
)

_PROFILER = None
_PROFILER_LOCK = threading.Lock()

# _done marker for a request cancelled at its deadline: the waiter maps
# it to DeadlineExceeded (504), never to a Completion
_DEADLINE = object()


def _serving_profiler():
    """Lazy managed-profiler instance for the serving process (the
    ``POST /profile`` route + tail-latency anomaly captures): ad-hoc
    time-bounded captures into ``./profiles`` (or PDTT_PROFILE_DIR),
    ring-retained and xplane-summarized like the trainer's.
    ``PDTT_PROFILE_BACKEND=fake`` swaps in the marker-file backend
    (serving_plane/testing.py) so subprocess drills can assert a
    capture fired without a real jax trace session."""
    global _PROFILER
    with _PROFILER_LOCK:
        if _PROFILER is None:
            from pytorch_distributed_train_tpu.config import ObsConfig
            from pytorch_distributed_train_tpu.obs.profiler import (
                ManagedProfiler,
            )

            backend = None
            if os.environ.get("PDTT_PROFILE_BACKEND") == "fake":
                from pytorch_distributed_train_tpu.serving_plane.testing \
                    import FakeCaptureBackend

                backend = FakeCaptureBackend()
            cfg = ObsConfig(profile_dir=os.environ.get(
                "PDTT_PROFILE_DIR", "profiles"))
            _PROFILER = ManagedProfiler(cfg, run_dir=".", backend=backend)
        return _PROFILER



def render_chat(messages, tok) -> str:
    """OpenAI ``messages`` → prompt text. When the tokenizer ships a chat
    template (HF tokenizers: ``chat_template``), rendering is the model's
    own (apply_chat_template with the generation prompt appended) — an
    OpenAI client pointed here gets the model's canonical formatting.
    Otherwise a ChatML-ish fallback keeps the endpoint usable with the
    byte tokenizer / template-less tokenizers (documented divergence:
    role markers are `<|role|>` lines, not model-specific tokens)."""
    msgs = []
    for m in messages:
        role, content = str(m["role"]), str(m["content"])
        if role not in ("system", "user", "assistant", "tool"):
            raise ValueError(f"unknown chat role {role!r}")
        msgs.append({"role": role, "content": content})
    if not msgs:
        raise ValueError("messages must be non-empty")
    inner = getattr(tok, "_tok", None)
    if inner is not None and getattr(inner, "chat_template", None):
        return inner.apply_chat_template(msgs, tokenize=False,
                                         add_generation_prompt=True)
    return "".join(f"<|{m['role']}|>\n{m['content']}\n" for m in msgs) \
        + "<|assistant|>\n"


def _chat_response(out: dict) -> dict:
    """Completion-shaped service result → OpenAI chat.completion shape."""
    if "choices" in out:  # complete_n already returns choices
        choices = [{"index": i,
                    "message": {"role": "assistant",
                                "content": c["text"]},
                    "finish_reason": c.get("finish_reason"),
                    **({"logprobs": c["logprobs"]} if "logprobs" in c
                       else {})}
                   for i, c in enumerate(out["choices"])]
    else:
        choices = [{"index": 0,
                    "message": {"role": "assistant",
                                "content": out["text"]},
                    "finish_reason": out.get("finish_reason"),
                    **({"logprobs": out["logprobs"]}
                       if "logprobs" in out else {})}]
    return {"object": "chat.completion", "choices": choices,
            "usage": out.get("usage", {})}


def _find_stop(text: str, stops: list[str]):
    """Index of the earliest stop-string occurrence in ``text``, or
    None. (Only the cut position matters — the match itself is always
    excluded from the output.)"""
    best = None
    for st in stops:
        i = text.find(st)
        if i >= 0 and (best is None or i < best):
            best = i
    return best


def _stop_holdback(text: str, stops: list[str]) -> int:
    """Length of the longest text SUFFIX that is a proper prefix of some
    stop string — the tail a streamer must hold back because the next
    tokens could complete a stop match."""
    h = 0
    for st in stops:
        for k in range(min(len(st) - 1, len(text)), 0, -1):
            if text.endswith(st[:k]):
                h = max(h, k)
                break
    return h


class BatcherService:
    """Thread-safe facade over a (seq2seq-aware) continuous batcher: a
    single scheduler thread steps the device; callers submit and wait."""

    def __init__(self, batcher, tokenizer, *, idle_sleep_s: float = 0.005,
                 max_new_default: int = 64,
                 plane: ReliabilityPlane | None = None,
                 orphan_grace_s: float = 5.0):
        self.batcher = batcher
        self.tok = tokenizer
        self.max_new_default = max_new_default
        # Reliability plane (serving_plane/): SLO tracking always on;
        # admission control and deadlines engage only when its knobs
        # are set, so a default-constructed service behaves as before.
        self.plane = plane if plane is not None else ReliabilityPlane(
            slots=getattr(batcher, "slots", 1))
        self._lock = threading.Lock()
        self._done: dict[int, object] = {}
        self._done_ts: dict[int, float] = {}  # landing time (leak sweep)
        self._events: dict[int, threading.Event] = {}
        self._streams: dict[int, queue_mod.Queue] = {}  # uid -> chunk queue
        self._stream_seen: dict[int, int] = {}  # tokens already pushed
        # uid -> (chunk queue, landing ts) for streams whose keep=True
        # completion LANDED (scheduler popped _streams) but whose waiter
        # has not consumed the "done" yet: keeps the parked session
        # reachable if the waiter dies in that window (leak sweep GC)
        self._landed: dict[int, tuple] = {}
        self._token_seen: dict[int, int] = {}  # SLO tap over EVERY request
        # uid -> distributed-trace bookkeeping (obs/tracing.py): the
        # submitting handler's context + phase timestamps, so the
        # scheduler can record the request's queue / prefill / per-
        # quantum decode / stream spans into ITS tree. Mutated only
        # under self._lock.
        self._trace: dict[int, dict] = {}
        self._spans = spans_lib.get_recorder()
        # Online weight plane (online/swap.py): the mutable weight
        # version + staged-swap slot. main() reseeds it from
        # --weight-version; `weight_applier` (set for real backends) is
        # `(leaves, header) -> zero-arg apply fn | None` — it prepares
        # placed params in the HANDLER thread, the scheduler flips them
        # between quanta via weights.apply_pending() in _loop.
        self.weights = WeightState()
        self.weight_applier = None
        self._orphan_grace_s = orphan_grace_s
        self.error: str | None = None  # scheduler-death reason (terminal)
        self._idle_sleep_s = idle_sleep_s
        self._stop = False
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while not self._stop:
            try:
                # Staged weight swap, applied BETWEEN decode quanta:
                # this is the only thread that runs batcher.step(), so
                # flipping params here can never land mid-forward, and
                # doing it outside the service lock keeps intake live
                # through the flip (handlers never read params).
                self.weights.apply_pending()
                with self._lock:
                    busy = bool(self.batcher.queue
                                or self.batcher.active_slots)
                    stall_s = 0.0
                    q_t0w = time.time()  # quantum start, wall clock
                    if busy:
                        # `serve.slow_decode` fault point: an injected
                        # delay in the decode quantum — the tail-latency
                        # spike the TTFT/inter-token detectors exist to
                        # catch; its sleep lands in the 'stalled' bucket
                        # but still counts into the CADENCE sample below
                        # (the user-visible inter-token gap includes it)
                        t_stall = time.perf_counter()
                        if _maybe_fire_fault("serve.slow_decode"):
                            stall_s = time.perf_counter() - t_stall
                            self.plane.goodput.account("stalled", stall_s)
                    queued_before = {q.uid for q in self.batcher.queue
                                     if hasattr(q, "uid")}
                    admit0 = self.batcher.stats.get("admit_ms", 0.0)
                    t_step = time.perf_counter()
                    finished = self.batcher.step() if busy else []
                    step_dt = time.perf_counter() - t_step
                    now = time.monotonic()
                    if busy:
                        # goodput split of the quantum: the batcher's own
                        # admit_ms meter is the prefill share, the rest
                        # is the batched decode
                        prefill_s = max(0.0, (self.batcher.stats.get(
                            "admit_ms", 0.0) - admit0) / 1e3)
                        self.plane.goodput.account("prefill", prefill_s)
                        self.plane.goodput.account(
                            "decode", max(0.0, step_dt - prefill_s))
                        queued_after = {q.uid for q in self.batcher.queue
                                        if hasattr(q, "uid")}
                        for uid in queued_before - queued_after:
                            self.plane.on_admitted(uid, now=now)
                            tr = self._trace.get(uid)
                            if tr is not None and "t_admit_m" not in tr:
                                tr["t_admit_m"] = now
                                tr["t_admit_w"] = time.time()
                                # the queue-wait SLO phase as a span
                                self._trace_span_locked(
                                    uid, "serve.queue", tr["tw"],
                                    now - tr["tm"])
                    # one scan feeds both consumers: _token_seen covers
                    # EVERY live request (streams included — the two
                    # cursors advance in lockstep from submit), so the
                    # SLO tap and the stream push share its fresh map
                    total_new = 0
                    if self._token_seen:
                        for uid, toks in self.batcher.new_tokens_since(
                                self._token_seen).items():
                            self._token_seen[uid] += len(toks)
                            total_new += len(toks)
                            if self.plane.on_tokens(uid, len(toks),
                                                    now=now):
                                # THIS request's TTFT tripped the tail
                                # detector: retain its trace — the
                                # anomalous sample itself, not just the
                                # journal record
                                tr = self._trace.get(uid)
                                if tr is not None:
                                    tracing.flag(tr["tid"],
                                                 "tail_latency")
                            tr = self._trace.get(uid)
                            if tr is not None:
                                if "t_first_m" not in tr:
                                    tr["t_first_m"] = now
                                    tr["t_first_w"] = time.time()
                                    # fallbacks pair: a request never
                                    # seen leaving the queue spans
                                    # submit -> first token (covers its
                                    # unobserved queue wait too)
                                    self._trace_span_locked(
                                        uid, "serve.prefill",
                                        tr.get("t_admit_w", tr["tw"]),
                                        now - tr.get("t_admit_m",
                                                     tr["tm"]),
                                        tokens=len(toks))
                                else:
                                    # one span per decode quantum that
                                    # surfaced tokens for this request
                                    self._trace_span_locked(
                                        uid, "serve.decode", q_t0w,
                                        stall_s + step_dt,
                                        tokens=len(toks))
                            q = self._streams.get(uid)
                            if q is not None:
                                q.put(("tokens", toks))
                                self._stream_seen[uid] += len(toks)
                    if busy and total_new:
                        # decode cadence: quantum / tokens surfaced — the
                        # inter-token series the tail detector watches
                        # (stall included: it is user-visible latency)
                        self.plane.on_inter_token(
                            (stall_s + step_dt) / total_new, now=now)
                    for c in finished:
                        seen = self._token_seen.pop(c.uid, None)
                        if seen is not None:
                            if len(c.tokens) > seen and self.plane.\
                                    on_tokens(c.uid, len(c.tokens) - seen,
                                              now=now):
                                # same contract as the token-scan path:
                                # the request whose TTFT tripped the
                                # tail detector retains its trace, even
                                # when its first tokens only surface in
                                # this finished-completion flush
                                tr = self._trace.get(c.uid)
                                if tr is not None:
                                    tracing.flag(tr["tid"],
                                                 "tail_latency")
                            self.plane.on_finish(
                                c.uid,
                                "ok" if c.finish_reason in ("eos", "length")
                                else c.finish_reason, now=now)
                        # after the flag above: this pops the trace entry
                        self._trace_finish_locked(
                            c.uid, now,
                            outcome="ok" if c.finish_reason
                            in ("eos", "length") else c.finish_reason)
                        q = self._streams.pop(c.uid, None)
                        if q is not None:
                            seen_s = self._stream_seen.pop(c.uid, 0)
                            if len(c.tokens) > seen_s:
                                q.put(("tokens", c.tokens[seen_s:]))
                            q.put(("done", c))
                            if getattr(c, "session", None) is not None:
                                # parked session in flight to the waiter:
                                # stay reachable until it is consumed
                                self._landed[c.uid] = (q, now)
                            continue  # streamed: never stored in _done
                        self._done[c.uid] = c
                        self._done_ts[c.uid] = now
                        # NOT popped: the _events entry is the waiter's
                        # liveness marker — the waiter removes it when it
                        # collects, so the orphan sweep can tell "waiter
                        # slow to wake" from "waiter gone" exactly
                        ev = self._events.get(c.uid)
                        if ev is not None:
                            ev.set()
                    self._sweep_locked(now)
            except Exception as e:  # noqa: BLE001 — must not die silently
                # Device/compile errors are terminal for the only decode
                # thread: record the reason (healthz flips to error), fail
                # every waiter immediately instead of letting them time out.
                with self._lock:
                    self.error = f"{type(e).__name__}: {e}"
                    for ev in self._events.values():
                        ev.set()
                    self._events.clear()
                    for q in self._streams.values():
                        q.put(("error", self.error))
                    self._streams.clear()
                    self._stream_seen.clear()
                    self._token_seen.clear()
                    self._trace.clear()
                    self._landed.clear()
                return
            if not busy:
                time.sleep(self._idle_sleep_s)
            else:
                # Fairness gap: python locks are unfair — released and
                # immediately re-acquired by this loop, a busy scheduler
                # can starve handler threads (submit, cancel, SHED) for
                # the whole busy period. One zero-sleep yields the GIL
                # so a waiting handler actually wins the lock; intake
                # must stay responsive exactly when the server is busy.
                time.sleep(0)

    # -------------------------------------------- reliability plane hooks
    def _register_locked(self, uid: int, deadline_ts: float | None) -> None:
        """Track a freshly submitted request (SLO record + token tap).
        Runs in the same lock block as the submit, so the leak sweep
        can never see a slot-holding uid it does not know. The handler
        thread's active trace context (if any) is captured here: the
        scheduler parents the request's phase spans to it."""
        self._token_seen[uid] = 0
        tr = spans_lib.current_trace()
        if tr is not None:
            self._trace[uid] = {"tid": tr[0], "parent": tr[1],
                                "tw": time.time(),
                                "tm": time.monotonic()}
        self.plane.on_submit(uid, deadline_ts)

    def _trace_span_locked(self, uid: int, name: str, t0_wall: float,
                           dur_s: float, **args) -> None:
        tr = self._trace.get(uid)
        if tr is not None:
            self._spans.record(name, t0_wall, max(0.0, dur_s),
                               trace=(tr["tid"], tr["parent"]), **args)

    def _trace_finish_locked(self, uid: int, now: float,
                             outcome: str = "ok") -> None:
        """Close a request's trace bookkeeping: record the stream-
        delivery phase (first token -> finish) and drop the entry. The
        retention DECISION stays with whoever owns the trace root (the
        HTTP handler / router) — the scheduler only contributes spans."""
        tr = self._trace.pop(uid, None)
        if tr is None:
            return
        if "t_first_m" in tr:
            self._spans.record("serve.stream", tr["t_first_w"],
                               max(0.0, now - tr["t_first_m"]),
                               trace=(tr["tid"], tr["parent"]),
                               outcome=outcome)

    def _forget_locked(self, uid: int, outcome: str) -> None:
        """Close a request's SLO record from a cancel path. A no-op for
        requests the scheduler already finished (their record closed at
        completion) — outcomes never double-count."""
        if self._token_seen.pop(uid, None) is not None:
            self.plane.on_finish(uid, outcome)
        tr = self._trace.get(uid)
        if tr is not None and outcome == "timeout":
            tracing.flag(tr["tid"], "timeout")
        self._trace_finish_locked(uid, time.monotonic(), outcome=outcome)

    def _record_admission(self, t0_wall: float, t0_mono: float) -> None:
        """The admission-gate SLO phase as a span (handler thread, only
        when the caller carries a trace — a plane-less fake service
        records nothing new)."""
        if spans_lib.current_trace() is not None:
            self._spans.record("serve.admission", t0_wall,
                               max(0.0, time.monotonic() - t0_mono))

    def _release_dead_queue_session(self, q) -> None:
        """A cancel raced its request's completion: the Completion is in
        the (now unread) chunk queue. If it parked a session, release it
        — otherwise the sid is known to nobody and squats a slot until
        LRU pressure (the exactly-once half of the slot-leak fix)."""
        try:
            while True:
                kind, payload = q.get_nowait()
                if kind == "done" and getattr(payload, "session",
                                              None) is not None:
                    self.batcher.release(payload.session)
        except queue_mod.Empty:
            pass

    def _expire_locked(self, uid: int, now: float) -> None:
        """Deadline expiry: cancel in the batcher (queued or active —
        the slot/KV frees NOW, not at natural completion) and fail the
        waiter with the 504 marker."""
        self.batcher.cancel(uid)
        self._token_seen.pop(uid, None)
        self.plane.on_finish(uid, "deadline", now=now)
        tr = self._trace.get(uid)
        if tr is not None:
            # a 504 is a tail by definition: retain its trace, and let
            # the journal record cross-link to it
            tracing.flag(tr["tid"], "deadline")
        self._trace_finish_locked(uid, now, outcome="deadline")
        events_lib.emit("serve", "deadline_expired", uid=uid,
                        trace=tr["tid"] if tr is not None else None)
        q = self._streams.pop(uid, None)
        if q is not None:
            self._stream_seen.pop(uid, None)
            q.put(("expired", f"request {uid} exceeded its deadline"))
        ev = self._events.pop(uid, None)
        if ev is not None:
            self._done[uid] = _DEADLINE
            self._done_ts[uid] = now
            ev.set()

    def _sweep_locked(self, now: float) -> None:
        """Between-steps reliability sweep (scheduler thread, under the
        service lock): (1) deadline expiries → cancel + 504; (2) slot
        leaks — any slot-holding request with no live waiter is
        reclaimed and counted (`serve_slot_leaks_total`), and a landed
        completion nobody will ever collect has its parked session
        released after a grace window."""
        for uid in self.plane.take_expired(now=now):
            self._expire_locked(uid, now)
        active_uids = getattr(self.batcher, "active_uids", None)
        if active_uids is None:
            return  # minimal fake batchers (tests): no slot surface
        waiters = set(self._events) | set(self._streams)
        for uid in active_uids():
            if uid in waiters or uid in self._done:
                continue
            self.batcher.cancel(uid)
            self._token_seen.pop(uid, None)
            tr = self._trace.get(uid)
            if tr is not None:
                tracing.flag(tr["tid"], "leak")
            self._trace_finish_locked(uid, now, outcome="leak")
            self.plane.note_leak(uid, "active_slot")
        for uid, t_done in list(self._done_ts.items()):
            if uid in self._events or now - t_done < self._orphan_grace_s:
                continue
            c = self._done.pop(uid, None)
            self._done_ts.pop(uid, None)
            if c is None or c is _DEADLINE:
                continue
            if getattr(c, "session", None) is not None:
                self.batcher.release(c.session)
            self.plane.note_leak(uid, "orphan_done")
        for uid, (q, t_land) in list(self._landed.items()):
            # landed "done" (with a parked session) nobody consumed and
            # nobody abandoned — a waiter thread that died without its
            # except path running. Release after the same grace.
            if now - t_land < self._orphan_grace_s:
                continue
            self._landed.pop(uid, None)
            self._release_dead_queue_session(q)
            self.plane.note_leak(uid, "orphan_stream")

    def healthy(self) -> bool:
        return self.error is None and self._thread.is_alive()

    def preload(self, prompt: str) -> int:
        """Park a shared-prefix template; returns its session id."""
        ids = self.tok.encode(prompt)
        if not ids:
            raise ValueError("empty prompt after tokenization")
        with self._lock:
            if self.error is not None:
                raise RuntimeError(f"scheduler dead: {self.error}")
            return self.batcher.preload(ids)

    def complete_n(self, prompt: str, max_tokens: int,
                   temperature: float, n: int,
                   timeout_s: float = 600.0, *,
                   logprobs: bool = False,
                   penalties: dict | None = None,
                   deadline_s: float | None = None) -> dict:
        """k independent sampled completions of one prompt. The prompt
        minus its last token prefills ONCE into a temporary prefix
        template; each of the k forks ingests just that final token (a
        fork must ingest something to have logits to sample from) and
        decodes its own continuation — the forks batch together in the
        decode step, so extra completions cost decode only. The template
        is released when all k land."""
        if n < 2:
            raise ValueError("n must be >= 2 (plain complete() covers 1)")
        if temperature <= 0.0:
            raise ValueError(
                "n > 1 with temperature 0 would return n identical "
                "greedy completions — set a temperature")
        ids = self.tok.encode(prompt)
        if not ids:
            raise ValueError("empty prompt after tokenization")
        events: dict[int, threading.Event] = {}
        sid = None
        # Repetition-penalized n>1 requests always prefill the FULL
        # prompt per fork: the shared-prefix template would leave only
        # the final token in each fork's repetition context, making the
        # distribution depend on slot availability (template admitted or
        # not). Deterministic semantics beat the saved prefills.
        # Only repetition_penalty scores the prompt — presence/frequency
        # count generated tokens only (OpenAI semantics) and logit_bias
        # is context-independent, so neither disables the shared-prefix
        # optimization; and EFFECTIVE values gate, not key presence (a
        # client sending the explicit OpenAI defaults must not lose the
        # optimization).
        force_full_prompt = (
            float((penalties or {}).get("repetition_penalty", 1.0)) != 1.0)
        # the shared-prefill trick needs session support (causal
        # batchers) and a >= 2-token prompt; otherwise n plain submits
        # still serve the request — just paying n prefills
        share = (getattr(self.batcher, "supports_sessions", False)
                 and len(ids) >= 2 and not force_full_prompt)

        def _cleanup_locked():
            """Release the template and withdraw every fork: cancel the
            unfinished (they then never complete — no abandon marker
            needed), drop any already-landed results (the lock excludes
            the scheduler, so cancel-vs-finish cannot race)."""
            nonlocal sid
            if sid is not None:
                self.batcher.release(sid)
                sid = None
            for uid in events:
                if not self.batcher.cancel(uid):
                    self._done.pop(uid, None)
                    self._done_ts.pop(uid, None)
                self._events.pop(uid, None)
                self._forget_locked(uid, "cancelled")

        deadline_ts = self.plane.resolve_deadline(deadline_s)
        adm_w, adm_m = time.time(), time.monotonic()
        with self._lock:
            if self.error is not None:
                raise RuntimeError(f"scheduler dead: {self.error}")
            self.plane.admit_or_raise(len(self.batcher.queue))
            try:
                if share and self.batcher.can_preload(len(ids) - 1):
                    # (a pure capacity check, not except RuntimeError: a
                    # broad catch would also swallow device errors from
                    # the synchronous template prefill)
                    sid = self.batcher.preload(ids[:-1])
                # else: every slot busy right now — a template can't
                # queue, but plain submits can; fall back to n
                # independent prefills rather than 503ing a request
                # that only needs to wait its turn
                for _ in range(n):
                    uid = self.batcher.submit(
                        ids[-1:] if sid is not None else ids, max_tokens,
                        temperature=temperature, eos_id=self.tok.eos_id,
                        prefix=sid, **(penalties or {}))
                    events[uid] = threading.Event()
                    self._events[uid] = events[uid]
                    self._register_locked(uid, deadline_ts)
            except (ValueError, RuntimeError):
                _cleanup_locked()
                raise
        self._record_admission(adm_w, adm_m)
        try:
            choices = []
            total_generated = 0
            # One timeout budget for the whole request, not timeout_s per
            # fork: waits are sequential, so each gets what remains.
            deadline = time.monotonic() + timeout_s
            for uid, ev in events.items():
                if not ev.wait(max(0.0, deadline - time.monotonic())):
                    raise TimeoutError(f"completion {uid} timed out")
                with self._lock:
                    c = self._done.pop(uid, None)
                    self._done_ts.pop(uid, None)
                    self._events.pop(uid, None)
                if c is _DEADLINE:
                    raise DeadlineExceeded(
                        f"request {uid} exceeded its deadline; "
                        "slot reclaimed")
                if c is None:
                    raise RuntimeError(f"scheduler dead: {self.error}")
                total_generated += len(c.tokens)
                new = trim_at_eos(c.tokens, self.tok.eos_id)
                choice = {"text": self.tok.decode(new),
                          "finish_reason": c.finish_reason}
                if logprobs:
                    choice["logprobs"] = [round(v, 6)
                                          for v in c.logprobs[: len(new)]]
                choices.append(choice)
            with self._lock:
                if sid is not None:
                    self.batcher.release(sid)
                    sid = None
        except BaseException:
            with self._lock:
                _cleanup_locked()
            raise
        return {"choices": choices, "session": None,
                "usage": {"prompt_tokens": len(ids),
                          "completion_tokens": total_generated}}

    def complete(self, prompt: str, max_tokens: int, temperature: float,
                 timeout_s: float = 600.0, *, keep: bool = False,
                 session: int | None = None, prefix: int | None = None,
                 stop: list[str] | None = None,
                 logprobs: bool = False,
                 penalties: dict | None = None,
                 deadline_s: float | None = None) -> dict:
        if stop:
            if keep:
                raise ValueError(
                    "stop with keep is unsupported (a stop-canceled "
                    "request parks no session)")
            return self._complete_with_stop(
                prompt, max_tokens, temperature, timeout_s,
                session=session, prefix=prefix, stop=stop,
                logprobs=logprobs, penalties=penalties,
                deadline_s=deadline_s)
        ids = self.tok.encode(prompt)
        if not ids:
            raise ValueError("empty prompt after tokenization")
        deadline_ts = self.plane.resolve_deadline(deadline_s)
        ev = threading.Event()
        adm_w, adm_m = time.time(), time.monotonic()
        with self._lock:
            # Checked UNDER the lock: the scheduler's death path clears
            # _events under this lock, so registering after a pre-lock
            # check could enqueue an event nothing will ever set.
            if self.error is not None:
                raise RuntimeError(f"scheduler dead: {self.error}")
            self.plane.admit_or_raise(len(self.batcher.queue))
            uid = self.batcher.submit(ids, max_tokens,
                                      temperature=temperature,
                                      eos_id=self.tok.eos_id,
                                      keep=keep, session=session,
                                      prefix=prefix, **(penalties or {}))
            self._events[uid] = ev
            self._register_locked(uid, deadline_ts)
        self._record_admission(adm_w, adm_m)
        # the scheduler's deadline sweep answers expiry (504 + slot
        # reclaim); the local wait only needs to outlast it slightly
        wait_s = timeout_s if deadline_ts is None else min(
            timeout_s, max(0.0, deadline_ts - time.monotonic()) + 2.0)
        timed_out = not ev.wait(wait_s)
        with self._lock:
            # The completion may have landed in the wait→lock window even
            # on the timeout path — prefer returning it over withdrawing.
            c = self._done.pop(uid, None)
            self._done_ts.pop(uid, None)
            self._events.pop(uid, None)  # this waiter is done waiting
            if timed_out and c is None:
                # Withdraw NOW (the slot-leak fix, non-streamed flavor):
                # a dead waiter's request must not decode on — and hold
                # its KV slot — until natural completion.
                self.batcher.cancel(uid)
                self._forget_locked(uid, "timeout")
        if c is _DEADLINE:
            raise DeadlineExceeded(
                f"request {uid} exceeded its deadline; slot reclaimed")
        if c is None:
            if timed_out:
                raise TimeoutError(
                    f"request {uid} timed out after {timeout_s}s")
            raise RuntimeError(f"scheduler dead: {self.error}")
        new = trim_at_eos(c.tokens, self.tok.eos_id)
        out = {
            "text": self.tok.decode(new),
            "finish_reason": c.finish_reason,
            "session": c.session,
            "usage": {"prompt_tokens": len(ids),
                      "completion_tokens": len(c.tokens)},
        }
        if logprobs:
            out["logprobs"] = [round(v, 6)
                               for v in c.logprobs[: len(new)]]
        return out

    def _complete_with_stop(self, prompt, max_tokens, temperature,
                            timeout_s, *, session, prefix, stop,
                            logprobs: bool = False,
                            penalties: dict | None = None,
                            deadline_s: float | None = None) -> dict:
        """Stop-sequence completions ride the streaming tap: decode the
        accumulated text each tick, CANCEL the request at the first stop
        match (it stops consuming decode steps), trim the match out."""
        uid, n_prompt, chunks = self.stream(prompt, max_tokens,
                                            temperature, timeout_s,
                                            session=session,
                                            prefix=prefix,
                                            penalties=penalties,
                                            deadline_s=deadline_s)
        acc: list[int] = []
        comp = None
        for toks, c in chunks:
            acc.extend(toks)
            if c is not None:
                comp = c
                break
            kept = trim_at_eos(acc, self.tok.eos_id)
            text = self.tok.decode(kept)
            hit = _find_stop(text, stop)
            if hit is not None:
                self.cancel_stream(uid)
                out = {"text": text[: hit], "finish_reason": "stop",
                       "session": None,
                       "usage": {"prompt_tokens": n_prompt,
                                 "completion_tokens": len(acc)}}
                if logprobs:
                    # the streaming tap carries token ids only; a
                    # stop-canceled request has no Completion to read
                    # per-token logprobs from — explicit null, not absent
                    out["logprobs"] = None
                return out
        # finished naturally — the final flush may still contain a stop
        kept = trim_at_eos(comp.tokens, self.tok.eos_id)
        text = self.tok.decode(kept)
        hit = _find_stop(text, stop)
        reason = comp.finish_reason
        if hit is not None:
            text, reason = text[: hit], "stop"
        out = {"text": text, "finish_reason": reason, "session": None,
               "usage": {"prompt_tokens": n_prompt,
                         "completion_tokens": len(comp.tokens)}}
        if logprobs:
            out["logprobs"] = [round(v, 6)
                               for v in comp.logprobs[: len(kept)]]
        return out

    def stream(self, prompt: str, max_tokens: int, temperature: float,
               timeout_s: float = 600.0, *, keep: bool = False,
               session: int | None = None, prefix: int | None = None,
               penalties: dict | None = None,
               deadline_s: float | None = None):
        """Returns (uid, chunk iterator). Validation and submission run
        EAGERLY (so callers can reject before committing to a response);
        the iterator yields (new_token_ids, completion_or_None) chunks as
        the batched decode produces them, ending with the Completion.
        Returns (uid, prompt_token_count, iterator); ``timeout_s`` bounds
        the wait for EACH chunk (a deadline tightens it — a stalled
        stream expires at the deadline, not at the generic timeout). A
        caller that stops consuming must call ``abandon_stream(uid)``
        (or ``cancel_stream`` to also stop the decode)."""
        ids = self.tok.encode(prompt)
        if not ids:
            raise ValueError("empty prompt after tokenization")
        deadline_ts = self.plane.resolve_deadline(deadline_s)
        q: queue_mod.Queue = queue_mod.Queue()
        adm_w, adm_m = time.time(), time.monotonic()
        with self._lock:
            if self.error is not None:
                raise RuntimeError(f"scheduler dead: {self.error}")
            self.plane.admit_or_raise(len(self.batcher.queue))
            uid = self.batcher.submit(ids, max_tokens,
                                      temperature=temperature,
                                      eos_id=self.tok.eos_id,
                                      keep=keep, session=session,
                                      prefix=prefix, **(penalties or {}))
            self._streams[uid] = q
            self._stream_seen[uid] = 0
            self._register_locked(uid, deadline_ts)
        self._record_admission(adm_w, adm_m)

        def chunks():
            while True:
                wait_s = timeout_s if deadline_ts is None else min(
                    timeout_s,
                    max(0.05, deadline_ts - time.monotonic() + 2.0))
                try:
                    kind, payload = q.get(timeout=wait_s)
                except queue_mod.Empty:
                    self.abandon_stream(uid)
                    raise TimeoutError(
                        f"request {uid} produced no chunk for {wait_s}s")
                if kind == "tokens":
                    yield payload, None
                elif kind == "done":
                    # consumed: the waiter frame now holds the payload
                    # (abandon_stream's `landed=` covers it from here)
                    with self._lock:
                        self._landed.pop(uid, None)
                    yield [], payload
                    return
                elif kind == "expired":  # deadline sweep cancelled it
                    raise DeadlineExceeded(str(payload))
                else:  # "error"
                    raise RuntimeError(f"scheduler dead: {payload}")

        return uid, len(ids), chunks()

    def cancel_stream(self, uid: int) -> None:
        """Cancel an in-flight streamed request (stop-sequence match) and
        drop its tap. If the request raced to completion first, any
        session its keep=True completion parked is released from the
        dead chunk queue — the exactly-once contract of the slot-leak
        fix (before it, a raced keep-completion's session squatted a
        slot nobody could ever release)."""
        with self._lock:
            q = self._streams.pop(uid, None)
            self._stream_seen.pop(uid, None)
            if not self.batcher.cancel(uid):
                if q is None:  # landed already: the queue moved
                    q, _ = self._landed.pop(uid, (None, None))
                if q is not None:
                    self._release_dead_queue_session(q)
            self._forget_locked(uid, "cancelled")

    def abandon_stream(self, uid: int, landed=None) -> None:
        """Stop tracking a streaming request whose consumer went away
        (client disconnect, chunk timeout) — and WITHDRAW it from the
        batcher. This is the abandoned-stream slot-leak fix: before it,
        a stream abandoned between submit and first token kept decoding
        into its KV slot until natural completion, and a keep=True
        completion then parked a session nobody owned (a permanent slot
        leak — exactly what the ``serve.slot_leak`` drill injects by
        skipping the release below; the scheduler's leak sweep must
        catch and reclaim it). If the completion already LANDED, its
        queue (still holding the "done") is drained from ``_landed``;
        ``landed=`` hands over a completion the caller consumed but
        failed to deliver (final-chunk write died — the client never
        learned the session id, so its parked session is released). A
        no-op once the request finished AND its session was delivered."""
        with self._lock:
            q = self._streams.pop(uid, None)
            if q is None:
                q, _ = self._landed.pop(uid, (None, None))
                if q is not None:
                    self._release_dead_queue_session(q)
                elif landed is not None and getattr(
                        landed, "session", None) is not None:
                    self.batcher.release(landed.session)
                return
            self._stream_seen.pop(uid, None)
            if _maybe_fire_fault("serve.slot_leak"):
                return  # drill: walk away without releasing anything
            if not self.batcher.cancel(uid):
                # raced to completion: its parked session (if any) is in
                # the dead queue — release exactly once
                self._release_dead_queue_session(q)
            self._forget_locked(uid, "abandoned")

    def stats(self) -> dict:
        # Snapshot WITHOUT the step lock: the counters are plain ints
        # mutated only by the scheduler thread, and a liveness probe must
        # not block behind a minutes-long first-compile step quantum.
        return dict(self.batcher.stats)

    def shutdown(self):
        self._stop = True
        self._thread.join(timeout=5)


class GracefulDrain:
    """SIGTERM → drain-and-exit for the HTTP server (the load-balancer
    contract every production rollout needs): stop ACCEPTING work (new
    POSTs get a retryable 503, ``/healthz`` flips to ``draining`` so the
    LB pulls this backend), let IN-FLIGHT requests finish — bounded by
    ``grace_s``, a wedged decode must not outlive the scheduler's
    SIGKILL — then stop the server and the batcher thread cleanly.

    The SIGTERM handler CHAINS to whatever was installed before it (the
    same convention as faults/preemption.py and the watchdog dump
    handler), so composing with diagnostics handlers works in either
    install order. ``request_drain()`` is also callable directly (tests,
    an admin endpoint)."""

    def __init__(self, server, service, grace_s: float = 30.0):
        self.server = server
        self.service = service
        self.grace_s = grace_s
        self.draining = False
        self._inflight = 0
        self._lock = threading.Lock()
        self._prev = None
        self._thread: threading.Thread | None = None

    # ------------------------------------------------------- request gate
    def begin_request(self) -> bool:
        """Admit one request; False once draining (caller answers 503)."""
        with self._lock:
            if self.draining:
                return False
            self._inflight += 1
            return True

    def end_request(self) -> None:
        with self._lock:
            self._inflight -= 1

    # ------------------------------------------------------------ drain
    def install(self) -> None:
        try:
            self._prev = signal.getsignal(signal.SIGTERM)
            signal.signal(signal.SIGTERM, self._handle)
        except ValueError:
            pass  # not the main thread (tests drive request_drain directly)

    def _handle(self, signum, frame) -> None:
        self.request_drain()
        prev = self._prev
        if callable(prev) and prev not in (signal.SIG_DFL, signal.SIG_IGN):
            prev(signum, frame)

    def request_drain(self) -> None:
        with self._lock:
            if self.draining:
                return
            self.draining = True
        events_lib.emit("serve", "drain_begin", grace_s=self.grace_s)
        print(f"[serve] draining: no new requests; waiting up to "
              f"{self.grace_s:.0f}s for in-flight to finish", flush=True)
        # The actual wait runs off-thread: a signal handler (or a test)
        # must return immediately, and server.shutdown() deadlocks when
        # called from a handler thread the server is joining.
        self._thread = threading.Thread(target=self._drain, daemon=True,
                                        name="serve-drain")
        self._thread.start()

    def _drain(self) -> None:
        deadline = time.monotonic() + self.grace_s
        while time.monotonic() < deadline:
            with self._lock:
                if self._inflight == 0:
                    break
            time.sleep(0.05)
        with self._lock:
            leftover = self._inflight
        if leftover:
            print(f"[serve] drain grace expired with {leftover} request(s) "
                  "still in flight — shutting down anyway", flush=True)
        else:
            print("[serve] drained; shutting down", flush=True)
        events_lib.emit("serve", "drain_done", leftover=leftover)
        self.server.shutdown()  # unblocks serve_forever()
        self.service.shutdown()


def _swap_store(service):
    """The replica's handle onto the weight-publish plane, built lazily
    and cached on the service (same resilient wrapper --advertise uses;
    None outside a store-backed job)."""
    store = getattr(service, "_weight_store", None)
    if store is None:
        from pytorch_distributed_train_tpu import store_plane

        store = store_plane.resilient_worker_store(name="weight-swap")
        if store is not None:
            service._weight_store = store
    return store


def _swap_weights(service, req: dict) -> tuple[int, dict]:
    """POST /admin/weights body: {"version": N?} (default: the newest
    sealed version). Fetch → CRC verify → place (handler thread) →
    stage → scheduler applies between quanta. Every failure leaves the
    replica serving its CURRENT version — a swap can reject, it cannot
    half-land (docs/online_training.md swap protocol)."""
    weights = getattr(service, "weights", None)
    if weights is None:
        return 503, {"error": "no weight plane on this service"}
    t0 = time.monotonic()
    want = req.get("version")
    want = int(want) if want is not None else None
    # `weights.swap` fault point: the injected failure is a 503 BEFORE
    # any fetch — the replica keeps its version, the caller retries
    try:
        _maybe_fire_fault("weights.swap")
    except InjectedFault as e:
        weights.reject(want if want is not None else "latest",
                       f"injected: {e}")
        return 503, {"error": str(e), "serving": weights.version}
    store = _swap_store(service)
    if store is None:
        return 503, {"error": "no TPUSTORE_ADDR: weight swaps ride the "
                              "launcher store"}
    from pytorch_distributed_train_tpu.online import publisher as pub_lib

    fetched = pub_lib.fetch_version(store, want)
    if fetched is None:
        # unsealed / incomplete / corrupt (CRC) — indistinguishable on
        # purpose: none of them may touch the serving params
        weights.reject(want if want is not None else "latest",
                       "verify_failed")
        return 409, {"error": "published version unavailable or failed "
                              "verification", "serving": weights.version}
    info, leaves, header = fetched
    weights.note_published(info["version"], info["step"])
    old = weights.version
    if str(info["version"]) == old:
        return 200, {"status": "already_current", "version": old}
    apply_fn = None
    if service.weight_applier is not None:
        # the expensive half (host→device placement into the serving
        # mesh's shardings) runs HERE, off the scheduler's critical path
        apply_fn = service.weight_applier(leaves, header)
        if apply_fn is None:
            weights.reject(info["version"], "placement_mismatch")
            return 409, {"error": "published leaves do not match the "
                                  "serving params template",
                         "serving": old}
    pending = PendingSwap(version=str(info["version"]),
                          step=int(info["step"]), apply_fn=apply_fn,
                          t0=t0)
    if not weights.stage(pending):
        return 409, {"error": "another swap is in flight",
                     "serving": old}
    if not pending.done.wait(timeout=30.0):
        return 504, {"error": "swap staged but not applied within 30s "
                              "(scheduler wedged?)", "serving": old}
    if pending.error:
        return 500, {"error": pending.error, "serving": weights.version}
    return 200, {"status": "swapped", "version": weights.version,
                 "old_version": old, "step": int(info["step"]),
                 "swap_seconds": round(pending.duration_s, 6)}


def make_handler(service: BatcherService, drain: GracefulDrain | None = None):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # quiet by default
            pass

        def _send(self, code: int, obj: dict,
                  headers: dict | None = None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _health_body(self, status: str) -> dict:
            # Reliability section riding /healthz (lock-free w.r.t. the
            # scheduler — a probe must not block behind a wedged decode):
            # queue depth, slot occupancy, admission state and the SLO
            # snapshot, so the router's balancing/probing needs no
            # second endpoint. Omitted for plane-less service fakes
            # (tests): their healthz keeps the pre-plane shape.
            out = {"status": status, "stats": service.stats()}
            weights = getattr(service, "weights", None)
            if weights is not None:
                # mutable weight version (online/swap.py): the swap is
                # visible here without a restart — current version/step,
                # lag vs the trainer's newest published step, swap count
                out["weights"] = weights.snapshot()
            batcher = getattr(service, "batcher", None)
            plane = getattr(service, "plane", None)
            if batcher is None or plane is None:
                return out
            depth = len(batcher.queue)
            acct = getattr(batcher, "slot_accounting", lambda: {})()
            rel = plane.snapshot(depth, acct)
            if status == "draining":
                rel["admission"] = "draining"
            out["reliability"] = rel
            return out

        def do_GET(self):
            if self.path == "/healthz":
                if drain is not None and drain.draining:
                    # 503 so load balancers stop routing here; the body
                    # says WHY (a drain, not a failure).
                    self._send(503, self._health_body("draining"))
                elif service.healthy():
                    self._send(200, self._health_body("ok"))
                else:
                    body = self._health_body("error")
                    body["error"] = service.error
                    self._send(503, body)
            elif self.path.split("?", 1)[0] == "/metrics":
                # Prometheus scrape (obs/): request counters + latency
                # histograms + batcher gauges, same registry the trainer
                # sidecar serves. Reads plain counters only — never the
                # scheduler lock, so a wedged decode stays scrapable.
                for k, v in service.stats().items():
                    if isinstance(v, (int, float)):
                        get_registry().gauge(
                            f"serve_batcher_{k}",
                            help="continuous-batcher counter").set(v)
                for k, v in getattr(getattr(service, "batcher", None),
                                    "slot_accounting", lambda: {})().items():
                    get_registry().gauge(
                        f"serve_slots_{k}",
                        help="slot/queue occupancy at scrape time").set(v)
                body = render_metrics().encode()
                self.send_response(200)
                self.send_header("Content-Type", _METRICS_CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self._send(404, {"error": "unknown path"})

        def do_POST(self):
            if self.path.split("?", 1)[0] == "/admin/drain":
                # The drain path over HTTP (same effect as SIGTERM): the
                # router's rolling restart walks replicas through this.
                if drain is None:
                    self._send(503, {"error": "no drain controller"})
                else:
                    drain.request_drain()
                    self._send(202, {"status": "draining"})
                return
            if self.path.split("?", 1)[0] == "/admin/weights":
                # Live weight swap (online/; docs/online_training.md):
                # fetch + verify the published version, stage it, wait
                # for the scheduler to flip it between quanta. Subject
                # to the drain gate: a draining replica is leaving the
                # rotation — swapping it is wasted work.
                if drain is not None and not drain.begin_request():
                    self._send(503, {"error": "server draining"})
                    return
                try:
                    try:
                        n = int(self.headers.get("Content-Length", 0))
                        req = json.loads(self.rfile.read(n) or b"{}")
                    except ValueError as e:
                        self._send(400, {"error": f"bad body: {e}"})
                        return
                    # the swap rides the driver's trace: its spans carry
                    # the OLD weight_version tag before apply_pending
                    # re-stamps, the NEW one after — the flip the
                    # timeline report shows
                    ctx = tracing.continue_or_start(
                        self.headers.get("traceparent"))
                    t0 = time.monotonic()
                    try:
                        with tracing.activate(ctx):
                            with span("http.admin.weights"):
                                code, obj = _swap_weights(service, req)
                    finally:
                        tracing.get_tracer().finish(
                            ctx.trace_id,
                            dur_s=time.monotonic() - t0)
                    self._send(code, obj)
                finally:
                    if drain is not None:
                        drain.end_request()
                return
            if self.path.split("?", 1)[0] == "/profile":
                # On-demand capture of the SERVING process (managed
                # profiler plane, obs/profiler.py): time-bounded since
                # there is no step loop to count windows in. Body:
                # {"seconds": N} (default 3, capped at 60). Subject to
                # the drain gate like any other POST: a draining server
                # must not accept new profiling work whose stop timer
                # would outlive the process.
                if drain is not None and not drain.begin_request():
                    self._send(503, {"error": "server draining"})
                    return
                try:
                    try:
                        n = int(self.headers.get("Content-Length", 0))
                        req = json.loads(self.rfile.read(n) or b"{}")
                        seconds = min(60.0, max(
                            0.1, float(req.get("seconds", 3.0))))
                        logdir = _serving_profiler().capture_for_seconds(
                            seconds, reason="http")
                    except Exception as e:
                        self._send(500,
                                   {"error": f"{type(e).__name__}: {e}"})
                        return
                    if logdir is None:
                        self._send(409, {"error": "capture already open"})
                    else:
                        self._send(202, {"status": "capturing",
                                         "seconds": seconds,
                                         "dir": logdir})
                finally:
                    if drain is not None:
                        drain.end_request()
                return
            if self.path not in ("/v1/completions", "/v1/preload",
                                 "/v1/chat/completions"):
                self._send(404, {"error": "unknown path"})
                return
            if drain is not None and not drain.begin_request():
                # Draining: the retryable status (the same contract as
                # an injected handler fault) — clients re-resolve and
                # land on a healthy backend.
                self._send(503, {"error": "server draining"})
                return
            try:
                self._do_post_admitted()
            finally:
                if drain is not None:
                    drain.end_request()

        def _do_post_admitted(self):
            # Request-handling observability: a counter per path and a
            # span covering the handler (wait + decode + serialization)
            # — span durations land in the span_seconds{name=...}
            # histogram, so /metrics carries request latency for free.
            get_registry().counter(
                "http_requests_total", labels={"path": self.path},
                help="requests by path").inc()
            # `serve.handler` fault point (faults/; armed via the
            # PDTT_FAULTS env var): an injected handler fault becomes a
            # client-visible 503 — the retryable status well-behaved
            # clients already handle — and a faults_injected_total tick.
            try:
                _maybe_fire_fault("serve.handler")
            except InjectedFault as e:
                self._send(503, {"error": str(e)})
                return
            # Distributed tracing (obs/tracing.py): honor the router's
            # inbound traceparent (NEVER mint over it — the trace-
            # hygiene analyze pass enforces this), else start a root.
            # The http span becomes the replica-side tree root; the
            # scheduler parents the request's queue/prefill/decode/
            # stream phase spans under it; the tail-based retention
            # decision runs when the request ends, below.
            ctx = tracing.continue_or_start(
                self.headers.get("traceparent"))
            t0 = time.monotonic()
            try:
                with tracing.activate(ctx):
                    # full path in the name: '/v1/completions' and
                    # '/v1/chat/completions' must be distinct histogram
                    # series
                    with span("http." + self.path.strip("/")
                              .replace("/", "."), path=self.path):
                        self._handle_post()
            finally:
                # finally: a client that disconnects mid-write raises
                # OSError out of _handle_post's response send — the
                # retention decision (often for an already-flagged 504)
                # must still run
                tracing.get_tracer().finish(
                    ctx.trace_id, dur_s=time.monotonic() - t0)

        def _handle_post(self):
            chat = self.path == "/v1/chat/completions"
            # weight version at ADMIT time: a request straddling a live
            # swap completes at the version it was admitted under — the
            # response says which (stale-version completions are
            # observable, never errors; docs/online_training.md)
            weights = getattr(service, "weights", None)
            admit_version = (weights.version if weights is not None
                             else None)
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                if chat:
                    # OpenAI chat is STATELESS (full history per call) —
                    # the resident-KV session/prefix machinery belongs to
                    # the completions endpoint.
                    if any(k in req for k in ("keep", "session", "prefix")):
                        raise ValueError(
                            "chat/completions is stateless (full messages "
                            "per call); keep/session/prefix live on "
                            "/v1/completions")
                    prompt = render_chat(req["messages"], service.tok)
                else:
                    prompt = str(req["prompt"])
                if self.path == "/v1/preload":
                    self._send(200, {"session": service.preload(prompt)})
                    return
                max_tokens = int(req.get("max_tokens",
                                         service.max_new_default))
                temperature = float(req.get("temperature", 0.0))
                # per-request wall-clock budget (serving_plane deadlines:
                # expiry cancels in the batcher and answers 504; the
                # server's --deadline-default/--deadline-max knobs apply)
                deadline_s = req.get("deadline_s")
                deadline_s = (float(deadline_s)
                              if deadline_s is not None else None)
                keep = bool(req.get("keep", False))
                session = req.get("session")
                session = int(session) if session is not None else None
                prefix = req.get("prefix")
                prefix = int(prefix) if prefix is not None else None
                stop = req.get("stop")
                if stop is not None:
                    if isinstance(stop, str):
                        stop = [stop]
                    stop = [str(x) for x in stop if str(x)]
                penalties = {
                    k: float(req[k])
                    for k in ("repetition_penalty", "presence_penalty",
                              "frequency_penalty", "top_p", "min_p")
                    if k in req
                }
                if "seed" in req and req["seed"] is not None:
                    # OpenAI `seed`: reproducible sampling independent of
                    # batch composition (per-row key chain in serving)
                    penalties["seed"] = int(req["seed"])
                if "logit_bias" in req:
                    # OpenAI convention: string token-id keys
                    penalties["logit_bias"] = {
                        int(k): float(v)
                        for k, v in dict(req["logit_bias"]).items()}
                n = int(req.get("n", 1))
                if n > 1:
                    if (req.get("stream") or keep or session is not None
                            or prefix is not None or stop):
                        raise ValueError(
                            "n > 1 composes with logprobs only (not "
                            "stream/keep/session/prefix/stop)")
                    out = service.complete_n(
                        prompt, max_tokens, temperature, n,
                        logprobs=bool(req.get("logprobs", False)),
                        penalties=penalties, deadline_s=deadline_s)
                    resp = _chat_response(out) if chat else out
                    if admit_version is not None:
                        resp["weight_version"] = admit_version
                    self._send(200, resp)
                    return
                if req.get("stream"):
                    if stop and keep:
                        raise ValueError(
                            "stop with keep is unsupported (a "
                            "stop-canceled request parks no session)")
                    # eager submit: validation errors raise BEFORE any
                    # headers go out, so they get a clean 400/503
                    uid, n_prompt, chunks = service.stream(
                        prompt, max_tokens, temperature, keep=keep,
                        session=session, prefix=prefix,
                        penalties=penalties, deadline_s=deadline_s)
                    self._stream_sse(uid, chunks, stop=stop,
                                     n_prompt=n_prompt, chat=chat)
                    return
                out = service.complete(prompt, max_tokens, temperature,
                                       keep=keep, session=session,
                                       prefix=prefix, stop=stop,
                                       logprobs=bool(
                                           req.get("logprobs", False)),
                                       penalties=penalties,
                                       deadline_s=deadline_s)
                resp = _chat_response(out) if chat else out
                if admit_version is not None:
                    resp["weight_version"] = admit_version
                self._send(200, resp)
            except (KeyError, ValueError, TypeError) as e:
                self._send(400, {"error": f"{e.args[0] if e.args else e}"})
            except OverloadShed as e:
                # load shedding: the admission controller refused the
                # queue slot — 429 with the standard back-off header;
                # the body repeats it so relays (serve_router) can
                # reconstruct the header they cannot see
                self._send(429, {"error": str(e),
                                 "retry_after_s": int(e.retry_after_s)},
                           headers={"Retry-After":
                                    str(int(e.retry_after_s))})
            except DeadlineExceeded as e:
                tracing.flag_current("deadline")
                self._send(504, {"error": str(e)})
            except (TimeoutError, RuntimeError) as e:
                # RuntimeError: scheduler dead OR no slot for preload
                tracing.flag_current("error")
                self._send(503, {"error": str(e)})

        def _stream_sse(self, uid, chunks, stop=None, n_prompt=0,
                        chat=False):
            """Server-sent events: one `data:` chunk per decode tick with
            the TEXT DELTA. Deltas come from re-decoding ALL tokens so
            far and holding back trailing replacement chars (an
            incomplete multi-byte sequence decodes to U+FFFD until its
            continuation bytes arrive — emitting it early would corrupt
            the stream); held-back chars flush at completion, when
            genuinely-invalid bytes are known to be final. Ends with a
            finish_reason chunk then `data: [DONE]`. Mid-stream errors
            become an SSE `error` event (the 200 already went out);
            client disconnects abandon the request in the batcher.
            """
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.end_headers()  # close-delimited body (HTTP/1.0 default)

            def emit(obj):
                if chat and ("delta" in obj or "finish_reason" in obj):
                    # OpenAI chat.completion.chunk shape; error events
                    # pass through untranslated.
                    obj = {
                        "object": "chat.completion.chunk",
                        "choices": [{
                            "index": 0,
                            "delta": ({"content": obj["delta"]}
                                      if obj.get("delta") else {}),
                            "finish_reason": obj.get("finish_reason"),
                        }],
                        **({"usage": obj["usage"]}
                           if "usage" in obj else {}),
                    }
                self.wfile.write(f"data: {json.dumps(obj)}\n\n".encode())
                self.wfile.flush()

            acc: list[int] = []
            sent_text = ""
            stopped = False
            undelivered = None  # consumed completion not yet sent
            try:
                for toks, comp in chunks:
                    if not stopped and toks:
                        acc.extend(toks)
                        trimmed = trim_at_eos(acc, service.tok.eos_id)
                        stopped = len(trimmed) < len(acc)
                        acc = trimmed
                        text = service.tok.decode(acc)
                        if stop:
                            hit = _find_stop(text, stop)
                            if hit is not None:
                                # cancel on-device work; emit up to the
                                # match and finish with reason "stop"
                                service.cancel_stream(uid)
                                cut = text[: hit]
                                if len(cut) > len(sent_text):
                                    emit({"delta": cut[len(sent_text):]})
                                emit({"delta": "",
                                      "finish_reason": "stop",
                                      "session": None,
                                      "usage": {
                                          "prompt_tokens": n_prompt,
                                          "completion_tokens": len(acc)}})
                                break
                        stable = (text if stopped
                                  else text.rstrip("\ufffd"))
                        if stop:
                            # hold back any tail that could still grow
                            # into a stop match next tick
                            h = _stop_holdback(stable, stop)
                            stable = stable[: len(stable) - h]
                        if len(stable) > len(sent_text):
                            emit({"delta": stable[len(sent_text):]})
                            sent_text = stable
                    if comp is not None:
                        final = service.tok.decode(acc)
                        reason = comp.finish_reason
                        if stop:
                            hit = _find_stop(final, stop)
                            if hit is not None:
                                final, reason = final[: hit], "stop"
                        tail = final[len(sent_text):]
                        undelivered = comp  # until the session goes out
                        emit({"delta": tail,
                              "finish_reason": reason,
                              "session": comp.session,
                              "usage": {
                                  "prompt_tokens": len(comp.prompt),
                                  "completion_tokens": len(comp.tokens)}})
                        undelivered = None
                self.wfile.write(b"data: [DONE]\n\n")
                self.wfile.flush()
            except OSError:  # client went away mid-stream
                service.abandon_stream(uid, landed=undelivered)
            except (TimeoutError, RuntimeError) as e:
                try:
                    emit({"error": str(e)})
                except OSError:
                    service.abandon_stream(uid)

    return Handler


def build_plane(args) -> ReliabilityPlane:
    """ReliabilityPlane from the CLI knobs (docs/serving_reliability.md
    has the full table). The tail-latency monitor is always armed
    (journal-only); profiler captures engage with --profile-on-tail."""
    monitor = None
    if args.tail_sigma > 0:
        monitor = TailLatencyMonitor(
            sigma=args.tail_sigma,
            profiler=(_serving_profiler() if args.profile_on_tail
                      else None),
            capture_seconds=args.tail_capture_seconds,
            cooldown_s=args.tail_cooldown)
    return ReliabilityPlane(
        max_queue_depth=args.max_queue_depth,
        shed_ttft_s=args.shed_ttft,
        deadline_default_s=args.deadline_default,
        deadline_max_s=args.deadline_max,
        slots=args.slots, monitor=monitor)


def build_service(args) -> BatcherService:
    if args.fake_backend:
        # Deterministic pure-Python token mill (serving_plane/testing.py)
        # — the reliability drills' and slo_soak's backend: boots in
        # import time, decode pace set by --fake-step-delay.
        from pytorch_distributed_train_tpu.serving_plane.testing import (
            FakeByteTok,
            FakeTokenBatcher,
        )

        batcher = FakeTokenBatcher(slots=args.slots,
                                   step_delay_s=args.fake_step_delay)
        return BatcherService(batcher, FakeByteTok(),
                              max_new_default=args.max_new_default,
                              plane=build_plane(args))
    import jax

    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.data.text import load_tokenizer
    from pytorch_distributed_train_tpu.serving import (
        ContinuousBatcher,
        PagedContinuousBatcher,
        Seq2SeqContinuousBatcher,
        load_params_for_serving,
    )

    from pytorch_distributed_train_tpu.utils import compile_cache

    compile_cache.enable()  # first thing: everything below compiles
    cfg = get_preset(args.config)
    cfg.apply_overrides(args.set)
    tok = load_tokenizer(args.tokenizer)
    params = load_params_for_serving(cfg, args.safetensors, args.quantize)
    if cfg.model.name.startswith("t5"):
        cls, extra = Seq2SeqContinuousBatcher, {}
    else:
        extra = {"auto_prefix_min": args.auto_prefix_min,
                 "spec_k": args.spec_k,
                 "spec_ngram": args.spec_ngram}
        if args.page_size > 0:
            cls = PagedContinuousBatcher
            extra["page_size"] = args.page_size
            extra["page_blocks"] = args.page_blocks
        else:
            cls = ContinuousBatcher
    batcher = cls(cfg.model, cfg.precision, params, slots=args.slots,
                  top_k=args.top_k, top_p=args.top_p, min_p=args.min_p,
                  rng=jax.random.PRNGKey(args.seed), **extra)
    service = BatcherService(batcher, tok,
                             max_new_default=args.max_new_default,
                             plane=build_plane(args))
    service.weight_applier = _make_weight_applier(batcher)
    return service


def _make_weight_applier(batcher):
    """Weight-swap placement for a real model backend: published leaves
    (the trainer's ``{"params": ...}`` savable, global flatten order) →
    device arrays in THIS batcher's param shardings → a cheap apply fn
    the scheduler flips between quanta. None on any shape/dtype
    mismatch (e.g. a --quantize serving tree vs fp32 trainer params):
    the swap rejects instead of serving a half-cast model."""

    def prepare(leaves, header):
        from pytorch_distributed_train_tpu.online import (
            publisher as pub_lib,
        )

        placed = pub_lib.place_leaves({"params": batcher.params}, leaves)
        if placed is None:
            return None

        def apply():
            batcher.params = placed["params"]

        return apply

    return prepare


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default="llama2_7b")
    p.add_argument("--set", action="append", default=[], metavar="KEY=VALUE")
    p.add_argument("--safetensors", default="",
                   help="model weights (required unless --fake-backend)")
    p.add_argument("--tokenizer", default="",
                   help="local HF tokenizer dir; empty → byte tokenizer")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=0.0)
    p.add_argument("--min-p", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-new-default", type=int, default=64)
    p.add_argument("--auto-prefix-min", type=int, default=0,
                   help="auto-fork completions from any PRELOADED "
                        "template of >= N tokens that prefixes the "
                        "prompt (0 = off); explicit prefix=/session= "
                        "always win")
    p.add_argument("--spec-k", type=int, default=0,
                   help="prompt-lookup SPECULATIVE serving: verify K "
                        "n-gram proposals per row per step (0 = off; "
                        "composes with penalties/logit_bias — the "
                        "penalized accept kernel preserves the lockstep "
                        "law)")
    p.add_argument("--spec-ngram", type=int, default=3,
                   help="with --spec-k: n-gram length for the lookup")
    p.add_argument("--page-size", type=int, default=0,
                   help="PAGED KV cache: tokens per block (0 = dense "
                        "per-slot reservation). Resident KV then scales "
                        "with actual lengths; forks share prefix blocks "
                        "copy-on-write (llama family)")
    p.add_argument("--page-blocks", type=int, default=0,
                   help="with --page-size: pool size in blocks (0 = "
                        "dense-equivalent slots*ceil(max_seq_len/"
                        "page_size))")
    p.add_argument("--quantize", default="", choices=["", "int8", "int4"])
    p.add_argument("--drain-grace", type=float, default=30.0,
                   help="seconds SIGTERM waits for in-flight requests "
                        "before shutting down (graceful drain; size "
                        "below the scheduler's kill grace)")
    # ---- serving reliability plane (docs/serving_reliability.md) ----
    p.add_argument("--max-queue-depth", type=int, default=0,
                   help="admission control: shed (429 + Retry-After) "
                        "once this many requests wait for a slot "
                        "(0 = unbounded)")
    p.add_argument("--shed-ttft", type=float, default=0.0,
                   help="admission control: shed once the estimated "
                        "TTFT for a new request exceeds this many "
                        "seconds (0 = off)")
    p.add_argument("--deadline-default", type=float, default=0.0,
                   help="default per-request wall-clock budget in "
                        "seconds; expiry cancels the request in the "
                        "batcher and answers 504 (0 = no default; "
                        "requests may still send deadline_s)")
    p.add_argument("--deadline-max", type=float, default=0.0,
                   help="cap on any client-requested deadline_s "
                        "(0 = uncapped)")
    p.add_argument("--tail-sigma", type=float, default=6.0,
                   help="tail-latency anomaly detector: median+MAD "
                        "sigma on TTFT / inter-token series "
                        "(0 = detector off)")
    p.add_argument("--tail-cooldown", type=float, default=60.0,
                   help="seconds between anomaly-triggered profiler "
                        "captures")
    p.add_argument("--tail-capture-seconds", type=float, default=2.0,
                   help="length of an anomaly-triggered capture")
    p.add_argument("--profile-on-tail", action="store_true",
                   help="fire the managed profiler on tail-latency "
                        "anomalies (anomalies journal regardless)")
    # ---- distributed request tracing (obs/tracing.py) ----
    p.add_argument("--trace-dir", default="",
                   help="retained-trace JSONL directory (default "
                        "$PDTT_TRACE_DIR, else a traces/ sibling of "
                        "the event journal; empty + no env = traces "
                        "counted but not spilled)")
    p.add_argument("--trace-sample-pct", type=float, default=None,
                   help="random baseline %% of traces retained "
                        "(default $PDTT_TRACE_SAMPLE_PCT or 0)")
    p.add_argument("--trace-keep-slow-ms", type=float, default=None,
                   help="retain any request trace slower than this "
                        "(tail-based sampling; default "
                        "$PDTT_TRACE_KEEP_SLOW_MS or 250)")
    p.add_argument("--weight-version", default="",
                   help="correlation tag stamped on every span/trace "
                        "(default: safetensors basename, or 'fake') — "
                        "an online weight swap updates it, so ROADMAP-4 "
                        "is traceable day one")
    p.add_argument("--advertise", action="store_true",
                   help="register host:port with the elastic launcher "
                        "store so tools/serve_router.py discovers this "
                        "replica (needs TPUSTORE_ADDR)")
    p.add_argument("--fake-backend", action="store_true",
                   help="serve a deterministic fake token batcher "
                        "(tests, slo_soak, router drills — no model)")
    p.add_argument("--fake-step-delay", type=float, default=0.0,
                   help="with --fake-backend: seconds per decode step")
    args = p.parse_args(argv)
    if not args.safetensors and not args.fake_backend:
        p.error("--safetensors is required (or pass --fake-backend)")

    tracing.configure(args.trace_dir or tracing.default_dir(),
                      sample_pct=args.trace_sample_pct,
                      keep_slow_ms=args.trace_keep_slow_ms)
    boot_version = args.weight_version or (
        os.path.basename(args.safetensors) if args.safetensors
        else "fake")
    spans_lib.set_correlation_tags(
        weight_version=boot_version,
        gen=os.environ.get("RESTART_GENERATION", "0"))
    try:
        service = build_service(args)
    except (KeyError, ValueError, FileNotFoundError, OSError) as e:
        print(f"serve_http: error: {e.args[0] if e.args else e}",
              file=sys.stderr)
        return 2
    # --weight-version only SEEDS the mutable weight state: a live swap
    # (/admin/weights) advances it, and /healthz + span tags follow
    service.weights = WeightState(version=boot_version)
    server = ThreadingHTTPServer((args.host, args.port), None)
    drain = GracefulDrain(server, service, grace_s=args.drain_grace)
    server.RequestHandlerClass = make_handler(service, drain)
    drain.install()
    adv_store, adv_idx = None, -1
    if args.advertise:
        from pytorch_distributed_train_tpu import store_plane
        from pytorch_distributed_train_tpu.elastic import (
            publish_obs_endpoint,
            publish_replica,
            routable_host,
        )

        # resilient wrapper (store_plane): the publish and the exit
        # tombstone get bounded timeouts + retries instead of wedging
        # startup/shutdown behind a slow launcher store
        store = store_plane.resilient_worker_store(name="serve-advertise")
        if store is None:
            print("serve_http: --advertise ignored (no TPUSTORE_ADDR)",
                  flush=True)
        else:
            # a wildcard bind is unconnectable from peers: advertise a
            # routable address instead
            addr = (f"{routable_host(args.host)}:"
                    f"{server.server_address[1]}")
            idx = publish_replica(store, addr)
            adv_store, adv_idx = store, idx
            # ... and the same address into the obs-endpoint registry,
            # so the fleet collector scrapes this replica's /metrics +
            # /healthz without static config (docs/observability.md
            # "Fleet health plane").
            publish_obs_endpoint(store, "serving", addr)
            print(f"serve_http: advertised as replica {idx} ({addr})",
                  flush=True)
    print(f"serving on http://{args.host}:{server.server_address[1]} "
          f"(slots={args.slots})", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        service.shutdown()  # idempotent: the drain path already did this
        if adv_store is not None:
            # clean exit (drain completed or ^C): tombstone the registry
            # slot so discovery stops returning this address forever — a
            # crash skips this, and the prober handles that stale entry
            from pytorch_distributed_train_tpu.elastic import (
                tombstone_replica,
            )

            tombstone_replica(adv_store, adv_idx)
    return 0


if __name__ == "__main__":
    sys.exit(main())
