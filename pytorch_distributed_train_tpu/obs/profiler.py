"""Managed profiler plane: bounded capture windows, opened ON TRIGGER.

The legacy profiler window (``obs.profile_start_step`` /
``obs.profile_num_steps``) is a fixed manual aperture: the operator
guesses a step before launch, and the window is never open at the
moment an anomaly actually fires. This plane makes the profiler a
managed resource instead:

- **bounded windows** — every capture is N steps (``jax.profiler``
  start/stop around the step loop) into its own artifact directory
  under ``obs.profile_dir``, auto-summarized through the
  utils/xplane.py top-ops report and journaled (obs/events.py).
- **triggers** — a capture can be requested
    * on cadence (``obs.profile_every_steps``),
    * on demand: a trigger FILE (touch ``<run>/PROFILE``) or the
      metrics sidecar's ``POST /profile`` route (obs/exposition.py) /
      tools/serve_http.py's ``POST /profile``,
    * cross-host-coordinated: under tpurun the request is published on
      the launcher worker_store and every host captures the SAME step
      window (a one-host profile of a collective stall blames the
      wrong thing),
    * automatically by anomaly hooks: sentinel loss-spike, cross-host
      straggler blame, and a rolling median+MAD step-time /
      input-stall regression detector (sentinel/numeric.py math) —
      gated by ``obs.profile_on_anomaly`` + a cooldown so a bad hour
      can't fill the disk.
- **retention** — completed captures form a ring
  (``obs.profile_ring``): oldest ``capture_*`` directories are evicted
  once the ring is full, so triggered profiling can run unattended.

The backend is injectable (``backend=``): tests drive every trigger
path deterministically on the CPU mesh with a fake capture object; the
default lazily wraps ``jax.profiler`` (no jax at module scope — the
obs/ package contract).

The legacy window keeps working as a shim: ``profile_num_steps > 0``
pre-queues one capture at ``profile_start_step`` writing directly into
``obs.profile_dir`` (old output layout, exempt from the ring).
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import shutil
import threading
import time

from pytorch_distributed_train_tpu.obs import events as events_lib
from pytorch_distributed_train_tpu.obs.registry import get_registry
from pytorch_distributed_train_tpu.sentinel.numeric import SpikeDetector

# launcher-store key all hosts poll for coordinated capture requests
REQUEST_KEY = "profiler/request"


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class JaxProfilerBackend:
    """The real thing: ``jax.profiler`` trace sessions."""

    def start(self, logdir: str) -> None:
        import jax

        os.makedirs(logdir, exist_ok=True)
        jax.profiler.start_trace(logdir)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()


@dataclasses.dataclass
class CaptureRequest:
    """One requested window. ``start_step`` -1 = start immediately
    (time-bounded ad-hoc captures from HTTP surfaces)."""

    id: str
    reason: str
    start_step: int
    window: int
    logdir: str = ""  # "" → ring-managed capture_* dir
    in_ring: bool = True

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, raw: str) -> "CaptureRequest":
        d = json.loads(raw)
        return cls(**{f.name: d[f.name]
                      for f in dataclasses.fields(cls) if f.name in d})


def straggler_blame(summary: dict, ratio: float) -> int | None:
    """Pure trigger predicate over the cluster aggregate
    (obs/cluster.py summarize output): the max host is BLAMED when its
    step-time p50 exceeds ``ratio`` x the cluster median. Returns the
    blamed host id or None; 0 disables."""
    if not ratio:
        return None
    med = summary.get("step_time_p50_med")
    mx = summary.get("step_time_p50_max")
    if med is None or mx is None or med <= 0:
        return None
    if mx >= ratio * med:
        return int(summary.get("step_time_p50_max_host", -1))
    return None


class ManagedProfiler:
    """Step-loop-driven capture state machine + trigger plumbing.

    The trainer calls ``on_step(step)`` once per loop iteration (cheap
    when dormant: one attr check, one stat) and feeds the anomaly
    detectors (``observe_step_time`` / ``observe_stall_pct``); every
    other surface funnels into ``request_capture``.
    """

    def __init__(self, obs_cfg, run_dir: str, *, backend=None,
                 store_factory=None, rank: int | None = None,
                 world: int | None = None):
        self.cfg = obs_cfg
        self.run_dir = run_dir
        self.backend = backend if backend is not None else JaxProfilerBackend()
        self.rank = rank if rank is not None else _env_int("PROCESS_ID", 0)
        self.world = world if world is not None else _env_int(
            "NUM_PROCESSES", 1)
        self.profile_dir = obs_cfg.profile_dir or os.path.join(
            run_dir, "profiles")
        self.trigger_file = obs_cfg.profile_trigger_file or os.path.join(
            run_dir, "PROFILE")
        self.window = max(1, int(getattr(obs_cfg, "profile_window_steps", 5)))
        self._lock = threading.Lock()
        self._pending: CaptureRequest | None = None
        self._active = None  # (request, started_step, logdir, t0)
        self._step = 0
        self._req_n = 0
        self._seen_req_id: str | None = None
        self._last_auto_step: int | None = None
        self._stop = threading.Event()
        self._watch_thread: threading.Thread | None = None
        self._timer: threading.Timer | None = None
        self._factory = store_factory
        # median+MAD regression detectors (the sentinel loss-spike math
        # pointed at wall-clock health): step time per step, input-stall
        # % per log window. Healthy-only windows, same rationale.
        self._dt_det = SpikeDetector(
            window=getattr(obs_cfg, "profile_regress_window", 64),
            sigma=getattr(obs_cfg, "profile_regress_sigma", 8.0),
            min_samples=getattr(obs_cfg, "profile_regress_min_samples", 16),
            min_rel=getattr(obs_cfg, "profile_regress_min_rel", 0.5))
        self._stall_det = SpikeDetector(
            window=getattr(obs_cfg, "profile_regress_window", 64),
            sigma=getattr(obs_cfg, "profile_regress_sigma", 8.0),
            min_samples=max(
                4, getattr(obs_cfg, "profile_regress_min_samples", 16) // 4),
            min_rel=getattr(obs_cfg, "profile_regress_min_rel", 0.5))

    # ----------------------------------------------------------- lifecycle
    def start(self) -> None:
        """Arm the plane: queue the legacy-window shim and (under a
        launcher store) start the coordinated-request watcher."""
        if getattr(self.cfg, "profile_num_steps", 0) > 0:
            # Legacy obs.profile_* shim: same window, same output root
            # (no capture_* subdir, never ring-evicted).
            self._adopt(CaptureRequest(
                id=self._new_id("legacy"), reason="legacy",
                start_step=int(self.cfg.profile_start_step),
                window=int(self.cfg.profile_num_steps),
                logdir=self.profile_dir, in_ring=False))
        store = self._open_store()
        if store is None:
            return
        try:  # a stale request from a previous life must not re-fire
            self._seen_req_id = CaptureRequest.from_json(
                store.get(REQUEST_KEY, timeout_ms=1).decode()).id
        except Exception:
            self._seen_req_id = None
        self._watch_thread = threading.Thread(
            target=self._watch, args=(store,), daemon=True,
            name="profiler-request-watch")
        self._watch_thread.start()

    def finish(self, step: int | None = None) -> None:
        """Close an open window (fit() ending mid-capture) and stop the
        watcher. Idempotent."""
        self._stop.set()
        if self._timer is not None:
            self._timer.cancel()
        with self._lock:
            active = self._active is not None
        if active:
            self._stop_capture(self._step if step is None else step)
        t = self._watch_thread
        if t is not None:
            t.join(timeout=2.0)
            self._watch_thread = None

    # -------------------------------------------------------------- store
    def _open_store(self):
        """A ResilientStore over the configured factory (store_plane:
        bounded ops + retry + health scoring), or None when no store
        is configured — a store-less run must not feed the health
        machine phantom failures from a 5 Hz watcher. The probe client
        is handed to the wrapper as its first connection, not closed
        and re-dialed."""
        factory = self._factory
        if factory is None:
            from pytorch_distributed_train_tpu.elastic import worker_store

            factory = worker_store
        try:
            probe = factory()
        except Exception:
            return None
        if probe is None:
            return None
        first = [probe]

        def _fac():
            if first:
                return first.pop()
            return factory()

        from pytorch_distributed_train_tpu import store_plane

        return store_plane.ResilientStore(_fac, name="profiler")

    def _watch(self, store) -> None:
        """Poll the launcher store for coordinated capture requests —
        every host (including the requester) adopts the same window."""
        try:
            while not self._stop.wait(0.2):
                try:
                    raw = store.get(REQUEST_KEY, timeout_ms=1)
                except TimeoutError:
                    continue  # no request published yet
                except OSError:
                    continue  # store degraded: ResilientStore scored
                    # it; keep watching — the outage ends, we resume
                try:
                    req = CaptureRequest.from_json(raw.decode())
                except (ValueError, TypeError, KeyError):
                    continue
                if req.id == self._seen_req_id:
                    continue
                self._seen_req_id = req.id
                self._adopt(req)
        except Exception:
            pass  # store gone (teardown): the plane goes dark
        finally:
            try:
                store.close()
            except Exception:
                pass

    # ----------------------------------------------------------- requests
    def _new_id(self, reason: str) -> str:
        self._req_n += 1
        return f"{self.rank}-{self._req_n}-{reason}"

    def request_capture(self, reason: str, *, start_step: int | None = None,
                        window: int | None = None,
                        coordinate: bool = True) -> CaptureRequest:
        """Request one window. With a launcher store and
        ``coordinate=True`` the request is PUBLISHED so every host
        captures the same steps; otherwise it is adopted locally.
        ``start_step`` defaults a couple of steps ahead so remote hosts
        have time to adopt before the window opens."""
        if start_step is None:
            start_step = self._step + 2
        req = CaptureRequest(
            id=self._new_id(reason), reason=reason,
            start_step=int(start_step),
            window=int(window or self.window))
        store = self._open_store() if coordinate else None
        if store is not None:
            try:
                store.set(REQUEST_KEY, req.to_json().encode())
            except Exception:
                self._adopt(req)  # store flaked: capture locally at least
            finally:
                try:
                    store.close()
                except Exception:
                    pass
        else:
            self._adopt(req)
        return req

    def _adopt(self, req: CaptureRequest) -> None:
        with self._lock:
            if self._active is not None or self._pending is not None:
                return  # one window at a time; overlapping asks collapse
            self._pending = req

    # ---------------------------------------------------------- step loop
    def on_step(self, step: int) -> None:
        """Drive the window state machine at a step boundary."""
        self._step = step
        with self._lock:
            active, pending = self._active, self._pending
        if active is not None:
            req, started, _, _ = active
            # ad-hoc (time-bounded) windows are owned by their timer,
            # not the step counter — start_step -1 marks them
            if req.start_step >= 0 and step >= started + req.window:
                self._stop_capture(step)
            return
        if os.path.exists(self.trigger_file):
            try:
                os.remove(self.trigger_file)
            except OSError:
                pass  # another host on a shared FS won the race
            else:
                # No explicit start_step: the default few-step lead is
                # what lets REMOTE hosts adopt the store-published
                # request before the window opens, so all hosts capture
                # the same steps.
                self.request_capture("trigger_file")
                with self._lock:
                    pending = self._pending
        every = getattr(self.cfg, "profile_every_steps", 0)
        if pending is None and every and step > 0 and step % every == 0:
            # cadence: every host computes the same boundary — aligned
            # by construction, no store round-trip needed
            self.request_capture("cadence", start_step=step,
                                 coordinate=False)
            with self._lock:
                pending = self._pending
        if pending is not None and step >= pending.start_step:
            self._start_capture(pending, step)

    # ----------------------------------------------------------- anomalies
    def observe_step_time(self, dt_s: float, step: int) -> None:
        """Feed one meter tick to the step-time regression detector."""
        with self._lock:
            if self._active is not None:
                return  # profiler overhead must not poison the baseline
        if self._dt_det.is_spike(dt_s):
            self.anomaly("step_time_regression", step,
                         dt_ms=round(dt_s * 1e3, 3))
            # Re-baseline: unlike the sentinel loss detector (whose
            # streak is bounded by the rewind), nothing recovers a
            # PERSISTENT step-time shift — without a reset it would
            # journal one anomaly per step forever. A fresh window
            # adopts the new regime within min_samples ticks and
            # bounds the event rate to ~1 per min_samples steps.
            self._dt_det.reset()
        else:
            self._dt_det.add(dt_s)

    def observe_stall_pct(self, pct: float, step: int) -> None:
        """Feed one log window's input-stall %% to its detector. An
        absolute floor (``profile_stall_min_pct``) keeps a near-zero
        baseline from flagging the first nonzero wait as a regression."""
        floor = getattr(self.cfg, "profile_stall_min_pct", 5.0)
        if pct >= floor and self._stall_det.is_spike(pct):
            self.anomaly("input_stall_regression", step,
                         stall_pct=round(pct, 3))
            self._stall_det.reset()  # same re-baseline as step time
        else:
            self._stall_det.add(pct)

    def anomaly(self, kind: str, step: int, **detail) -> None:
        """An anomaly fired: journal it always; open a capture when
        ``profile_on_anomaly`` and outside the auto-capture cooldown."""
        events_lib.emit("anomaly", kind, step=step, **detail)
        get_registry().counter(
            "profiler_anomalies_total", labels={"kind": kind},
            help="anomaly-detector firings seen by the profiler "
                 "plane").inc()
        if not getattr(self.cfg, "profile_on_anomaly", False):
            return
        with self._lock:
            if self._active is not None or self._pending is not None:
                # a window is already in flight: the request would be
                # collapsed anyway — don't burn the cooldown on it
                return
        cooldown = getattr(self.cfg, "profile_cooldown_steps", 200)
        if (self._last_auto_step is not None
                and step - self._last_auto_step < cooldown):
            return
        self._last_auto_step = step
        self.request_capture(kind, start_step=step + 1)

    # ------------------------------------------------------- capture core
    def _capture_dir(self, req: CaptureRequest) -> str:
        if req.logdir:
            return req.logdir
        if req.start_step >= 0:
            # deterministic across hosts: every host's window lands in
            # the same directory (jax writes per-host files inside)
            name = f"capture_step{req.start_step:08d}_{req.reason}"
        else:
            name = f"capture_adhoc_{req.reason}_{req.id}"
        return os.path.join(self.profile_dir, name)

    def _start_capture(self, req: CaptureRequest, step: int) -> bool:
        """Claim-then-start: the window slot is taken under the lock
        BEFORE the backend call, so concurrent openers (step loop vs a
        POST /profile handler thread) cannot double-start the backend
        or cross-wire each other's stop timers."""
        logdir = self._capture_dir(req)
        with self._lock:
            if self._pending is req:
                self._pending = None
            if self._active is not None:
                return False  # lost the race: one window at a time
            self._active = (req, step, logdir, time.perf_counter())
        try:
            self.backend.start(logdir)
        except Exception as e:
            get_registry().counter(
                "profiler_errors_total",
                help="capture start/stop failures (backend)").inc()
            print(f"[profiler] capture start failed "
                  f"({type(e).__name__}: {e}); dropping request "
                  f"{req.reason}", flush=True)
            with self._lock:
                self._active = None
            return False
        get_registry().counter(
            "profiler_captures_total", labels={"trigger": req.reason},
            help="managed profiler captures by trigger").inc()
        events_lib.emit("profile", "capture_start", step=step,
                        reason=req.reason, dir=logdir, window=req.window)
        print(f"[profiler] capture open at step {step} "
              f"({req.reason}, {req.window} steps) -> {logdir}", flush=True)
        return True

    def _stop_capture(self, step: int, only: CaptureRequest | None = None
                      ) -> None:
        """Close the open window. ``only`` restricts the stop to THAT
        request's window — a stale ad-hoc timer must not kill a capture
        someone else opened after its own ended."""
        with self._lock:
            if self._active is None:
                return
            if only is not None and self._active[0] is not only:
                return
            req, started, logdir, t0 = self._active
            self._active = None
        try:
            self.backend.stop()
        except Exception as e:
            get_registry().counter(
                "profiler_errors_total",
                help="capture start/stop failures (backend)").inc()
            print(f"[profiler] capture stop failed "
                  f"({type(e).__name__}: {e})", flush=True)
        summary = self._summarize(logdir)
        events_lib.emit(
            "profile", "capture_end", step=step, reason=req.reason,
            dir=logdir, steps=step - started,
            wall_s=round(time.perf_counter() - t0, 3),
            summary=summary.splitlines()[:12])
        # Perf attribution (obs/perf.py): op-class split + gauges + one
        # `perf` journal record per capture. Best-effort inside — an
        # environment without the xplane proto still keeps the capture.
        from pytorch_distributed_train_tpu.obs import perf as perf_lib

        mfu = get_registry().get_value("perf_mfu_pct")
        perf_lib.attribute_capture(
            logdir, step=step, mfu_pct=mfu,
            top=getattr(self.cfg, "profile_top_ops", 5))
        print(f"[profiler] capture closed at step {step} ({req.reason}); "
              f"summary:\n{summary}", flush=True)
        if req.in_ring:
            self._gc_ring()

    def _summarize(self, logdir: str) -> str:
        """Best-effort top-ops report over the fresh dump — the capture
        is useful without it (the xplane proto needs the tsl protobuf).
        Where the trainer mapped its compiled step (obs/step_program.py),
        each top operation carries what the map says it is:
        ``%fusion.2973 ... [backward/ffn]``."""
        try:
            from pytorch_distributed_train_tpu.obs import step_program
            from pytorch_distributed_train_tpu.utils import xplane

            program = step_program.latest()
            text = xplane.report(
                logdir, top=getattr(self.cfg, "profile_top_ops", 5),
                describe=program.describe if program is not None else None)
        except Exception as e:
            text = (f"(xplane summary unavailable: "
                    f"{type(e).__name__}: {e})")
        try:
            with open(os.path.join(logdir, "top_ops.txt"), "w") as f:
                f.write(text + "\n")
        except OSError:
            pass
        return text

    def _gc_ring(self) -> None:
        """Keep the newest ``profile_ring`` completed capture dirs."""
        keep = max(1, int(getattr(self.cfg, "profile_ring", 4)))
        dirs = [d for d in glob.glob(
            os.path.join(self.profile_dir, "capture_*"))
            if os.path.isdir(d)]
        dirs.sort(key=lambda d: os.path.getmtime(d), reverse=True)
        for d in dirs[keep:]:
            shutil.rmtree(d, ignore_errors=True)
            get_registry().counter(
                "profiler_ring_evicted_total",
                help="capture directories evicted by ring "
                     "retention").inc()
            events_lib.emit("profile", "ring_evict",
                            dir=os.path.basename(d))

    # ------------------------------------------------------- ad-hoc (HTTP)
    def capture_for_seconds(self, seconds: float,
                            reason: str = "http") -> str | None:
        """Time-bounded capture for step-less surfaces (the serving
        process, a wedged-looking trainer poked over the sidecar).
        Returns the capture dir, or None when a window is already
        open. The stop timer is bound to THIS request (``only=``) so
        concurrent callers can't truncate each other's windows."""
        req = CaptureRequest(id=self._new_id(reason), reason=reason,
                             start_step=-1, window=0)
        if not self._start_capture(req, self._step):
            return None
        self._timer = threading.Timer(
            max(0.05, float(seconds)), self._stop_capture,
            args=(self._step,), kwargs={"only": req})
        self._timer.daemon = True
        self._timer.start()
        return self._capture_dir(req)
