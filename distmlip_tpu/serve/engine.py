"""In-process async inference engine: continuous micro-batching over the
batched multi-structure potential.

``ServeEngine`` is the serving layer the ROADMAP's "heavy traffic" north
star needs on top of PR 3's block-diagonal packing: callers ``submit()``
single structures and get ``concurrent.futures.Future``s back; a
background scheduler thread continuously assembles micro-batches —
bucket-aware (scheduler.plan_batch fills toward the BucketPolicy capacity
ladder), priority/deadline-ordered, with a max-wait timer so a lone
request is never starved — and executes them through ONE shared
``BatchedPotential``. Oversized structures route to a ``DistPotential``
fallback lane instead of blowing up the packed program's shape buckets.

Robustness contract (tests/test_serve.py):

- bounded queue with admission control: ``admission="reject"`` raises
  ``ServeRejected`` when the queue is full, ``"block"`` parks the caller
  until the scheduler frees a slot;
- memory-aware admission: when the shared potential carries an HBM budget
  (``BatchedPotential.hbm_budget_bytes``) and its calibrated bytes model
  estimates that a submitted structure ALONE would exceed it, the request
  is rejected at submit in BOTH admission modes (parking a request that
  can never fit would hang the submitter forever); batch assembly fills
  toward the same budget (``plan_batch(bytes_budget=...)``), so no
  dispatched batch is ever estimated over budget;
- per-request error isolation: a poison structure (non-finite positions,
  or anything that makes the batch raise) fails its OWN Future; the rest
  of the batch returns results and the engine thread survives;
- ``drain()`` flushes everything in flight deterministically and returns
  with the queue empty and every Future resolved; ``close()`` drains by
  default, then joins the scheduler thread; ``extract_pending()`` is the
  fleet router's handoff hook — it reclaims the queued requests (Futures
  UNRESOLVED) for re-dispatch on another replica instead of failing them;
- the scheduler thread can never die: every execution path is wrapped so
  an unexpected failure resolves the affected Futures exceptionally and
  the loop continues.

Telemetry: each dispatched batch emits a ``StepRecord`` (kind
``serve_batch`` / ``serve_fallback``) carrying per-request queue-wait and
latency lists, queue depth, batch occupancy and cumulative reject /
deadline-miss counters — rendered by ``telemetry_report``'s "serving"
section.

Observability (:mod:`distmlip_tpu.obs`): with a hub installed, every
request grows a span tree — ``engine.submit`` root (standalone) or the
router's ambient context (fleet), a retroactive ``engine.queue`` span at
dispatch, a batch-level ``serve.batch`` trace (plan/pack/compile/device
children) LINKED to every member request, and exactly one terminal
``future.resolve`` per request, whatever path it took (dispatch, shed,
over-budget fail, poison isolation, non-draining close). The layer that
OPENED the root closes it: a router-adopted request's terminal is the
router's to emit. Metrics (queue depth, batch occupancy, service
histogram, compiles, rejects/sheds) ride the same points. With no hub
installed each site costs one global read — the disabled hot path is
unchanged.
"""

from __future__ import annotations

import contextlib
import heapq
import itertools
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field

import numpy as np

from ..obs import runtime as obsrt
from ..telemetry import StepRecord
from .scheduler import plan_batch

ADMISSION_MODES = ("reject", "block")


class ServeRejected(RuntimeError):
    """The request was NOT enqueued: queue full under admission="reject",
    or the structure's estimated HBM footprint alone exceeds the batched
    lane's budget (rejected in both admission modes — it can never fit)."""


class EngineClosed(RuntimeError):
    """submit() after close(), or a pending request flushed by a
    non-draining close."""


@dataclass(order=True)
class _Request:
    """One queued request. Heap order: priority, then earliest deadline,
    then submission order (FIFO within a class)."""

    priority: int
    deadline_abs: float      # absolute clock time; +inf = no deadline
    seq: int
    atoms: object = field(compare=False)
    properties: tuple | None = field(compare=False, default=None)
    future: Future = field(compare=False, default_factory=Future)
    t_submit: float = field(compare=False, default=0.0)
    n_atoms: int = field(compare=False, default=0)
    # observability handle (obs.tracing.RequestTrace): the request's span
    # context, carried across the submitter -> scheduler thread hop. When
    # its .root is set the ENGINE owns the trace (standalone submit) and
    # emits the terminal future.resolve; under a FleetRouter the root
    # lives router-side and this holds only the adopted context.
    trace: object = field(compare=False, default=None, repr=False)


@dataclass
class ServeStats:
    """Cumulative engine counters (thread-safe reads: plain ints swapped
    under the engine lock)."""

    submitted: int = 0
    completed: int = 0
    failed: int = 0
    cancelled: int = 0
    rejected: int = 0
    deadline_misses: int = 0
    shed_count: int = 0          # deadline-shed at assembly (never ran)
    batches: int = 0
    fallback_requests: int = 0
    scheduler_errors: int = 0    # isolated loop faults (engine survived)
    # bucket_key -> [batches, sum(batch_occupancy), sum(batch_size)]
    buckets: dict = field(default_factory=dict)

    def note_batch(self, bucket_key: str, occupancy: float, size: int):
        b = self.buckets.setdefault(bucket_key, [0, 0.0, 0])
        b[0] += 1
        b[1] += occupancy
        b[2] += size

    def dominant_bucket(self) -> tuple[str, float] | None:
        """(bucket_key, mean batch-slot occupancy) of the bucket that served
        the most batches — the load test's acceptance metric."""
        if not self.buckets:
            return None
        key = max(self.buckets, key=lambda k: self.buckets[k][0])
        n, occ_sum, _ = self.buckets[key]
        return key, occ_sum / max(n, 1)

    def snapshot(self) -> dict:
        d = {k: v for k, v in vars(self).items() if k != "buckets"}
        d["buckets"] = {k: {"batches": v[0],
                            "mean_batch_occupancy": v[1] / max(v[0], 1),
                            "requests": v[2]}
                        for k, v in self.buckets.items()}
        return d


def _finite_positions(atoms) -> bool:
    pos = np.asarray(atoms.positions)
    return bool(np.isfinite(pos).all())


_NULL_CTX = contextlib.nullcontext()


class ServeEngine:
    """Continuous micro-batching scheduler over a shared BatchedPotential.

    Parameters
    ----------
    potential : BatchedPotential — the shared batched executor. Its Verlet
        cache and compile cache are only touched from the scheduler thread
        (and BatchedPotential.calculate is itself lock-guarded, so a caller
        sharing the potential outside the engine stays safe).
    fallback : optional DistPotential for structures larger than
        ``max_batch_atoms`` — the single-structure (possibly
        halo-partitioned) lane. When the shared ``BatchedPotential`` runs
        on a 2-D mesh and no explicit fallback is given, the engine builds
        the lane AUTOMATICALLY on the SPATIAL sub-axis of that same mesh
        (a ``DistPotential`` over one batch row's spatial devices): small
        requests pack onto the batch axis, oversized ones spatially
        partition across the spatial axis — one mesh, two routes, uniform
        ``last_stats`` telemetry either way. Without a mesh or explicit
        fallback, oversized requests fail their Future with ValueError.
    max_batch : micro-batch slot budget (power of two keeps the packed
        ``batch_size`` bucket stable).
    max_wait_s : max time a request waits for co-batching before the
        scheduler dispatches an underfilled batch (the lone-request
        starvation bound). Measured on ``clock``.
    max_queue : admission bound on queued (not yet dispatched) requests.
    admission : "reject" (raise ServeRejected when full) or "block" (park
        the submitter until space frees).
    max_batch_atoms : per-structure size ceiling for the batched lane;
        larger structures route to ``fallback``. None disables routing.
    window : how deep past the queue head assembly may scan.
    shed_deadlines : deadline-aware LOAD SHEDDING (off by default — the
        historical contract delivers late results and only counts the
        miss). When on, a queued request whose deadline has already
        passed at assembly time — or which PROVABLY cannot be met even
        if dispatched in the very next batch, judged against the
        engine's EWMA batch service time — fails fast with
        ``ServeRejected`` instead of occupying batch slots, so a
        backed-up queue sheds the work nobody will use and the live
        deadlines keep making it. Shed requests count in
        ``stats.shed_count`` (and the ``shed_count`` StepRecord field),
        never in ``deadline_misses``. The service EWMA is measured in
        real seconds; with an injected test clock, seed
        ``_service_ewma`` directly.
    clock : monotonic-seconds callable; tests inject a fake clock so the
        max-wait timer is deterministic (no real sleeps).
    start : spawn the scheduler thread immediately. ``start=False`` lets
        tests stage a queue and then start the engine for deterministic
        assembly.
    """

    def __init__(
        self,
        potential,
        fallback=None,
        max_batch: int = 8,
        max_wait_s: float = 0.02,
        max_queue: int = 256,
        admission: str = "reject",
        max_batch_atoms: int | None = None,
        window: int = 64,
        shed_deadlines: bool = False,
        telemetry=None,
        clock=None,
        start: bool = True,
    ):
        if admission not in ADMISSION_MODES:
            raise ValueError(
                f"admission {admission!r} not in {ADMISSION_MODES}")
        if max_batch < 1 or max_queue < 1:
            raise ValueError("max_batch and max_queue must be >= 1")
        self.potential = potential
        self.fallback = fallback
        self._spatial_lane = None         # lazily built mesh spatial lane
        self._spatial_lane_error = None
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_s)
        self.max_queue = int(max_queue)
        self.admission = admission
        self.max_batch_atoms = (int(max_batch_atoms)
                                if max_batch_atoms is not None else None)
        self.window = int(window)
        self.shed_deadlines = bool(shed_deadlines)
        # EWMA of per-batch service seconds — the fastest a freshly
        # queued request could possibly complete (None until the first
        # dispatch lands; the predictive shed rule stays off until then)
        self._service_ewma: float | None = None
        self._real_clock = clock is None
        self._clock = clock if clock is not None else time.monotonic
        self.telemetry = telemetry
        if telemetry is not None and hasattr(potential, "attach_telemetry"):
            potential.attach_telemetry(telemetry)
        self.stats = ServeStats()
        self._cv = threading.Condition()
        self._pending: list[_Request] = []   # heap
        self._seq = itertools.count()
        self._inflight = 0
        self._draining = 0
        self._closed = False     # submit() gate
        self._closing = False    # scheduler exit signal
        # last time the scheduler completed a dispatch round (or had an
        # empty queue) — the wedge-detection signal health_snapshot serves
        self._last_progress = self._clock()
        self._step = 0
        self._last_plan_attrs: dict | None = None   # obs plan-span attrs
        self._thread: threading.Thread | None = None
        if start:
            self.start()

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        if self._closed:
            raise EngineClosed("engine already closed")
        self._thread = threading.Thread(
            target=self._loop, name="distmlip-serve", daemon=True)
        self._thread.start()

    @property
    def queue_depth(self) -> int:
        with self._cv:
            return len(self._pending)

    @property
    def compile_count(self) -> int:
        return getattr(self.potential, "compile_count", 0)

    def kick(self) -> None:
        """Wake the scheduler immediately (tests use this after advancing a
        fake clock past the max-wait deadline)."""
        with self._cv:
            self._cv.notify_all()

    def extract_pending(self) -> list:
        """Reclaim every NOT-YET-DISPATCHED request for re-dispatch
        elsewhere (the fleet router's drain-and-handoff path).

        Atomically pops the whole queue and returns the ``_Request``
        objects in dispatch (priority/deadline/FIFO) order — each carries
        ``atoms``, ``properties``, ``priority``, ``deadline_abs``,
        ``t_submit`` and its UNRESOLVED ``future``. The engine stops
        accepting new submits (as if closed); in-flight batches still
        complete and resolve their own Futures. Unlike
        ``close(drain=False)``, nothing returned here is failed with
        ``EngineClosed`` — the caller owns re-dispatching (or failing)
        the reclaimed requests, so no submitted Future is ever lost to a
        replica handoff."""
        with self._cv:
            self._closed = True     # no new submits race the handoff
            reqs = []
            while self._pending:
                reqs.append(heapq.heappop(self._pending))
            # blocked admission waiters observe _closed and raise
            self._cv.notify_all()
        return reqs

    @property
    def scheduler_alive(self) -> bool:
        """The scheduler thread exists and is still serving (a dead
        thread strands Futures and blocks drain forever)."""
        t = self._thread
        return t is not None and t.is_alive()

    def health_snapshot(self) -> dict:
        """One consistent health sample for a replica monitor: queue
        depth, in-flight batches, liveness, and how long ago the
        scheduler last made dispatch progress (on the engine clock). A
        wedged engine shows ``queue_depth > 0`` (or in-flight work) with
        an ever-growing ``last_progress_age_s`` while
        ``scheduler_alive`` stays True — visible without touching the
        device."""
        with self._cv:
            return {
                "queue_depth": len(self._pending),
                "inflight": self._inflight,
                "scheduler_alive": self.scheduler_alive,
                "last_progress_age_s": self._clock() - self._last_progress,
                "completed": self.stats.completed,
                "failed": self.stats.failed,
            }

    def drain(self, timeout: float | None = None) -> bool:
        """Flush: dispatch everything queued (bypassing max-wait) and wait
        until the queue is empty and no batch is in flight — i.e. every
        submitted Future is resolved. Returns False on (real-time)
        timeout."""
        with self._cv:
            if self._thread is None:
                # no scheduler to flush the queue: report the truth instead
                # of blocking forever
                return not self._pending
            self._draining += 1
            self._cv.notify_all()
            try:
                return self._cv.wait_for(
                    lambda: not self._pending and self._inflight == 0,
                    timeout=timeout)
            finally:
                self._draining -= 1
                self._cv.notify_all()

    def close(self, drain: bool = True, timeout: float | None = None) -> None:
        """Stop accepting work and shut the scheduler down.

        ``drain=True`` (default) flushes queued work first so every
        accepted Future resolves deterministically; ``drain=False`` fails
        still-queued requests with ``EngineClosed`` (in-flight batches
        still complete). Idempotent."""
        with self._cv:
            if self._closed and self._thread is None:
                return
            self._closed = True      # no new submits
            if self._thread is None:
                # never started: there is no scheduler to flush the queue,
                # so a "graceful" close can only fail what's pending
                drain = False
            if not drain:
                while self._pending:
                    req = heapq.heappop(self._pending)
                    if req.future.set_running_or_notify_cancel():
                        self._trace_terminal(req, "error")
                        req.future.set_exception(EngineClosed(
                            "engine closed before this request was "
                            "dispatched"))
                        self.stats.failed += 1
            self._closing = True
            self._cv.notify_all()
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout)
        # the auto-built spatial lane is engine-owned (unlike an explicit
        # user fallback): release its background-rebuild worker and cached
        # graphs deterministically rather than waiting on GC
        lane, self._spatial_lane = self._spatial_lane, None
        if lane is not None:
            lane.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------

    def submit(self, atoms, properties=None, priority: int = 0,
               deadline: float | None = None) -> Future:
        """Enqueue one structure; returns a Future resolving to the same
        result dict ``calculate`` produces (optionally trimmed to
        ``properties``).

        ``priority``: lower values dispatch first (default 0; negative =
        urgent). ``deadline``: seconds from now (on the engine clock); used
        for earliest-deadline-first ordering within a priority class and
        for deadline-miss accounting — late results are still delivered.
        """
        now = self._clock()
        req = _Request(
            priority=int(priority),
            deadline_abs=(now + float(deadline) if deadline is not None
                          else float("inf")),
            seq=next(self._seq),
            atoms=atoms,
            properties=tuple(properties) if properties is not None else None,
            t_submit=now,
            n_atoms=len(atoms),
        )
        mx = obsrt.metrics()
        with self._cv:
            if self._closed:
                raise EngineClosed("submit() on a closed engine")
            try:
                self._check_hbm_admission(atoms)
            except ServeRejected:
                if mx is not None:
                    mx.counter("distmlip_serve_rejected_total",
                               "admission-rejected requests").inc()
                raise
            if len(self._pending) >= self.max_queue:
                if self.admission == "reject":
                    self.stats.rejected += 1
                    if mx is not None:
                        mx.counter("distmlip_serve_rejected_total",
                                   "admission-rejected requests").inc()
                    raise ServeRejected(
                        f"queue full ({self.max_queue} pending); retry later "
                        f"or construct with admission='block'")
                self._cv.wait_for(
                    lambda: len(self._pending) < self.max_queue
                    or self._closed)
                if self._closed:
                    raise EngineClosed("engine closed while blocked on "
                                       "admission")
            self.stats.submitted += 1
            tr = obsrt.tracer()
            if tr is not None:
                # adopt an ambient (router-owned) request trace, or open
                # a root of our own for standalone submissions
                req.trace = tr.adopt_request()
                if req.trace is None:
                    req.trace = tr.start_request(
                        "engine.submit",
                        attrs={"n_atoms": req.n_atoms,
                               "priority": req.priority})
            heapq.heappush(self._pending, req)
            if mx is not None:
                mx.counter("distmlip_serve_submitted_total",
                           "accepted engine submissions").inc()
                mx.gauge("distmlip_serve_queue_depth",
                         "requests queued, not yet dispatched").set(
                             len(self._pending))
            self._cv.notify_all()
        return req.future

    def _hbm_budget(self) -> int | None:
        """The batched lane's per-device HBM budget (None: no budget)."""
        return getattr(self.potential, "hbm_budget_bytes", None)

    def _check_hbm_admission(self, atoms) -> None:
        """Reject a structure whose MEASURED solo footprint (its own
        calibrated rung) exceeds the batched lane's HBM budget — it
        cannot fit any batch, so parking it (admission="block") would
        hang the submitter forever. An over-budget EXTRAPOLATED estimate
        admits: the planner ships it as a solo probe whose compile
        calibrates the rung (rejecting on guesses could livelock the
        lane after one over-budget calibration elsewhere). Routed
        oversized structures (> max_batch_atoms) are exempt: they ride
        the fallback lane, which this budget does not govern."""
        budget = self._hbm_budget()
        if budget is None:
            return
        n = len(atoms)
        if self.max_batch_atoms is not None and n > self.max_batch_atoms:
            return
        caps = getattr(self.potential, "caps", None)
        exact = getattr(caps, "has_calibrated_rung", None)
        if exact is None or not exact(n):
            return
        est_fn = getattr(self.potential, "estimate_batch_bytes", None)
        est = est_fn(n) if est_fn is not None else None
        if est is not None and est > budget:
            self.stats.rejected += 1
            raise ServeRejected(
                f"structure of {n} atoms is estimated at "
                f"{est / 2**20:.1f} MiB peak — over the batched lane's "
                f"{budget / 2**20:.1f} MiB HBM budget; partition it "
                f"spatially (DistPotential / the engine's oversized "
                f"lane via max_batch_atoms) instead")

    # ------------------------------------------------------------------
    # scheduler loop
    # ------------------------------------------------------------------

    def _wait_timeout(self, oldest_age: float) -> float:
        """How long the scheduler may sleep before re-checking the max-wait
        deadline. On the real clock this is the exact remaining budget; on
        an injected (fake) clock fall back to a short poll so tests stay
        deterministic without mapping fake seconds to real ones."""
        if self._real_clock:
            return max(min(self.max_wait_s - oldest_age, 0.05), 0.001)
        return 0.005

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._closing:
                    self._last_progress = self._clock()  # idle = healthy
                    self._cv.wait(timeout=0.05)
                if not self._pending and self._closing:
                    return
                now = self._clock()
                oldest = min(r.t_submit for r in self._pending)
                ready = (len(self._pending) >= self.max_batch
                         or self._draining > 0 or self._closing
                         or now - oldest >= self.max_wait_s)
                if not ready:
                    self._cv.wait(timeout=self._wait_timeout(now - oldest))
                    continue
                tr = obsrt.tracer()
                t_plan0 = tr.now() if tr is not None else 0.0
                batch, oversized, overbudget, shed = \
                    self._assemble_locked(now)
                plan_win = ((t_plan0, tr.now())
                            if tr is not None else None)
                self._inflight += 1
                self._cv.notify_all()   # admission slots freed
            try:
                self._run_dispatch(batch, oversized, overbudget, shed, now,
                                   plan_win)
            except BaseException:  # noqa: BLE001 - the loop must survive
                self.stats.scheduler_errors += 1
                import traceback
                import warnings

                warnings.warn("serve scheduler dispatch fault (isolated):\n"
                              + traceback.format_exc(), stacklevel=1)
            finally:
                with self._cv:
                    self._inflight -= 1
                    self._last_progress = self._clock()
                    self._cv.notify_all()

    def _provably_late(self, req: _Request, now: float) -> bool:
        """Deadline shedding predicate: the deadline already passed, or —
        given the EWMA batch service time — the request would miss even
        if dispatched in the very next batch (the most optimistic drain
        the queue can offer)."""
        if req.deadline_abs == float("inf"):
            return False
        if req.deadline_abs <= now:
            return True
        ewma = self._service_ewma
        return ewma is not None and req.deadline_abs < now + ewma

    def _note_service(self, service_s: float) -> None:
        """Fold one dispatch's service time into the shedding EWMA."""
        prev = self._service_ewma
        self._service_ewma = (service_s if prev is None
                              else 0.7 * prev + 0.3 * service_s)

    def _assemble_locked(self, now: float):
        """Pop the next micro-batch (plus any oversized requests seen
        while scanning, a head whose solo HBM estimate is over budget,
        and — with ``shed_deadlines`` — requests whose deadline provably
        cannot be met; all failed by the dispatcher, never run). Called
        under the lock; returns ``(batch, oversized, overbudget,
        shed)``."""
        window: list[_Request] = []
        limit = max(self.window, self.max_batch)
        while self._pending and len(window) < limit:
            window.append(heapq.heappop(self._pending))
        oversized, normal, shed = [], [], []
        for r in window:
            if self.shed_deadlines and self._provably_late(r, now):
                shed.append(r)
            elif (self.max_batch_atoms is not None
                    and r.n_atoms > self.max_batch_atoms):
                oversized.append(r)
            else:
                normal.append(r)
        batch: list[_Request] = []
        overbudget: list[_Request] = []
        self._last_plan_attrs = None
        if normal:
            plan = plan_batch([r.n_atoms for r in normal],
                              policy=getattr(self.potential, "caps", None),
                              max_batch=self.max_batch, window=limit,
                              bytes_budget=self._hbm_budget())
            self._last_plan_attrs = plan.span_attrs()
            chosen = set(plan.take)
            for i, r in enumerate(normal):
                if i in chosen:
                    # a head flagged over_budget was admitted BEFORE the
                    # bytes model calibrated (the admission race); it can
                    # never fit a batch — fail it instead of dispatching
                    # an over-budget program
                    (overbudget if plan.over_budget else batch).append(r)
                else:
                    # not picked this round (occupancy rule / slot budget):
                    # keep its queue position for the next batch
                    heapq.heappush(self._pending, r)
        return batch, oversized, overbudget, shed

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------

    def _run_dispatch(self, batch, oversized, overbudget, shed,
                      t_dispatch, plan_win=None) -> None:
        mx = obsrt.metrics()
        for req in shed:
            # outside the lock (done-callbacks run here). Shed requests
            # were healthy at admission and expired in the queue: they
            # count in shed_count, not in failed/deadline_misses
            for r in self._start_requests([req]):
                self.stats.shed_count += 1
                if mx is not None:
                    mx.counter("distmlip_serve_shed_total",
                               "deadline-shed requests").inc()
                why = ("has already passed" if r.deadline_abs <= t_dispatch
                       else "provably cannot be met at the current queue "
                            "drain rate")
                self._trace_terminal(r, "shed")
                r.future.set_exception(ServeRejected(
                    f"deadline shed: the request's deadline {why} (queue "
                    f"wait {t_dispatch - r.t_submit:.3f}s); retry with a "
                    f"looser deadline or more capacity"))
        for req in overbudget:
            # outside the lock: failing a Future runs its done-callbacks.
            # Accounting: this request WAS accepted (it predates the bytes
            # model), so it counts as a failure via _fail — NOT as a
            # submit-time reject (which would double-count it)
            for r in self._start_requests([req]):
                self._fail(r, ServeRejected(
                    f"structure of {r.n_atoms} atoms is estimated over the "
                    f"batched lane's HBM budget (admitted before the bytes "
                    f"model calibrated); partition it spatially instead"))
        for req in oversized:
            self._run_fallback(req, t_dispatch)
        if batch:
            self._run_batch(batch, t_dispatch, plan_win)

    def _start_requests(self, requests) -> list[_Request]:
        """Transition Futures to running; drop the ones a caller already
        cancelled."""
        live = []
        for r in requests:
            if r.future.set_running_or_notify_cancel():
                live.append(r)
            else:
                self.stats.cancelled += 1
                self._trace_terminal(r, "cancelled")
        return live

    def _trace_terminal(self, req: _Request, status: str) -> None:
        """Close an ENGINE-OWNED request trace with its one terminal
        ``future.resolve`` span (no-op for router-owned traces — the
        router closes those when the caller-visible Future resolves)."""
        if req.trace is None:
            return
        tr = obsrt.tracer()
        if tr is not None:
            tr.finish_request(req.trace, status=status)

    def _resolve(self, req: _Request, result: dict, t_done: float) -> None:
        if req.deadline_abs < t_done:
            self.stats.deadline_misses += 1
            fl = obsrt.flight()
            if fl is not None:
                # first deadline miss = incident (rate-limited inside):
                # the flight recorder captures traces + metrics while the
                # regression is still on the wire
                fl.capture("serve deadline miss", attrs={
                    "queue_wait_s": round(t_done - req.t_submit, 6),
                    "n_atoms": req.n_atoms,
                    "deadline_misses": self.stats.deadline_misses})
            mx = obsrt.metrics()
            if mx is not None:
                mx.counter("distmlip_serve_deadline_miss_total",
                           "requests resolved past their deadline").inc()
        if req.properties is not None:
            keep = set(req.properties) | {"energy"}
            result = {k: v for k, v in result.items() if k in keep}
        self.stats.completed += 1
        mx = obsrt.metrics()
        if mx is not None:
            mx.counter("distmlip_serve_completed_total",
                       "requests resolved with a result").inc()
        self._trace_terminal(req, "ok")
        req.future.set_result(result)

    def _fail(self, req: _Request, exc: BaseException) -> None:
        self.stats.failed += 1
        mx = obsrt.metrics()
        if mx is not None:
            mx.counter("distmlip_serve_failed_total",
                       "requests resolved with an explicit error").inc()
        self._trace_terminal(req, "error")
        req.future.set_exception(exc)

    def _oversized_lane(self):
        """The potential serving oversized structures: the explicit
        ``fallback`` if configured, else a lazily built ``DistPotential``
        over the SPATIAL sub-axis of the shared BatchedPotential's mesh
        (one batch row's spatial devices — same chips, spatial route).
        Returns None when neither is available."""
        if self.fallback is not None:
            return self.fallback
        mesh = getattr(self.potential, "mesh", None)
        if mesh is None:
            return None
        if self._spatial_lane is None:
            try:
                from ..calculators.calculator import DistPotential
                from ..parallel import mesh_shape

                pot = self.potential
                _bp, sp = mesh_shape(mesh)
                # the lane mirrors the shared potential's configuration
                # (magmoms, skin cache, threading, telemetry) so the two
                # routes differ only in placement
                self._spatial_lane = DistPotential(
                    pot.model, pot.params,
                    num_partitions=sp,
                    devices=list(np.asarray(mesh.devices).reshape(-1)[:sp]),
                    species_map=getattr(pot, "species_map", None),
                    compute_stress=getattr(pot, "compute_stress", True),
                    compute_magmom=getattr(pot, "compute_magmom", False),
                    skin=getattr(pot, "skin", 0.0),
                    num_threads=getattr(pot, "num_threads", None),
                    kernels=getattr(pot, "kernels", None),
                    telemetry=getattr(pot, "telemetry", None))
                self._spatial_lane_error = None
            except Exception as e:  # noqa: BLE001 - retried next request
                # remember the cause for the failure message but do NOT
                # latch it: a transient build failure (OOM while a batch is
                # resident, backend hiccup) must not disable the lane for
                # the engine's lifetime
                self._spatial_lane_error = e
                return None
        return self._spatial_lane

    def _run_fallback(self, req: _Request, t_dispatch: float) -> None:
        live = self._start_requests([req])
        if not live:
            return
        req = live[0]
        tr = obsrt.tracer()
        t_dev0 = 0.0
        if tr is not None and req.trace is not None:
            # queue wait + device dispatch ride the request's OWN trace
            # (no separate batch trace: the oversized lane is B=1)
            t_dev0 = tr.now()
            tr.emit("engine.queue", parent=req.trace.ctx,
                    t_start=req.trace.t_submit, t_end=t_dev0,
                    attrs={"n_atoms": req.n_atoms, "lane": "oversized"})
        t0 = time.perf_counter()
        try:
            lane = self._oversized_lane()
            if lane is None:
                raise ValueError(
                    f"structure with {req.n_atoms} atoms exceeds "
                    f"max_batch_atoms={self.max_batch_atoms} and no "
                    f"fallback DistPotential (or batched-potential mesh "
                    f"spatial axis) is configured"
                ) from self._spatial_lane_error
            if not _finite_positions(req.atoms):
                raise ValueError("non-finite positions")
            # snapshot last_stats in the same critical section as the
            # call (same rule as _run_batch): a direct caller sharing an
            # explicit fallback potential must not overwrite the stats
            # between this request executing and the engine reading them
            lock = getattr(lane, "_lock", None)
            with lock if lock is not None else _NULL_CTX:
                result = lane.calculate(req.atoms)
                pot_stats = dict(getattr(lane, "last_stats", None) or {})
        except Exception as e:  # noqa: BLE001 - isolate to this request
            self._fail(req, e)
            return
        t_done = self._clock()
        self.stats.fallback_requests += 1
        if tr is not None and req.trace is not None:
            tr.emit("device.dispatch", parent=req.trace.ctx,
                    t_start=t_dev0, t_end=tr.now(),
                    attrs={"lane": "oversized"})
        # deliberately NOT folded into the shedding EWMA: one slow
        # oversized request on the spatial lane would inflate the
        # batched lane's drain estimate and shed healthy deadlines
        self._resolve(req, result, t_done)
        # unified stats emission: the spatial/fallback lane reports the
        # same last_stats surface the batched lane does, so fallback
        # batches no longer bypass graph/occupancy telemetry
        self._emit_record("serve_fallback", [req], t_dispatch, t_done,
                          service_s=time.perf_counter() - t0,
                          pot_stats=pot_stats,
                          trace_ctx=(req.trace.ctx if req.trace is not None
                                     else None))

    def _run_batch(self, batch: list[_Request], t_dispatch: float,
                   plan_win=None) -> None:
        batch = self._start_requests(batch)
        if not batch:
            return
        # cheap poison screen: non-finite positions would feed NaN through
        # the neighbor build; fail those Futures here and keep the rest
        good = []
        for r in batch:
            if _finite_positions(r.atoms):
                good.append(r)
            else:
                self._fail(r, ValueError(
                    "non-finite positions (NaN/inf) in submitted structure"))
        if not good:
            return
        # --- tracing: close each member's queue wait, open the batch
        # trace with span LINKS back to every member request ---
        tr = obsrt.tracer()
        batch_span = None
        if tr is not None:
            t_q = tr.now()
            links = []
            for r in good:
                if r.trace is not None:
                    tr.emit("engine.queue", parent=r.trace.ctx,
                            t_start=r.trace.t_submit, t_end=t_q,
                            attrs={"n_atoms": r.n_atoms})
                    links.append(r.trace.ctx)
            batch_span = tr.begin(
                "serve.batch", new_trace=True, links=links,
                t_start=plan_win[0] if plan_win is not None else t_q,
                attrs={"batch_size": len(good)})
            if plan_win is not None:
                tr.emit("scheduler.plan_batch", parent=batch_span,
                        t_start=plan_win[0], t_end=plan_win[1],
                        attrs=self._last_plan_attrs)
        t0 = time.perf_counter()
        cc_before = self.compile_count
        pot_stats: dict = {}
        pot_timings: dict = {}
        t_calc_end = 0.0
        try:
            # snapshot last_stats in the same critical section as the call:
            # a direct caller sharing the potential (or this lane's own
            # singles retry below) must not overwrite the stats between the
            # batch executing and the engine reading its occupancy
            lock = getattr(self.potential, "_lock", None)
            with lock if lock is not None else _NULL_CTX:
                # ambient batch context: the potential's own record
                # stamps these ids and its TraceAnnotation carries the
                # trace id, lining device timelines up with host spans
                with (tr.use(batch_span) if tr is not None
                      else contextlib.nullcontext()):
                    results = self.potential.calculate(
                        [r.atoms for r in good])
                pot_stats = dict(
                    getattr(self.potential, "last_stats", None) or {})
                pot_timings = dict(
                    getattr(self.potential, "last_timings", None) or {})
            if tr is not None:
                t_calc_end = tr.now()
        except Exception:  # noqa: BLE001 - isolate per request below
            # a batch-level fault (one request's graph build blowing up the
            # pack) is isolated by re-running each request alone: the
            # poison fails its own Future, the rest still get results
            results = None
        if results is None:
            for r in good:
                t_r0 = tr.now() if tr is not None else 0.0
                try:
                    r_result = self.potential.calculate([r.atoms])[0]
                except Exception as e:  # noqa: BLE001
                    exc: BaseException | None = e
                else:
                    exc = None
                if tr is not None and r.trace is not None:
                    tr.emit("device.dispatch", parent=r.trace.ctx,
                            t_start=t_r0, t_end=tr.now(),
                            status="ok" if exc is None else "error",
                            attrs={"retry": True})
                if exc is not None:
                    self._fail(r, exc)
                else:
                    self._resolve(r, r_result, self._clock())
            t_done = self._clock()
        else:
            t_done = self._clock()
            for r, res in zip(good, results):
                self._resolve(r, res, t_done)
        # diffed AFTER any singles retries: a retry's fresh B=1 bucket
        # is a real compile and must keep the compiles counter in step
        # with the compile_count gauge
        compiled = self.compile_count > cc_before
        if tr is not None and batch_span is not None:
            if results is not None and pot_timings.get("total_s"):
                # reconstruct the pack/device phase windows from the
                # potential's own perf_counter phase timings, anchored at
                # the end of the calculate call (same tracer clock)
                t_c0 = t_calc_end - pot_timings["total_s"]
                pack_s = (pot_timings.get("neighbor_s", 0.0)
                          + pot_timings.get("partition_s", 0.0)
                          + pot_timings.get("rebuild_s", 0.0))
                tr.emit("batched.pack", parent=batch_span,
                        t_start=t_c0, t_end=t_c0 + pack_s,
                        attrs={"bucket_key":
                               pot_stats.get("bucket_key", "")})
                tr.emit("device.compile" if compiled
                        else "device.dispatch", parent=batch_span,
                        t_start=t_c0 + pack_s,
                        t_end=t_c0 + pack_s
                        + pot_timings.get("device_s", 0.0),
                        attrs={"compiled": compiled})
            tr.end(batch_span,
                   status="ok" if results is not None else "error",
                   attrs={"bucket_key": pot_stats.get("bucket_key", "")})
        service = time.perf_counter() - t0
        self._note_service(service)
        self.stats.batches += 1
        if results is not None:
            occupancy = (len(good) / pot_stats["batch_slots"]
                         if pot_stats.get("batch_slots") else 1.0)
            self.stats.note_batch(pot_stats.get("bucket_key", ""), occupancy,
                                  len(good))
        else:
            # the planned batch never ran as one packed program — the
            # requests executed as B=1 singles, so attributing the intended
            # batch's occupancy/bucket would corrupt the per-bucket stats
            pot_stats = {}
            occupancy = 0.0
        mx = obsrt.metrics()
        if mx is not None:
            mx.counter("distmlip_serve_batches_total",
                       "dispatched micro-batches").inc()
            mx.histogram("distmlip_serve_service_seconds",
                         "batch service time").observe(service)
            mx.gauge("distmlip_serve_batch_occupancy",
                     "real structures / padded batch slots of the last "
                     "batch").set(occupancy)
            mx.gauge("distmlip_serve_queue_depth",
                     "requests queued, not yet dispatched").set(
                         self.queue_depth)
            mx.gauge("distmlip_serve_compile_count",
                     "executables compiled by the shared potential").set(
                         self.compile_count)
            if compiled:
                mx.counter("distmlip_serve_compiles_total",
                           "batches that triggered an XLA compile").inc()
            if pot_stats.get("hbm_headroom_frac"):
                mx.gauge("distmlip_hbm_headroom_frac",
                         "1 - est_peak_bytes / bytes_limit of the last "
                         "batch").set(pot_stats["hbm_headroom_frac"])
        self._emit_record("serve_batch", good, t_dispatch, t_done,
                          service_s=service, pot_stats=pot_stats,
                          batch_occupancy=occupancy,
                          trace_ctx=(batch_span.ctx
                                     if batch_span is not None else None))

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------

    def _emit_record(self, kind: str, requests, t_dispatch, t_done,
                     service_s: float, pot_stats: dict | None = None,
                     batch_occupancy: float = 1.0,
                     trace_ctx: tuple | None = None) -> None:
        self._step += 1
        tel = self.telemetry
        if tel is None or not tel.wants_records():
            return
        rec = StepRecord(
            trace_id=trace_ctx[0] if trace_ctx is not None else "",
            span_id=trace_ctx[1] if trace_ctx is not None else "",
            step=self._step, kind=kind,
            timings={"service_s": service_s,
                     "total_s": max(t_done - t_dispatch, service_s)},
            batch_size=len(requests),
            batch_occupancy=batch_occupancy,
            queue_depth=self.queue_depth,
            queue_wait_s=[round(t_dispatch - r.t_submit, 6)
                          for r in requests],
            request_latency_s=[round(t_done - r.t_submit, 6)
                               for r in requests],
            reject_count=self.stats.rejected,
            deadline_miss_count=self.stats.deadline_misses,
            shed_count=self.stats.shed_count,
            structures_per_sec=(len(requests) / service_s
                                if service_s > 0 else 0.0),
        )
        for k in ("bucket_key", "node_occupancy", "edge_occupancy",
                  "padding_waste_frac", "n_atoms", "rebuild_count",
                  "rebuild_on_device", "rebuild_overflow_count",
                  "num_partitions", "n_cap", "e_cap",
                  "mesh_shape", "spatial_parts", "batch_parts",
                  "halo_send_per_part", "kernel_mode", "kernel_coverage",
                  "est_peak_bytes", "hbm_headroom_frac", "aot_rehydrated"):
            if pot_stats and k in pot_stats:
                setattr(rec, k, pot_stats[k])
        tel.emit(rec)
