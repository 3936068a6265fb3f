"""Per-pod scheduling traces: spans from queue admit to store ack.

The metrics registry answers "what is the p99"; nothing in the system
could answer "WHERE did the p99 pod spend its time". This module is the
tail-latency attribution layer:

  * a **trace** is minted per pod at queue admission (and per wave at
    kernel launch) and accumulates **spans** — named `[t0, t1)`
    monotonic intervals (`queue`, `encode`, `device`, `readback`,
    `guard`, `assume`, `bind`, `outage.wait`, ...) — plus point
    **events** (`bind.parked`, `unschedulable`, `bind.fenced`, ...);
  * **wave traces** fan-in the N pod traces sharing one kernel launch:
    each pod span chain carries its wave's trace id, so one slow wave
    explains N slow pods;
  * completed traces land in a bounded per-process **ring buffer**
    served by the SIGUSR2 "traces" dump section, the `/debug/traces`
    REST view (slowest-N, by-id lookup), and the `--debug-port`
    listener on scheduler/controller-manager processes;
  * trace context **propagates across process boundaries**: the REST
    client attaches an ``X-Trace-Context`` header to a `/binding` POST
    and a ``traceContext`` to each item of a BindingList, the route
    re-establishes the context thread-locally, and the
    store stamps the apply — or the LeaderFenced rejection — under the
    same id into a bounded store-side ledger (`stamp_bind`), so a
    zombie's fenced bind is visible as a trace event in the store
    process.

Span API contract (machine-enforced by graftlint's tracing pass): a
span is either recorded atomically with measured endpoints
(`add_span`/`add_spans`/`add_span_many` — nothing is left open) or
opened through the ``span()`` context manager, which MUST be used as a
``with`` statement so every started span is finished on all exits.

Clock discipline: every timestamp in a span is `time.monotonic()` —
never wall clock (deflake guard: NTP steps and clock skew must not
produce negative or inflated stages). Wall time appears only as a trace
attribute: a pod trace's `admit_lag_s`, the store's commit of the pod's
create -> its queue admission (a wall-clock delta computed by the queue,
``Event.committed`` -> now; only on a first admission, delivered by the
watch), also folded into ``scheduler_pod_admit_lag_seconds``.

Window-long aggregates (the ring holds 1,024 traces; a benchmark window
binds ten times that): at ``finish()`` a pod trace's per-stage durations
fold into per-stage sum/count/buckets beside the counter deltas and flush
with them, at most once a second, as
``scheduling_pod_stage_duration_seconds{stage}``. The scheduling loop's
own wall is accounted by a :class:`PhaseTracker` (exactly one phase at
any instant, one clock read per switch; published as
``scheduler_loop_phase_seconds_total{phase,inflight}`` and, while a
profiler session runs, as ``ktpu.loop.<phase>`` annotations on the
device trace's clock); the same switches split the pod-seconds that the
scheduling queue's pods waited by the phase the loop was in
(``scheduler_queue_wait_seconds_total{phase}``, with
``scheduler_queue_waits_total`` the visits they were waited over). Stalls — GC pauses and periodic background passes
— land in a small bounded log (``stall_events``: ``/debug/traces?stalls=1``
and the SIGUSR2 dump), so a stall seen from outside at t can be matched
to what ran at t.

Concurrency: one named lock (``tracing.ring``) guards the active table,
the ring, and the store ledger; the lock is a leaf (nothing else is
acquired under it) and the shared attributes are Eraser-tracked
(`track_attrs`) so the chaos suites' lockset sanitizer machine-checks
the guard from day one. Disabled (``KTPU_TRACING=0`` or
``set_enabled(False)``) every entry point is one attribute test.
"""

from __future__ import annotations

import bisect
import gc
import itertools
import os
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Tuple

from ..testing.lockgraph import named_lock, track_attrs
from .metrics import DEFAULT_BUCKETS

# the cross-process propagation header (attached by RESTClient.bind_pod,
# consumed by the /binding route; bind_pods sends the same id per item of
# its BindingList)
TRACE_HEADER = "X-Trace-Context"

COUNTER_STARTED = "tracing_traces_total"
COUNTER_COMPLETED = "tracing_traces_completed_total"
COUNTER_DROPPED = "tracing_traces_dropped_total"
COUNTER_STORE_STAMPS = "tracing_store_stamps_total"
GAUGE_RING_DEPTH = "tracing_ring_depth"
GAUGE_ACTIVE = "tracing_active_traces"
GAUGE_ENABLED = "tracing_enabled"
# a finished pod trace's stages, folded over the whole process lifetime
# (the ring is for "which pod", this is for "how long, on average")
HIST_POD_STAGE = "scheduling_pod_stage_duration_seconds"
# the scheduling loop's wall by phase; inflight="1" while a launched
# batch's index payload has not been read back (the chip has work)
COUNTER_LOOP_PHASE = "scheduler_loop_phase_seconds_total"
# the queue's waiting pods, integrated over time, by the loop phase that
# held them (pod-seconds), and the pods pop_batch handed to the loop
COUNTER_QUEUE_WAIT = "scheduler_queue_wait_seconds_total"
COUNTER_QUEUE_WAITS = "scheduler_queue_waits_total"
# a pod's store commit -> queue admit (first admissions), batch-published
HIST_ADMIT_LAG = "scheduler_pod_admit_lag_seconds"
# stall causes, in every process that installs the probes
HIST_GC_PAUSE = "process_gc_pause_seconds"
GAUGE_PROCESS_CLOCK = "process_clock_seconds"

# pod-trace span names in waterfall order (the bench stage waterfall and
# the SIGUSR2 renderer both order stages by this, unknown names last)
STAGE_ORDER = (
    "queue",
    "encode",
    "device",
    "readback",
    "guard",
    "assume",
    "bind",
    "ack",
    "outage.wait",
    "algo",
    "launch",
    "commit",
)

_tls = threading.local()


def _new_agg() -> list:
    """[n, total seconds, bucket counts]: observations folded where they
    happen and merged into the registry later (Histogram.merge)."""
    return [0, 0.0, [0] * (len(DEFAULT_BUCKETS) + 1)]


def _fold(agg: list, dur: float) -> None:
    agg[0] += 1
    agg[1] += dur
    agg[2][bisect.bisect_left(DEFAULT_BUCKETS, dur)] += 1


class _TraceRecord:
    __slots__ = (
        "trace_id",
        "kind",
        "key",
        "t0",
        "t1",
        "attrs",
        "spans",
        "events",
        "outcome",
    )

    def __init__(
        self,
        trace_id: str,
        kind: str,
        key: str,
        attrs: dict,
        t0: Optional[float] = None,
    ):
        self.trace_id = trace_id
        self.kind = kind
        self.key = key
        # t0 may be backdated (monotonic): a wave trace is minted only
        # once its launch succeeds, but its lifetime starts at cycle
        # entry — without this, its own encode span would predate it
        # (negative offsets) and total_s would omit encode+launch
        self.t0 = t0 if t0 is not None else time.monotonic()
        self.t1: Optional[float] = None
        self.attrs = attrs
        # (name, t0, t1, attrs-or-None) — atomic, never half-open
        self.spans: List[Tuple[str, float, float, Optional[dict]]] = []
        self.events: List[Tuple[float, str, str]] = []
        self.outcome = ""

    def total_s(self) -> float:
        end = self.t1 if self.t1 is not None else time.monotonic()
        return end - self.t0

    def stages(self) -> Dict[str, float]:
        """Per-stage wall, summed over same-named spans (a requeued pod
        legitimately has several `queue` spans)."""
        out: Dict[str, float] = {}
        for name, s0, s1, _a in self.spans:
            out[name] = out.get(name, 0.0) + (s1 - s0)
        return out

    def to_dict(self) -> dict:
        """JSON-renderable form; span times become offsets (ms) from the
        trace start so they are meaningful outside this process."""
        order = {n: i for i, n in enumerate(STAGE_ORDER)}
        return {
            "trace_id": self.trace_id,
            "kind": self.kind,
            "key": self.key,
            "finished": self.t1 is not None,
            "outcome": self.outcome,
            "total_ms": round(self.total_s() * 1e3, 3),
            "attrs": dict(self.attrs),
            "stages_ms": {
                k: round(v * 1e3, 3)
                for k, v in sorted(
                    self.stages().items(),
                    key=lambda kv: order.get(kv[0], len(order)),
                )
            },
            "spans": [
                {
                    "name": name,
                    "start_ms": round((s0 - self.t0) * 1e3, 3),
                    "dur_ms": round((s1 - s0) * 1e3, 3),
                    **({"attrs": a} if a else {}),
                }
                for name, s0, s1, a in self.spans
            ],
            "events": [
                {
                    "at_ms": round((t - self.t0) * 1e3, 3),
                    "name": name,
                    **({"detail": detail} if detail else {}),
                }
                for t, name, detail in self.events
            ],
        }


class Tracer:
    """Process-global span pipeline: active traces, completed ring,
    store-side stamp ledger. All shared state under ONE leaf lock."""

    # spans/events per trace are capped: a pod stuck in a requeue storm
    # must not grow an unbounded span list
    MAX_SPANS = 96
    MAX_EVENTS = 64

    def __init__(
        self,
        ring_size: int = 1024,
        max_active: int = 65536,
        stamp_ledger_size: int = 4096,
    ):
        # one attribute test per entry point when disabled; flipped only
        # by set_enabled — a torn read is impossible for a bool
        self._enabled = os.environ.get("KTPU_TRACING", "1").lower() not in (  # graftlint: unguarded(single-writer bool flag, atomic read by design — same contract as lockgraph._enabled)
            "0",
            "false",
        )
        # named + Eraser-tracked: the ring enters the race-sanitizer
        # contract from day one (lock is a leaf — nothing acquired under)
        self._lock = named_lock("tracing.ring")
        self._active: Dict[str, _TraceRecord] = {}
        self._by_key: Dict[str, str] = {}  # pod key -> active trace id
        self._ring: deque = deque(maxlen=ring_size)
        self._store_ledger: deque = deque(maxlen=stamp_ledger_size)
        self._max_active = max_active
        # trace ids: one random per-process prefix + a counter — globally
        # unique like uuid4 but ~10x cheaper to mint on the admit path
        # (ids are minted per pod CREATE; a uuid4 per pod measurably taxes
        # a 4096-pod burst admit). next() on a count() is GIL-atomic.
        self._id_prefix = uuid.uuid4().hex[:8]
        self._id_counter = itertools.count(1)
        # counter/gauge deltas accumulate HERE (plain dict bumps under
        # the already-held trace lock) and publish to the metrics
        # registry in batches: per-op metrics.inc from the admit/finish
        # hot paths measurably taxed burst scheduling — the registry
        # lock is contended by the scheduler's own histogram observes
        # (measured: ~16% of a 6k-pod burst wall went to per-op
        # inc/set_gauge lock hops; batched, it is noise)
        self._counts: Dict[Tuple[str, str], int] = {}
        # stage -> [n, total_s, bucket counts]: finished pod traces'
        # stages, folded under the same lock and flushed with _counts
        self._stage_agg: Dict[str, list] = {}
        # admitted pods' commit -> admit lags, folded at start()
        self._admit_agg: list = _new_agg()
        self._last_pub = 0.0  # graftlint: unguarded(single-float publish throttle; a torn read double-publishes at worst)
        self._pub_interval_s = 1.0

    # -- enable/disable -------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    def set_enabled(self, on: bool) -> None:
        from .metrics import metrics

        self._enabled = on
        metrics.set_gauge(GAUGE_ENABLED, 1.0 if on else 0.0)

    # -- trace lifecycle ------------------------------------------------------

    def start(
        self,
        kind: str,
        key: str,
        t0: Optional[float] = None,
        admit_lag_s: Optional[float] = None,
        **attrs,
    ) -> str:
        """Mint a trace; returns "" when disabled (every other entry
        point treats "" as a no-op id, so call sites stay unconditional).
        t0 (monotonic) backdates the trace start for records minted
        after their first span's interval began. admit_lag_s (a pod's
        commit -> admit, seconds) is kept as the attribute of that name
        and folded into ``scheduler_pod_admit_lag_seconds``: one value."""
        if not self._enabled:
            return ""
        seq = next(self._id_counter)
        trace_id = f"{self._id_prefix}{seq:08x}"
        if admit_lag_s is not None:
            attrs["admit_lag_s"] = admit_lag_s
        rec = _TraceRecord(trace_id, kind, key, attrs, t0)
        with self._lock:
            if admit_lag_s is not None:
                _fold(self._admit_agg, admit_lag_s)
            if len(self._active) >= self._max_active:
                # evict the oldest active trace (dict preserves insertion
                # order) — bounded memory beats a complete tail under a
                # pathological backlog
                old_id, old = next(iter(self._active.items()))
                del self._active[old_id]
                if self._by_key.get(old.key) == old_id:
                    del self._by_key[old.key]
                self._bump_locked("dropped", "active_overflow")
            self._active[trace_id] = rec
            if kind == "pod":
                self._by_key[key] = trace_id
            self._bump_locked("started", kind)
        self._maybe_publish()
        return trace_id

    def finish(self, trace_id: str, outcome: str = "", **attrs) -> None:
        """Complete a trace: stamp t1, move it into the ring."""
        if not self._enabled or not trace_id:
            return
        with self._lock:
            rec = self._active.pop(trace_id, None)
            if rec is None:
                return
            if self._by_key.get(rec.key) == trace_id:
                del self._by_key[rec.key]
            rec.t1 = time.monotonic()
            rec.outcome = outcome
            if attrs:
                rec.attrs.update(attrs)
            self._ring.append(rec)
            self._bump_locked("completed", rec.kind)
            if rec.kind == "pod":
                self._fold_stages_locked(rec)
        self._maybe_publish()

    def _fold_stages_locked(self, rec: _TraceRecord) -> None:
        agg = self._stage_agg
        for name, dur in rec.stages().items():
            a = agg.get(name)
            if a is None:
                a = agg[name] = _new_agg()
            _fold(a, dur)

    def discard(self, trace_id: str) -> None:
        """Drop an active trace without completing it (pod deleted while
        queued — there is no lifecycle left to attribute)."""
        if not trace_id:
            return
        with self._lock:
            rec = self._active.pop(trace_id, None)
            if rec is not None:
                if self._by_key.get(rec.key) == trace_id:
                    del self._by_key[rec.key]
                self._bump_locked("dropped", "discarded")

    # -- span & event recording ----------------------------------------------

    def add_span(
        self, trace_id: str, name: str, t0: float, t1: float, **attrs
    ) -> None:
        """Record one closed span [t0, t1) (time.monotonic endpoints)."""
        if not self._enabled or not trace_id:
            return
        with self._lock:
            self._add_span_locked(trace_id, name, t0, t1, attrs or None)

    def add_spans(
        self, items: List[Tuple[str, str, float, float]]
    ) -> None:
        """Batch form — (trace_id, name, t0, t1) tuples, ONE lock
        acquisition for a whole wave's worth of per-pod spans."""
        if not self._enabled or not items:
            return
        with self._lock:
            for trace_id, name, t0, t1 in items:
                self._add_span_locked(trace_id, name, t0, t1, None)

    def add_span_many(
        self,
        trace_ids: List[str],
        name: str,
        t0: float,
        t1: float,
        **attrs,
    ) -> None:
        """The wave fan-in: one identical span recorded into N pod
        traces (e.g. the shared `device` interval) in one acquisition."""
        if not self._enabled or not trace_ids:
            return
        a = attrs or None
        with self._lock:
            for trace_id in trace_ids:
                self._add_span_locked(trace_id, name, t0, t1, a)

    def _add_span_locked(
        self,
        trace_id: str,
        name: str,
        t0: float,
        t1: float,
        attrs: Optional[dict],
    ) -> None:
        rec = self._active.get(trace_id)
        if rec is None or len(rec.spans) >= self.MAX_SPANS:
            return
        rec.spans.append((name, t0, t1, attrs))

    @contextmanager
    def span(self, trace_id: str, name: str, **attrs):
        """Inline span over a code region. MUST be used as a `with`
        statement (graftlint's tracing pass enforces it), so the span is
        closed on every exit path, exceptions included."""
        if not self._enabled or not trace_id:
            yield
            return
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.add_span(trace_id, name, t0, time.monotonic(), **attrs)

    def event(self, trace_id: str, name: str, detail: str = "") -> None:
        """Point-in-time annotation on an active trace."""
        if not self._enabled or not trace_id:
            return
        t = time.monotonic()
        with self._lock:
            rec = self._active.get(trace_id)
            if rec is None or len(rec.events) >= self.MAX_EVENTS:
                return
            rec.events.append((t, name, detail[:160]))

    # -- cross-process store-side stamps --------------------------------------

    def stamp(self, trace_id: str, event: str, **attrs) -> None:
        """Store-side ledger entry under a (possibly foreign) trace id:
        the apply/fence record a scheduler's trace resolves to after the
        REST hop. Kept even when the id was minted in another process —
        that is the point."""
        if not self._enabled or not trace_id:
            return
        with self._lock:
            self._store_ledger.append(
                {
                    "trace_id": trace_id,
                    "event": event,
                    "t": time.monotonic(),
                    **attrs,
                }
            )
            self._bump_locked("stamp", event)
        self._maybe_publish()

    def stamps_for(self, trace_id: str) -> List[dict]:
        with self._lock:
            return [
                dict(s)
                for s in self._store_ledger
                if s["trace_id"] == trace_id
            ]

    # -- lookup / rendering ---------------------------------------------------

    def trace_for_pod(self, key: str) -> str:
        """The trace id owning pod `key` right now: the thread-local
        bind context (re-established from the REST header on the server
        side) wins; else the in-process active-trace index."""
        if not self._enabled:
            return ""
        ctx = getattr(_tls, "bind_ctx", None)
        if ctx:
            tid = ctx.get(key)
            if tid:
                return tid
        with self._lock:
            return self._by_key.get(key, "")

    def get(self, trace_id: str) -> Optional[dict]:
        """By-id lookup across active + ring, with any store-side stamps
        attached."""
        with self._lock:
            rec = self._active.get(trace_id)
            if rec is None:
                rec = next(
                    (r for r in self._ring if r.trace_id == trace_id), None
                )
            out = rec.to_dict() if rec is not None else None
            stamps = [
                dict(s)
                for s in self._store_ledger
                if s["trace_id"] == trace_id
            ]
        if out is None:
            if not stamps:
                return None
            # a foreign trace known only by its store stamps (the store
            # process's view of a scheduler-minted trace)
            out = {"trace_id": trace_id, "kind": "foreign", "spans": []}
        if stamps:
            out["store_stamps"] = stamps
        return out

    def slowest(self, n: int = 10, kind: str = "pod") -> List[dict]:
        with self._lock:
            recs = [r for r in self._ring if not kind or r.kind == kind]
            recs.sort(key=lambda r: r.total_s(), reverse=True)
            return [r.to_dict() for r in recs[:n]]

    def stage_stats(self, kind: str = "pod") -> Dict[str, dict]:
        """Aggregate per-stage durations over the ring's completed
        traces of `kind`: the bench stage waterfall's data source."""
        per_stage: Dict[str, List[float]] = {}
        with self._lock:
            recs = [r for r in self._ring if r.kind == kind]
            for r in recs:
                for name, dur in r.stages().items():
                    per_stage.setdefault(name, []).append(dur)
        out: Dict[str, dict] = {}
        for name, durs in per_stage.items():
            durs.sort()
            n = len(durs)
            out[name] = {
                "count": n,
                "total_s": round(sum(durs), 6),
                "p50_ms": round(durs[min(n // 2, n - 1)] * 1e3, 3),
                "p99_ms": round(
                    durs[min(int(0.99 * n), n - 1)] * 1e3, 3
                ),
            }
        order = {s: i for i, s in enumerate(STAGE_ORDER)}
        return dict(
            sorted(out.items(), key=lambda kv: order.get(kv[0], len(order)))
        )

    def render_lines(self, n: int = 5) -> List[str]:
        """The SIGUSR2 "traces" section: slowest-N completed pod traces
        as waterfall lines, plus ring/active occupancy."""
        with self._lock:
            active, ring = len(self._active), len(self._ring)
        lines = [
            f"  enabled: {self._enabled}  active: {active}  "
            f"ring: {ring}  (lookup: /debug/traces?id=<trace_id>)"
        ]
        for d in self.slowest(n):
            stages = "  ".join(
                f"{k}={v:.1f}ms" for k, v in d["stages_ms"].items()
            )
            lines.append(
                f"  {d['trace_id']} {d['key']} total={d['total_ms']:.1f}ms "
                f"[{d.get('outcome') or '?'}] {stages}"
            )
        return lines

    def render_if_long(
        self, trace_id: str, title: str, threshold_s: float
    ) -> Optional[str]:
        """The slow-batch report: when trace `trace_id` (active, or the
        newest match in the ring) has lasted `threshold_s` or more, its
        spans in start order as one multi-line text, else None —
        ``"<title>" {attrs} (total ms):`` then one ``+<dur>ms <span>``
        line per span."""
        if not self._enabled or not trace_id:
            return None
        with self._lock:
            rec = self._active.get(trace_id)
            if rec is None:
                rec = next(
                    (r for r in reversed(self._ring)
                     if r.trace_id == trace_id),
                    None,
                )
            if rec is None or rec.total_s() < threshold_s:
                return None
            total, attrs = rec.total_s(), dict(rec.attrs)
            spans = sorted(rec.spans, key=lambda sp: sp[1])
        parts = [f'"{title}" {attrs} ({total * 1e3:.1f}ms):']
        parts.extend(
            f"  +{(s1 - s0) * 1e3:.1f}ms {name}" for name, s0, s1, _a in spans
        )
        return "\n".join(parts)

    def _bump_locked(self, what: str, label: str) -> None:
        """Caller holds self._lock: accumulate one counter delta for the
        next batched publish (a plain dict bump — no registry lock)."""
        k = (what, label)
        self._counts[k] = self._counts.get(k, 0) + 1

    def _maybe_publish(self) -> None:
        """Time-throttled flush of accumulated deltas into the metrics
        registry (called OUTSIDE the trace lock)."""
        now = time.monotonic()
        if now - self._last_pub >= self._pub_interval_s:
            self._last_pub = now
            self.publish_gauges()

    def publish_gauges(self) -> None:
        """Flush accumulated counter deltas and refresh the occupancy
        gauges. Dump/scrape paths call this so a reader never sees stale
        tracing series; the hot paths only bump plain dicts and flush
        through here at most once per second."""
        with self._lock:
            depth, active = len(self._ring), len(self._active)
            deltas, self._counts = self._counts, {}
            stages, self._stage_agg = self._stage_agg, {}
            admit, self._admit_agg = self._admit_agg, _new_agg()
        from .metrics import metrics

        for name, (n, total, counts) in sorted(stages.items()):
            metrics.merge_histogram(
                HIST_POD_STAGE, {"stage": name}, counts, total, n
            )
        if admit[0]:
            metrics.merge_histogram(
                HIST_ADMIT_LAG, None, admit[2], admit[1], admit[0]
            )
        for (what, label), n in sorted(deltas.items()):
            by = float(n)
            if what == "started":
                metrics.inc(COUNTER_STARTED, {"kind": label}, by=by)
            elif what == "completed":
                metrics.inc(COUNTER_COMPLETED, {"kind": label}, by=by)
            elif what == "dropped":
                metrics.inc(COUNTER_DROPPED, {"reason": label}, by=by)
            elif what == "stamp":
                metrics.inc(COUNTER_STORE_STAMPS, {"outcome": label}, by=by)
        metrics.set_gauge(GAUGE_RING_DEPTH, float(depth))
        metrics.set_gauge(GAUGE_ACTIVE, float(active))
        metrics.set_gauge(GAUGE_ENABLED, 1.0 if self._enabled else 0.0)

    def reset(self) -> None:
        """Test/bench-window helper: drop every trace and stamp."""
        with self._lock:
            self._active.clear()
            self._by_key.clear()
            self._ring.clear()
            self._store_ledger.clear()
            self._counts.clear()
            self._stage_agg.clear()
            self._admit_agg = _new_agg()


# lockset sanitizer (testing/lockgraph.py Eraser mode): the active
# table, pod-key index, completed ring, and store-stamp ledger are
# shared by scheduler/informer/bind-pool/REST-handler threads — all
# guarded by the one `tracing.ring` leaf lock, machine-checked in chaos
track_attrs(
    Tracer, "_active", "_by_key", "_ring", "_store_ledger", "_counts",
    "_stage_agg", "_admit_agg",
)


tracer = Tracer()  # process-global tracer (one ring per process)


# -- the scheduling loop's wall, phase by phase ---------------------------------


class PhaseTracker:
    """Wall-clock accounting for ONE thread: at any instant the thread is
    in exactly one phase, and a switch is one ``time.monotonic()`` read
    (returned, so the span and the stage histogram that share the
    boundary use the same instant). Seconds accumulate per (phase,
    inflight); ``publish()`` — a metrics collector, run at every scrape —
    adds the open phase up to now and incs
    ``scheduler_loop_phase_seconds_total`` by what is new, so the series'
    delta over two scrapes sums to their distance on this process's
    clock, whatever the loop was in the middle of.

    ``waiting`` (the scheduling queue's ``PriorityQueue.waiting``: its
    waiting population integrated over time, one tuple read) splits the
    queue's wait the same way: each switch credits the pod-seconds waited
    since the last one to the phase it leaves, and ``publish()`` incs
    ``scheduler_queue_wait_seconds_total{phase}`` (no ``inflight``) and
    ``scheduler_queue_waits_total`` (the pods ``pop_batch`` handed out) up
    to the scrape. Δ(all phases) / Δwaits is the mean wait of a visit to
    the queue (Little's law); ``pop`` is the batch former's linger.

    ``annotate`` (``jax.profiler.TraceAnnotation``, handed in by the
    scheduler: this module stays importable without JAX) opens each phase
    as a ``ktpu.loop.<phase>`` host event, so a profiler session started
    from outside records the phases on the device trace's own clock; with
    no session active an annotation is a flag test. Always on:
    ``KTPU_TRACING=0`` does not reach here (operators read the series
    from ``/metrics``). ``clock`` is for tests."""

    def __init__(
        self,
        annotate: Optional[Callable[[str], object]] = None,
        prefix: str = "ktpu.loop.",
        start: str = "other",
        waiting: Optional[Callable[[], tuple]] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        # a leaf: taken by the owner thread per switch (uncontended) and
        # by the scrape thread in publish()
        self._lock = named_lock("tracing.phase")
        self._acc: Dict[Tuple[str, str], float] = {}
        self._published: Dict[Tuple[str, str], float] = {}
        self._phase = start
        self._inflight = "0"
        self._clock = clock
        self._waiting = waiting
        # phase -> pod-seconds of queue wait; `_area` is the queue's
        # integral at the last switch, `_handed_pub` the visits published
        self._wacc: Dict[str, float] = {}
        self._wpublished: Dict[str, float] = {}
        self._handed_pub = 0
        self._t, self._area, _ = self._now()
        self._annotate = annotate
        self._prefix = prefix
        # the open annotation: touched by the owner thread only
        self._ann = None  # graftlint: unguarded(owner-thread only: opened and closed by the thread the tracker belongs to)

    @property
    def phase(self) -> str:
        with self._lock:
            return self._phase

    def _now(self) -> Tuple[float, float, int]:
        """(now, the queue's pod-seconds waited up to now, pods it has
        handed out). The queue's tuple is read BEFORE the clock, so `now`
        is never earlier than the tuple's own instant."""
        if self._waiting is None:
            return self._clock(), 0.0, 0
        area, n, at, handed = self._waiting()
        now = self._clock()
        return now, area + n * (now - at), handed

    def switch(self, phase: str, inflight: Optional[bool] = None) -> float:
        """Enter `phase` now; returns the instant. `inflight` (when given)
        re-labels the time from here on: True while the chip holds a
        launched batch whose index payload has not been read back."""
        with self._lock:
            now, area, _ = self._now()
            k = (self._phase, self._inflight)
            self._acc[k] = self._acc.get(k, 0.0) + (now - self._t)
            self._wacc[self._phase] = (
                self._wacc.get(self._phase, 0.0) + (area - self._area)
            )
            self._t, self._area = now, area
            changed = phase != self._phase
            self._phase = phase
            if inflight is not None:
                self._inflight = "1" if inflight else "0"
        if self._annotate is not None and (changed or self._ann is None):
            self._reannotate(phase)
        return now

    def _reannotate(self, phase: Optional[str]) -> None:
        ann, self._ann = self._ann, None
        try:
            if ann is not None:
                ann.__exit__(None, None, None)
            if phase is not None:
                ann = self._annotate(self._prefix + phase)
                ann.__enter__()
                self._ann = ann
        except Exception:
            self._annotate = None  # a profiler fault must not stop the loop

    def close(self) -> None:
        """The owner thread is leaving: account up to now as `other` and
        close the open annotation."""
        self.switch("other", inflight=False)
        if self._annotate is not None:
            self._reannotate(None)

    def _totals_locked(self, now: float) -> Dict[Tuple[str, str], float]:
        out = dict(self._acc)
        k = (self._phase, self._inflight)
        out[k] = out.get(k, 0.0) + (now - self._t)
        return out

    def _waits_locked(self, area: float) -> Dict[str, float]:
        out = dict(self._wacc)
        out[self._phase] = out.get(self._phase, 0.0) + (area - self._area)
        return out

    def totals(self) -> Dict[Tuple[str, str], float]:
        """(phase, inflight) -> seconds, the open phase counted to now."""
        with self._lock:
            return self._totals_locked(self._now()[0])

    def queue_waits(self) -> Tuple[Dict[str, float], int]:
        """(phase -> pod-seconds the queue waited, the open phase counted
        to now; pods handed out)."""
        with self._lock:
            _now, area, handed = self._now()
            return self._waits_locked(area), handed

    def publish(self) -> None:
        from .metrics import metrics

        with self._lock:
            now, area, handed = self._now()
            totals = self._totals_locked(now)
            deltas = {
                k: v - self._published.get(k, 0.0) for k, v in totals.items()
            }
            self._published = totals
            waits = self._waits_locked(area)
            wdeltas = {
                p: v - self._wpublished.get(p, 0.0) for p, v in waits.items()
            }
            self._wpublished = waits
            visits, self._handed_pub = handed - self._handed_pub, handed
        for (phase, inflight), by in sorted(deltas.items()):
            if by > 0.0:
                metrics.inc(
                    COUNTER_LOOP_PHASE,
                    {"phase": phase, "inflight": inflight},
                    by=by,
                )
        for phase, by in sorted(wdeltas.items()):
            if by > 0.0:
                metrics.inc(COUNTER_QUEUE_WAIT, {"phase": phase}, by=by)
        if visits > 0:
            metrics.inc(COUNTER_QUEUE_WAITS, by=float(visits))


track_attrs(
    PhaseTracker, "_acc", "_published", "_phase", "_inflight", "_t",
    "_wacc", "_wpublished", "_area", "_handed_pub",
)


# -- stalls: GC pauses and background passes -----------------------------------

# A gc callback runs wherever an allocation tripped the collector — also
# inside a `with` of the registry lock or of tracing.ring — so it may take
# NO lock: it writes plain module state (collections never nest, the GIL
# orders the writes) and a collector publishes it at scrape time.
_GC_EVENT_MIN_S = 0.001  # shorter pauses are counted, not listed
_GC_RING = 256
_gc_t0 = [0.0]
_gc_acc: Dict[int, list] = {g: _new_agg() for g in (0, 1, 2)}
_gc_published: Dict[int, list] = {g: _new_agg() for g in (0, 1, 2)}
_gc_events: List[Optional[tuple]] = [None] * _GC_RING
_gc_n_events = [0]
_probes_installed = [False]

# periodic passes: (task, t0, dur) under a leaf lock of their own
_PASS_RING = 1024
_pass_lock = named_lock("tracing.stalls")
_pass_events: deque = deque(maxlen=_PASS_RING)


def _gc_callback(phase: str, info: dict) -> None:
    if phase == "start":
        _gc_t0[0] = time.monotonic()
        return
    t0 = _gc_t0[0]
    dur = time.monotonic() - t0
    gen = info.get("generation", 2)
    a = _gc_acc.get(gen)
    if a is None:
        return
    _fold(a, dur)
    if dur >= _GC_EVENT_MIN_S:
        i = _gc_n_events[0]
        _gc_events[i % _GC_RING] = (t0, dur, gen)
        _gc_n_events[0] = i + 1


def _publish_process_series() -> None:
    from .metrics import metrics

    metrics.set_gauge(GAUGE_PROCESS_CLOCK, time.monotonic())
    for gen, a in _gc_acc.items():
        n, total, counts = a[0], a[1], list(a[2])
        p = _gc_published[gen]
        dn = n - p[0]
        if dn <= 0:
            continue
        metrics.merge_histogram(
            HIST_GC_PAUSE,
            {"generation": str(gen)},
            [c - pc for c, pc in zip(counts, p[2])],
            total - p[1],
            dn,
        )
        _gc_published[gen] = [n, total, counts]


def install_stall_probes() -> None:
    """Once per process (cmd/scheduler, cmd/apiserver): the gc.callbacks
    hook behind ``process_gc_pause_seconds{generation}`` and the scrape-
    time ``process_clock_seconds`` gauge (the delta of two scrapes is
    their distance on this process's clock: the denominator of every
    per-second reading)."""
    from .metrics import metrics

    if not _probes_installed[0]:
        _probes_installed[0] = True
        gc.callbacks.append(_gc_callback)
    metrics.add_collector(_publish_process_series)


def note_pass(task: str, t0: float, dur: float) -> None:
    """One periodic background pass (anti-entropy audit, assume-TTL
    sweep, queue flush, WAL compaction's locked part) ran [t0, t0+dur)
    on time.monotonic(): kept for ``/debug/traces?stalls=1``. The caller
    observes its own ``*_background_pass_seconds{task}`` series, outside
    the lock the pass took."""
    with _pass_lock:
        _pass_events.append((task, t0, dur))


def stall_events(min_ms: float = 0.0) -> dict:
    """GC pauses (>= 1 ms) and background passes, oldest first, with
    starts on this process's monotonic clock (`now` says where that
    clock stands)."""
    with _pass_lock:
        passes = list(_pass_events)
    n = _gc_n_events[0]
    pauses = [
        e for e in (
            _gc_events[i % _GC_RING] for i in range(max(0, n - _GC_RING), n)
        ) if e is not None
    ]
    floor = min_ms / 1e3
    return {
        "now": time.monotonic(),
        "gc": [
            {"t0": round(t0, 6), "ms": round(d * 1e3, 3), "generation": g}
            for t0, d, g in pauses if d >= floor
        ],
        "passes": [
            {"task": task, "t0": round(t0, 6), "ms": round(d * 1e3, 3)}
            for task, t0, d in passes if d >= floor
        ],
    }


def stall_lines(n: int = 8) -> List[str]:
    """The SIGUSR2 "stalls" section: the n longest recent GC pauses and
    background passes, with how long ago each began."""
    ev = stall_events()
    now = ev["now"]
    rows = [
        (e["ms"], f"gc gen{e['generation']}", now - e["t0"]) for e in ev["gc"]
    ] + [(e["ms"], e["task"], now - e["t0"]) for e in ev["passes"]]
    rows.sort(reverse=True)
    lines = [
        f"  recorded: {len(ev['gc'])} gc pauses >= 1 ms, "
        f"{len(ev['passes'])} background passes "
        f"(all: /debug/traces?stalls=1)"
    ]
    lines.extend(
        f"  {ms:9.3f} ms  {what}  began {ago:.1f}s ago"
        for ms, what, ago in rows[:n]
    )
    return lines


# -- cross-process bind context ------------------------------------------------


@contextmanager
def bind_context(mapping: Dict[str, str]):
    """Establish pod-key -> trace-id context for the current thread (the
    REST binding routes enter this from the X-Trace-Context header, or
    from the items of a BindingList, so the store's stamps land under
    the scheduler-minted id)."""
    prev = getattr(_tls, "bind_ctx", None)
    _tls.bind_ctx = mapping
    try:
        yield
    finally:
        _tls.bind_ctx = prev


def stamp_bind(binding, event: str, **attrs) -> None:
    """Stamp a bind outcome for one Binding under whatever trace id owns
    the pod (thread-local context from the REST hop, or the in-process
    active index). No-op when nobody is tracing the pod."""
    key = f"{binding.pod_namespace}/{binding.pod_name}"
    tid = tracer.trace_for_pod(key)
    if tid:
        tracer.stamp(
            tid, event, key=key, node=getattr(binding, "target_node", ""),
            **attrs,
        )


def trace_for_binding(binding) -> str:
    """The trace id to attach to a /binding POST for this Binding."""
    return tracer.trace_for_pod(
        f"{binding.pod_namespace}/{binding.pod_name}"
    )


def health_lines() -> List[str]:
    """Tracing counters/gauges for the SIGUSR2 dump (covers the
    `tracing_` dump-required metric family)."""
    from .metrics import metrics

    tracer.publish_gauges()
    lines: List[str] = []
    for name, labels, value in metrics.snapshot_gauges("tracing_"):
        lines.append(metrics.format_series_line(name, labels, value))
    for name, labels, value in metrics.snapshot_counters("tracing_"):
        lines.append(metrics.format_series_line(name, labels, value))
    return lines
