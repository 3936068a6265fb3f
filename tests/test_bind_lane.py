"""The bind lane (scheduler/bindlane.py): one thread per scheduler sends
the in-cycle bindings, one request in flight, in the order they were
handed over; what is handed over meanwhile leaves as the next request.

On the in-process store, held or failed at will: the order and the
coalescing of hand-offs from waves and from the host path; the loop
launching the next wave while a request is in flight; a refused request
taking every entry queued behind it into the ride-through buffer or the
fence's drop, with nothing sent after it; wait_for_idle and stop() with
entries in the lane; the two counters; and the lane alone under racing
producers."""

import sys
import threading
import time

import pytest

from test_tracing import _hist_n, make_node, make_pod, wait_until

from kubernetes_tpu.client.apiserver import APIServer, LeaderFenced
from kubernetes_tpu.runtime.consensus import DegradedWrites
from kubernetes_tpu.scheduler import KubeSchedulerConfiguration, Scheduler
from kubernetes_tpu.scheduler.bindlane import (
    COUNTER_LANE_HANDOFFS,
    HIST_LANE_WAIT,
    BindLane,
    LaneEntry,
)
from kubernetes_tpu.scheduler.queue import QueuedPodInfo
from kubernetes_tpu.utils.metrics import metrics

COUNTER_FENCED = "scheduler_ha_fenced_binds_total"


class _HeldStore(APIServer):
    """The in-process store, with every bind_pods call recorded (its pod
    keys, in order), held while `gate` is clear, and the next one made
    to raise `fail` once it is let through."""

    def __init__(self):
        super().__init__()
        self.calls = []
        self.entered = threading.Event()
        self.gate = threading.Event()
        self.gate.set()
        self.fail = None
        self._lk = threading.Lock()

    def bind_pods(self, bindings, fence=None):
        with self._lk:
            self.calls.append(
                [f"{b.pod_namespace}/{b.pod_name}" for b in bindings]
            )
            fail, self.fail = self.fail, None
        self.entered.set()
        if not self.gate.wait(60):
            raise RuntimeError("test store held for a minute")
        if fail is not None:
            raise fail
        return super().bind_pods(bindings, fence=fence)

    def bound(self):
        pods, _ = self.list("pods")
        return {p.metadata.key for p in pods if p.spec.node_name}


def _flat(lists):
    return [k for keys in lists for k in keys]


# -- the lane under the scheduler's own sender, driven by hand -----------------


@pytest.fixture
def lane_rig():
    """A scheduler that was never started (no loop, no informers): its
    lane and its sender, and a store with one node and 12 pods."""
    store = _HeldStore()
    store.create("nodes", make_node("n0"))
    pods = [store.create("pods", make_pod(f"p{i}")) for i in range(12)]
    sched = Scheduler(store, KubeSchedulerConfiguration(use_device=False))
    prof = next(iter(sched.profiles.values()))

    def entries(idx, wave=True):
        now = time.monotonic()
        return [
            LaneEntry(QueuedPodInfo(pods[i]), "n0", prof,
                      f"wave-{idx[0]}" if wave else "", now, now)
            for i in idx
        ]

    yield store, sched, pods, entries
    store.gate.set()
    sched.stop()


def test_hand_offs_of_waves_and_the_host_path_leave_in_order(lane_rig):
    """Three waves and a host-path pod handed over while the first
    wave's request is held: the store sees the first wave, then the
    other four hand-offs as ONE request, in hand-off order."""
    store, sched, pods, entries = lane_rig
    lane = sched._bind_lane
    store.gate.clear()
    lane.put(entries([0, 1, 2]))
    assert store.entered.wait(30)
    lane.put(entries([5, 3]))
    lane.put(entries([4], wave=False))
    lane.put(entries([7, 6, 8]))
    lane.put(entries([9], wave=False))
    assert lane.busy() and len(store.calls) == 1
    store.gate.set()
    assert wait_until(lambda: not lane.busy(), 30)
    keys = [p.metadata.key for p in pods]
    assert store.calls == [
        [keys[0], keys[1], keys[2]],
        [keys[5], keys[3], keys[4], keys[7], keys[6], keys[8], keys[9]],
    ]
    assert store.bound() == set(keys[:10])


def test_a_request_carries_at_most_a_chunk():
    """Behind a held request, 5 hand-offs of 3 with a chunk of 4: they
    leave as requests of at most 4, in order, and the sixth hand-off
    waits while a whole chunk is queued (back-pressure)."""
    sent, gate, entered = [], threading.Event(), threading.Event()

    def send(batch):
        sent.append([e.node_name for e in batch])
        entered.set()
        assert gate.wait(30)

    lane = BindLane(send, chunk=4)

    def mk(names):
        return [LaneEntry(None, n, None, "", 0.0, time.monotonic())
                for n in names]

    try:
        lane.put(mk(["a0"]))
        assert entered.wait(30)
        lane.put(mk(["b0", "b1", "b2"]))
        lane.put(mk(["c0", "c1", "c2"]))  # 6 queued: at the chunk
        held = threading.Thread(
            target=lane.put, args=(mk(["d0"]),), daemon=True)
        held.start()
        held.join(0.3)
        assert held.is_alive(), "a hand-off past a whole chunk did not wait"
        gate.set()
        held.join(30)
        assert not held.is_alive()
        assert wait_until(lambda: not lane.busy(), 30)
    finally:
        gate.set()
        lane.close()
    assert _flat(sent) == ["a0", "b0", "b1", "b2", "c0", "c1", "c2", "d0"]
    assert all(len(s) <= 4 for s in sent)
    assert sent[1] == ["b0", "b1", "b2", "c0"]


def test_a_degraded_request_parks_everything_behind_it_in_order(lane_rig):
    store, sched, pods, entries = lane_rig
    lane = sched._bind_lane
    store.gate.clear()
    store.fail = DegradedWrites("store degraded read-only")
    lane.put(entries([0, 1]))
    assert store.entered.wait(30)
    lane.put(entries([2, 3]))
    lane.put(entries([4], wave=False))
    store.gate.set()
    assert wait_until(lambda: not lane.busy(), 30)
    assert sched._ridethrough.open
    assert len(store.calls) == 1, "an entry was sent after the refusal"
    # while the breaker is open, a hand-off parks behind the buffer
    lane.put(entries([5]))
    assert wait_until(lambda: not lane.busy(), 30)
    assert len(store.calls) == 1
    keys = [p.metadata.key for p in pods]
    parked = [e.pi.pod.metadata.key for e in sched._ridethrough.drain()]
    assert parked == keys[:6]
    assert not store.bound()


def test_a_fenced_request_drops_everything_behind_it(lane_rig):
    store, sched, pods, entries = lane_rig
    lane = sched._bind_lane
    fenced0 = metrics.counter(COUNTER_FENCED, {"path": "local"})
    store.gate.clear()
    store.fail = LeaderFenced("a newer leadership grant exists")
    lane.put(entries([0, 1]))
    assert store.entered.wait(30)
    lane.put(entries([2, 3, 4]))
    lane.put(entries([5], wave=False))
    store.gate.set()
    assert wait_until(lambda: not lane.busy(), 30)
    assert len(store.calls) == 1, "an entry was sent after the fence"
    assert metrics.counter(COUNTER_FENCED, {"path": "local"}) - fenced0 == 6
    assert not store.bound()
    assert sched._ridethrough.depth == 0 and not sched._ridethrough.open


def test_wait_for_idle_counts_the_lanes_entries(lane_rig):
    """Nothing else is busy in a scheduler that never started: the
    lane's held request and the entries behind it are."""
    store, sched, pods, entries = lane_rig
    assert sched.wait_for_idle(5)
    store.gate.clear()
    sched._bind_lane.put(entries([0]))
    assert store.entered.wait(30)
    sched._bind_lane.put(entries([1, 2], wave=False))
    assert not sched.wait_for_idle(0.3)
    store.gate.set()
    assert sched.wait_for_idle(30)
    assert len(store.bound()) == 3


def test_the_counters_count_hand_offs_and_each_entrys_wait(lane_rig):
    store, sched, pods, entries = lane_rig
    lane = sched._bind_lane
    waves0 = metrics.counter(COUNTER_LANE_HANDOFFS)
    n0 = _hist_n(HIST_LANE_WAIT)
    h0 = metrics.histogram(HIST_LANE_WAIT)
    s0 = h0.total if h0 is not None else 0.0
    store.gate.clear()
    lane.put(entries([0]))
    assert store.entered.wait(30)
    lane.put(entries([1, 2]))
    lane.put(entries([3], wave=False))
    time.sleep(0.2)
    store.gate.set()
    assert wait_until(lambda: not lane.busy(), 30)
    assert metrics.counter(COUNTER_LANE_HANDOFFS) - waves0 == 3
    assert _hist_n(HIST_LANE_WAIT) - n0 == 4
    # three entries waited out the held request: 0.2 s each at least
    assert metrics.histogram(HIST_LANE_WAIT).total - s0 >= 3 * 0.2


# -- the lane behind the driven loop -------------------------------------------


def _started(store, **cfg):
    for i in range(8):
        store.create("nodes", make_node(f"n{i}"))
    sched = Scheduler(store, KubeSchedulerConfiguration(**cfg))
    handed = []
    put = sched._bind_lane.put

    def recording_put(lane_entries):
        handed.append([e.pi.pod.metadata.key for e in lane_entries])
        put(lane_entries)

    sched._bind_lane.put = recording_put
    sched.start()
    return sched, handed


def test_the_loop_hands_waves_and_host_pods_over_in_order():
    """At 8 nodes a lone pod takes the host lane and a burst the wave
    path. Everything the loop hands over while the first request is
    held reaches the store in hand-off order, as one more request."""
    store = _HeldStore()
    sched, handed = _started(store)
    waves0 = metrics.counter("scheduler_wave_batches_total")
    host0 = metrics.counter(
        "scheduler_host_path_pods_total", {"lane": "small_batch"})
    try:
        store.gate.clear()
        store.create("pods", make_pod("a-0"))
        assert store.entered.wait(60)
        for i in range(40):
            store.create("pods", make_pod(f"b-{i}"))
        assert wait_until(lambda: len(_flat(handed)) >= 41, 120)
        store.create("pods", make_pod("c-0"))
        assert wait_until(lambda: len(_flat(handed)) >= 42, 60)
        assert len(store.calls) == 1
        store.gate.set()
        assert sched.wait_for_idle(60)
    finally:
        store.gate.set()
        sched.stop()
    assert _flat(store.calls) == _flat(handed)
    assert len(store.calls) == 2, "the held hand-offs did not coalesce"
    assert len(store.bound()) == 42
    assert metrics.counter("scheduler_wave_batches_total") > waves0
    assert metrics.counter(
        "scheduler_host_path_pods_total", {"lane": "small_batch"}) >= host0 + 2


def test_the_next_wave_launches_while_a_request_is_in_flight():
    store = _HeldStore()
    sched, handed = _started(store, small_batch_host_max=0)
    try:
        store.gate.clear()
        for i in range(6):
            store.create("pods", make_pod(f"w0-{i}"))
        assert store.entered.wait(120)
        launched = metrics.counter("scheduler_wave_batches_total")
        for i in range(6):
            store.create("pods", make_pod(f"w1-{i}"))
        # launched and resolved: its bindings wait in the lane
        assert wait_until(lambda: len(_flat(handed)) >= 12, 120)
        assert metrics.counter("scheduler_wave_batches_total") > launched
        assert len(store.calls) == 1 and not store.gate.is_set()
        store.gate.set()
        assert sched.wait_for_idle(60)
    finally:
        store.gate.set()
        sched.stop()
    assert len(store.bound()) == 12


def test_idle_and_stop_wait_for_the_lane():
    """wait_for_idle is false while the lane holds entries; stop() sends
    them before the event recorders flush."""
    store = _HeldStore()
    sched, handed = _started(store)
    flushed_with = []
    for prof in sched.profiles.values():
        rec = prof.recorder
        flush = rec.flush

        def recording_flush(*a, _flush=flush, **kw):
            flushed_with.append((sched._bind_lane.busy(), len(store.bound())))
            return _flush(*a, **kw)

        rec.flush = recording_flush
    stopper = threading.Thread(target=sched.stop, daemon=True)
    try:
        store.gate.clear()
        for i in range(3):
            store.create("pods", make_pod(f"s-{i}"))
        assert store.entered.wait(60)
        assert wait_until(lambda: len(_flat(handed)) >= 3, 60)
        assert not sched.wait_for_idle(0.5)
        stopper.start()
        stopper.join(0.5)
        assert stopper.is_alive(), "stop() returned with bindings in the lane"
        store.gate.set()
        stopper.join(60)
        assert not stopper.is_alive()
    finally:
        store.gate.set()
        if stopper.ident is None:
            sched.stop()
    assert len(store.bound()) == 3
    assert flushed_with and all(f == (False, 3) for f in flushed_with)


# -- the lane alone, under racing producers --------------------------------------


def test_racing_producers_lose_and_reorder_nothing():
    """Eight producers hand over 300 entries each, in 1-3-entry puts, at
    a 1 us switch interval: every entry is sent once, each producer's in
    its own order, one send at a time, at most a chunk a send."""
    sent, active, overlap = [], [0], []
    lk = threading.Lock()

    def send(batch):
        with lk:
            active[0] += 1
            if active[0] > 1:
                overlap.append(active[0])
        sent.extend(batch)
        time.sleep(0.0005)
        with lk:
            active[0] -= 1

    lane = BindLane(send, chunk=16)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def producer(p):
        k = 0
        while k < 300:
            n = min(1 + k % 3, 300 - k)
            lane.put([LaneEntry(None, f"{p}:{k + j}", None, "", 0.0,
                                time.monotonic()) for j in range(n)])
            k += n

    threads = [threading.Thread(target=producer, args=(p,), daemon=True)
               for p in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
        assert wait_until(lambda: not lane.busy(), 60)
    finally:
        sys.setswitchinterval(old)
        lane.close()
    names = [e.node_name for e in sent]
    assert len(names) == len(set(names)) == 8 * 300
    for p in range(8):
        mine = [int(n.split(":")[1]) for n in names if n.startswith(f"{p}:")]
        assert mine == list(range(300))
    assert not overlap
