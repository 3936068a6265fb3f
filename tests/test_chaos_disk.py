"""Disk chaos: the storage medium is the last un-chaos'd fault domain.

Every robustness layer above the store (leadership fencing, HA adoption,
persist-first promotion) treats the WAL as the one component that never
lies — these tests make the WAL earn it under the four real disk failure
modes, with deterministic injection (testing/diskfaults.py, never
random) and the consistency-check ledger as the done-bar:

  * crash mid-append (kill -9 loops + a byte-level truncation sweep):
    recovery is exactly the acked prefix, zero acked-write loss, zero
    wrong binds;
  * bit-flip mid-log: recovery refuses to serve silently-wrong state —
    longest valid prefix + DiskCorrupt promotion bar, healed by a
    replication resync from the leader;
  * fsync/write failure: the sink poisons permanently (fsyncgate), the
    store degrades to read-only with the retryable DiskFailed reason,
    and a LEADER with a failed disk releases its lease so a healthy
    replica promotes within retry-periods — not lease expiry;
  * ENOSPC / low space: read-only BEFORE writes fail, nothing poisoned,
    auto-reopen once space recovers, with the fsync-stall watchdog
    catching the slow-dying-disk prequel.

Plus the disaster-recovery end of the story: a cluster restored from an
online backup structurally rejects every pre-restore fencing token.
"""

import json
import os
import shutil
import subprocess
import sys
import threading
import time
import types

import pytest

from kubernetes_tpu.api import objects as v1
from kubernetes_tpu.client.apiserver import APIServer, LeaderFenced, NotFound
from kubernetes_tpu.client.leaderelection import (
    COUNTER_DISK_STEPDOWNS,
    BindFence,
    Lease,
    LeaderElectionConfig,
    LeaderElector,
)
from kubernetes_tpu.runtime import backup
from kubernetes_tpu.runtime.consensus import DiskFailed, DiskPressure
from kubernetes_tpu.runtime.replication import Follower, ReplicationListener
from kubernetes_tpu.runtime.wal import (
    COUNTER_FSYNC_STALLS,
    COUNTER_RETRIES_EXHAUSTED,
    COUNTER_TMP_SWEEPS,
    DiskSpaceProbe,
    RecoveryReport,
    SinkFailed,
    WriteAheadLog,
)
from kubernetes_tpu.testing.diskfaults import (
    DiskFaultInjector,
    bit_flip_record,
    truncate_log_at,
)
from kubernetes_tpu.utils.metrics import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(REPO, "scripts") not in sys.path:
    sys.path.insert(0, os.path.join(REPO, "scripts"))

import consistency_check  # noqa: E402  (scripts/ is not a package)


def wait_until(fn, timeout=30.0, period=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(period)
    return False


def make_pod(name, namespace="default"):
    return v1.Pod(
        metadata=v1.ObjectMeta(name=name, namespace=namespace),
        spec=v1.PodSpec(containers=[v1.Container(name="c", image="img")]),
    )


def make_wal(tmp_path, name="store", **kw):
    kw.setdefault("native", False)  # python sink: the injection seam
    kw.setdefault("fsync", False)
    return WriteAheadLog(str(tmp_path / name), **kw)


def fake_probe(path, free_bytes):
    """DiskSpaceProbe with injected statvfs + an always-advancing clock
    (defeats the 1s rate limit); mutate probe.free[0] to move space."""
    free = [free_bytes]
    tick = [0.0]

    def clock():
        tick[0] += 10.0
        return tick[0]

    def statvfs(_d):
        return types.SimpleNamespace(f_bavail=free[0], f_frsize=1)

    probe = DiskSpaceProbe(path, statvfs=statvfs, clock=clock)
    probe.free = free
    return probe


# ---------------------------------------------------------------------------
# kill -9 mid-append loops (the ChaosStore/consistency-check ledger)
# ---------------------------------------------------------------------------

_KILL_LOOP_CHILD = r"""
import json, os, signal, sys, time

prefix, ack_path, cycles, repo = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), sys.argv[4]
)
sys.path.insert(0, repo)
from kubernetes_tpu.api import objects as v1
from kubernetes_tpu.client.apiserver import APIServer
from kubernetes_tpu.runtime.wal import WriteAheadLog


def worker():
    # recover exactly like a restarting node, then append + bind forever
    # until SIGKILLed; every ack line is written only AFTER the client-
    # visible success (the consistency checker's contract)
    report = WriteAheadLog.recover_report(prefix)
    if report.corrupt:
        os._exit(7)  # a process kill must never look like media damage
    srv = APIServer(wal=WriteAheadLog(prefix, fsync=False, native=False))
    srv._rv = report.rv
    srv._objects = report.objects
    ack = open(ack_path, "a", buffering=1)
    have = {p.metadata.name: p for p in srv.list("pods", "default")[0]}
    i = 0
    while True:
        name = "p%d" % i
        pod = have.get(name)
        if pod is None:
            pod = srv.create("pods", v1.Pod(
                metadata=v1.ObjectMeta(name=name, namespace="default"),
                spec=v1.PodSpec(
                    containers=[v1.Container(name="c", image="img")]
                ),
            ))
            ack.write(json.dumps({
                "op": "create", "kind": "pods",
                "key": "default/%s" % name,
                "rv": pod.metadata.resource_version,
            }) + "\n")
        if not pod.spec.node_name:
            srv.bind_pod(v1.Binding(
                pod_name=name, pod_namespace="default",
                target_node="n%d" % (i % 4),
            ))
            bound = srv.get("pods", "default", name)
            ack.write(json.dumps({
                "op": "update", "kind": "pods",
                "key": "default/%s" % name,
                "rv": bound.metadata.resource_version,
            }) + "\n")
        i += 1


for cycle in range(cycles):
    pid = os.fork()
    if pid == 0:
        try:
            worker()
        finally:
            os._exit(9)
    time.sleep(0.12)
    os.kill(pid, signal.SIGKILL)
    os.waitpid(pid, 0)

report = WriteAheadLog.recover_report(prefix)
pods = report.objects.get("pods", {})
wrong = [
    key for key, pod in pods.items()
    if pod.spec.node_name
    and pod.spec.node_name != "n%d" % (int(pod.metadata.name[1:]) % 4)
]
print(json.dumps({
    "rv": report.rv,
    "pods": len(pods),
    "bound": sum(1 for p in pods.values() if p.spec.node_name),
    "corrupt": report.corrupt,
    "wrong_binds": wrong,
}))
"""


def _run_kill_loop(tmp_path, cycles):
    prefix = str(tmp_path / "killstore")
    ack_path = str(tmp_path / "acks.jsonl")
    child = tmp_path / "kill_child.py"
    child.write_text(_KILL_LOOP_CHILD)
    proc = subprocess.run(
        [sys.executable, str(child), prefix, ack_path, str(cycles), REPO],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=60 + 2 * cycles,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (
        f"kill loop child failed (rc={proc.returncode}):\n"
        f"{proc.stdout}\n{proc.stderr}"
    )
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    # zero double/wrong binds on the recovered state
    assert summary["corrupt"] is False
    assert summary["wrong_binds"] == []
    assert summary["pods"] > cycles  # each cycle made real progress
    # zero acked-write loss, proven by the external checker against the
    # surviving WAL exactly as a restarted node would recover it
    assert consistency_check.run(ack_path, [prefix]) == 0
    return summary


def test_kill9_mid_append_recovery_loop(tmp_path):
    """A handful of kill -9-mid-append crash/recover cycles: every acked
    create and bind survives; recovery never classifies a process kill
    as media corruption (tier-1-speed variant of the 50x loop below)."""
    _run_kill_loop(tmp_path, cycles=4)


@pytest.mark.slow
def test_kill9_mid_append_recovery_loop_50x(tmp_path):
    """The acceptance bar: 50 consecutive kill -9 mid-append cycles with
    zero acked-write loss and zero double-binds on the ledger."""
    summary = _run_kill_loop(tmp_path, cycles=50)
    assert summary["bound"] >= 50


# ---------------------------------------------------------------------------
# byte-level crash points (satellite: property sweep + legacy format)
# ---------------------------------------------------------------------------

def test_every_crash_point_recovers_exactly_the_acked_prefix(tmp_path):
    """Truncate a live WAL at EVERY byte offset of the final-record
    region: recovery must equal exactly the acked prefix (records whose
    bytes fully landed), never lose an acked write, and never classify
    the torn tail as mid-log corruption."""
    prefix = str(tmp_path / "sweep")
    wal = make_wal(tmp_path, "sweep")
    acks = []  # (end_offset_of_record, ack dict)
    for i in range(8):
        rv = i + 1
        pod = make_pod(f"p{i}")
        pod.metadata.resource_version = rv
        wal.append(rv, "create", "pods", pod)
        acks.append((
            os.path.getsize(wal.log_path),
            {"op": "create", "kind": "pods", "key": f"default/p{i}", "rv": rv},
        ))
    wal.close()
    size = os.path.getsize(prefix + ".wal")
    last_start = acks[-2][0]  # byte where the final record begins
    scratch = str(tmp_path / "cut")
    for cut in range(last_start, size + 1):
        shutil.copyfile(prefix + ".wal", scratch + ".wal")
        truncate_log_at(scratch + ".wal", cut)
        report = WriteAheadLog.recover_report(scratch)
        assert not report.corrupt, f"cut@{cut}: torn tail misread as corrupt"
        acked = [a for end, a in acks if end <= cut]
        want_rv = acked[-1]["rv"] if acked else 0
        # recovery must hold AT LEAST every acked record; one extra is
        # legal (a complete record whose trailing newline the crash ate
        # — durable but never acknowledged), more than one is not
        assert want_rv <= report.rv <= want_rv + 1, (
            f"cut@{cut}: recovered rv={report.rv}, acked prefix rv={want_rv}"
        )
        state = {
            "rv": report.rv,
            "commit": report.commit,
            "objects": {
                kind: {
                    key: o.metadata.resource_version for key, o in d.items()
                }
                for kind, d in report.objects.items()
            },
        }
        losses = consistency_check.check(acked, state)
        assert not losses, f"cut@{cut}: {losses}"


def test_legacy_pre_crc_wal_still_recovers(tmp_path):
    """A v1 (pre-CRC, raw-JSON-lines) log recovers unchanged, and a new
    writer appends v2 frames after it — the reader sniffs per line."""
    from kubernetes_tpu.api import serialization

    prefix = str(tmp_path / "legacy")
    with open(prefix + ".wal", "w", encoding="utf-8") as f:
        for i in range(5):
            f.write(json.dumps({
                "rv": i + 1, "verb": "create", "kind": "pods",
                "obj": serialization.encode(make_pod(f"old{i}")),
            }) + "\n")
    report = WriteAheadLog.recover_report(prefix)
    assert report.rv == 5 and not report.corrupt
    assert len(report.objects["pods"]) == 5

    wal = make_wal(tmp_path, "legacy")
    wal.append(6, "create", "pods", make_pod("new0"))
    wal.close()
    report = WriteAheadLog.recover_report(prefix)
    assert report.rv == 6 and not report.corrupt
    names = {p.metadata.name for p in report.objects["pods"].values()}
    assert names == {"old0", "old1", "old2", "old3", "old4", "new0"}


# ---------------------------------------------------------------------------
# bit-flip mid-log: refuse-to-lie + heal-by-resync
# ---------------------------------------------------------------------------

def test_bit_flip_midlog_recovers_longest_valid_prefix(tmp_path):
    prefix = str(tmp_path / "flip")
    wal = make_wal(tmp_path, "flip")
    for i in range(10):
        wal.append(i + 1, "create", "pods", make_pod(f"p{i}"))
    wal.close()
    bit_flip_record(prefix + ".wal", 3)
    report = WriteAheadLog.recover_report(prefix)
    # valid acked records exist AFTER the damage: this is mid-log
    # corruption, not a torn tail — serve the honest prefix and say so
    assert report.corrupt and report.bad_records >= 1
    assert report.rv == 3
    assert set(report.objects["pods"]) == {
        "default/p0", "default/p1", "default/p2"
    }
    # the recovered server carries the promotion bar
    srv = APIServer.recover(prefix)
    assert srv.disk_corrupt


def test_corrupt_replica_heals_via_resync_and_promotes(tmp_path):
    """DiskCorrupt bars promotion until the replication snapshot-resync
    from a healthy leader replaces the state — then the bar lifts."""
    primary = APIServer()
    for i in range(6):
        primary.create("pods", make_pod(f"p{i}"))
    listener = ReplicationListener(heartbeat_s=0.1)
    listener.attach(primary)
    follower = Follower(
        listener.address,
        lease_s=30.0,
        wal=make_wal(tmp_path, "healme"),
        disk_corrupt=True,
    ).start()
    try:
        assert follower.disk_corrupt
        assert follower.promote() is None  # barred while corrupt
        assert wait_until(lambda: not follower.disk_corrupt, 10), (
            "snapshot resync never lifted the DiskCorrupt bar"
        )
        promoted = follower.promote()
        assert promoted is not None
        assert len(promoted.list("pods", "default")[0]) == 6
    finally:
        follower.stop()
        listener.close()


def test_follower_own_disk_failure_bars_promotion_keeps_serving(tmp_path):
    """A follower whose OWN wal append fails fail-stops durability only:
    it keeps tailing in memory (reads/watch stay live) but is barred
    from promotion permanently."""
    primary = APIServer()
    listener = ReplicationListener(heartbeat_s=0.1)
    listener.attach(primary)
    wal = make_wal(tmp_path, "failfoll")
    inj = DiskFaultInjector(fail_writes=(0,)).install(wal)
    follower = Follower(listener.address, lease_s=30.0, wal=wal).start()
    try:
        assert wait_until(follower._synced.is_set, 10)
        primary.create("pods", make_pod("after-sync"))
        assert wait_until(lambda: follower.disk_failed, 10), (
            "WAL append failure never flipped disk_failed"
        )
        # in-memory replication still tracked the write...
        assert wait_until(
            lambda: follower.list_kind("pods")[1]
            >= primary.resource_version,
            10,
        )
        # ...but this replica can never again vouch for durability
        assert follower.promote() is None
    finally:
        inj.uninstall()
        follower.stop()
        listener.close()


# ---------------------------------------------------------------------------
# fail-stop fsync discipline (fsyncgate) + leader step-down
# ---------------------------------------------------------------------------

def test_fsync_failure_poisons_sink_and_store_fail_stops(tmp_path):
    wal = make_wal(tmp_path, "fsyncfail", fsync=True)
    srv = APIServer(wal=wal)
    srv.create("pods", make_pod("before"))
    inj = DiskFaultInjector(fail_all_fsyncs=True).install(wal)
    with pytest.raises(DiskFailed):
        srv.create("pods", make_pod("doomed"))
    assert wal.failed is not None  # poisoned permanently
    assert srv.write_gate.disk_failed
    assert metrics.gauge("store_disk_state") == 2.0
    # fsyncgate: the next write must 503 WITHOUT touching the sink —
    # retrying fsync on dirty pages can never prove durability
    calls = inj.write_calls
    with pytest.raises(DiskFailed):
        srv.create("pods", make_pod("rejected"))
    assert inj.write_calls == calls
    # reads and the already-applied (readable, unacked-durable) state
    # keep serving: fail-stop is a durability statement, not an outage
    names = {p.metadata.name for p in srv.list("pods", "default")[0]}
    assert "before" in names and "doomed" in names
    inj.uninstall()
    # poisoning survives the injector: the sink never comes back
    with pytest.raises((DiskFailed, SinkFailed)):
        srv.create("pods", make_pod("still-rejected"))


def test_leader_with_failed_disk_steps_down_within_retry_periods(tmp_path):
    """The leader releases its lease on disk death, so a disk-healthy
    standby promotes inside retry-periods — NOT after lease expiry."""
    store = APIServer()
    cfg = lambda ident: LeaderElectionConfig(  # noqa: E731
        identity=ident,
        lease_duration=2.0,
        renew_deadline=1.2,
        retry_period=0.2,
        lock_name="disk-chaos",
    )
    disk_ok = [True]
    led_a, led_b = threading.Event(), threading.Event()
    a = LeaderElector(
        store, cfg("leader"), on_started_leading=led_a.set,
        disk_health=lambda: disk_ok[0],
    )
    b = LeaderElector(store, cfg("standby"), on_started_leading=led_b.set)
    ta = threading.Thread(target=a.run, daemon=True)
    tb = threading.Thread(target=b.run, daemon=True)
    ta.start()
    assert wait_until(led_a.is_set, 10)
    tb.start()
    stepdowns0 = metrics.counter(COUNTER_DISK_STEPDOWNS)
    try:
        t0 = time.monotonic()
        disk_ok[0] = False  # the leader's disk dies
        assert wait_until(led_b.is_set, 10), "standby never promoted"
        elapsed = time.monotonic() - t0
        assert metrics.counter(COUNTER_DISK_STEPDOWNS) > stepdowns0
        assert elapsed < cfg("x").lease_duration, (
            f"failover took {elapsed:.2f}s — that's lease-expiry takeover, "
            "not an active disk-death release"
        )
    finally:
        a.stop()
        b.stop()
        ta.join(timeout=5)
        tb.join(timeout=5)


# ---------------------------------------------------------------------------
# ENOSPC / disk-pressure ride-through + heal
# ---------------------------------------------------------------------------

def test_enospc_ride_through_and_heal(tmp_path):
    """ENOSPC mid-append degrades to DiskPressure read-only WITHOUT
    poisoning the sink; once space frees, a retried write reopens the
    store; recovery shows zero acked loss either side of the squeeze."""
    prefix = str(tmp_path / "enospc")
    wal = make_wal(tmp_path, "enospc")
    srv = APIServer(wal=wal)
    # pre-arm a deterministic probe so the auto-clear path is driven by
    # the test, not the real (never-full) filesystem under tmp_path
    probe = fake_probe(prefix, free_bytes=1 << 30)
    srv.disk_probe = probe
    inj = DiskFaultInjector(enospc_after_bytes=700).install(wal)

    created, squeezed = [], False
    for i in range(100):
        try:
            srv.create("pods", make_pod(f"p{i}"))
            created.append(f"p{i}")
        except DiskPressure:
            squeezed = True
            break
    assert squeezed and created, "never hit the ENOSPC squeeze"
    assert srv.write_gate.disk_pressure
    assert wal.failed is None, "ENOSPC pre-fsync must not poison the sink"
    assert probe.under_pressure, (
        "ENOSPC entry must arm the probe's hysteresis or nothing clears"
    )
    assert metrics.gauge("store_disk_state") == 1.0
    assert len(srv.list("pods", "default")[0]) >= len(created)  # reads

    # space still low: writes keep 503ing as DiskPressure
    probe.free[0] = 0
    with pytest.raises(DiskPressure):
        srv.create("pods", make_pod("still-full"))

    # space recovers: the next (client-retried) write reopens the store
    inj.free_space()
    probe.free[0] = 1 << 30
    srv.create("pods", make_pod("healed"))
    assert not srv.write_gate.disk_pressure
    assert metrics.gauge("store_disk_state") == 0.0

    inj.uninstall()
    wal.close()
    report = WriteAheadLog.recover_report(prefix)
    assert not report.corrupt
    names = {p.metadata.name for p in report.objects["pods"].values()}
    for n in created:
        assert n in names, f"acked {n} lost across the ENOSPC squeeze"
    assert "healed" in names


def test_low_watermark_enters_read_only_before_writes_fail(tmp_path):
    """The probe trips the gate on the admission path BEFORE any append
    can hit ENOSPC — the sink is never even touched while gated."""
    prefix = str(tmp_path / "watermark")
    wal = make_wal(tmp_path, "watermark")
    srv = APIServer(wal=wal)
    inj = DiskFaultInjector().install(wal)
    probe = fake_probe(prefix, free_bytes=(32 << 20) - 1)
    assert probe.free[0] < probe.low_bytes
    srv.disk_probe = probe
    with pytest.raises(DiskPressure):
        srv.create("pods", make_pod("early"))
    assert inj.write_calls == 0, "gated write must never reach the sink"
    assert srv.write_gate.disk_pressure
    # hysteresis: recovering past low but under high stays read-only
    probe.free[0] = probe.high_bytes - 1
    with pytest.raises(DiskPressure):
        srv.create("pods", make_pod("between-watermarks"))
    probe.free[0] = probe.high_bytes
    srv.create("pods", make_pod("recovered"))
    assert not srv.write_gate.disk_pressure
    inj.uninstall()


def test_fsync_stall_watchdog_flags_slow_disk(tmp_path):
    """A dying disk stretches fsyncs long before erroring: the watchdog
    gauge flips on a stalled fsync and clears on the next healthy one."""
    wal = make_wal(tmp_path, "stall", fsync=True)
    wal.FSYNC_STALL_S = 0.01
    inj = DiskFaultInjector(slow_fsyncs=(0,), fsync_delay_s=0.05).install(wal)
    stalls0 = metrics.counter(COUNTER_FSYNC_STALLS)
    wal.append(1, "create", "pods", make_pod("slow"))
    assert metrics.counter(COUNTER_FSYNC_STALLS) == stalls0 + 1
    assert metrics.gauge("wal_fsync_stalled") == 1.0
    wal.append(2, "create", "pods", make_pod("fast"))
    assert metrics.gauge("wal_fsync_stalled") == 0.0
    inj.uninstall()
    wal.close()


# ---------------------------------------------------------------------------
# compaction resilience + recovery-signal satellites
# ---------------------------------------------------------------------------

def test_orphaned_compaction_tmp_files_swept_at_open(tmp_path):
    prefix = str(tmp_path / "orphans")
    for suffix in (".snapshot.json.tmp", ".wal.tmp"):
        with open(prefix + suffix, "w") as f:
            f.write("{half-written garbage from a crash mid-compaction")
    sweeps0 = metrics.counter(COUNTER_TMP_SWEEPS)
    wal = WriteAheadLog(prefix, native=False, fsync=False)
    assert not os.path.exists(prefix + ".snapshot.json.tmp")
    assert not os.path.exists(prefix + ".wal.tmp")
    assert metrics.counter(COUNTER_TMP_SWEEPS) == sweeps0 + 2
    wal.close()


def test_recover_staleness_retries_exhausted_is_surfaced(
    tmp_path, monkeypatch
):
    """recover_full exhausting its 10 staleness retries must say so
    (report flag + counter), never silently return possibly-torn state."""
    prefix = str(tmp_path / "stale")
    wal = make_wal(tmp_path, "stale")
    wal.write_snapshot(5, {"pods": [make_pod("p0")]})
    wal.close()

    def always_stale(path):
        return RecoveryReport(rv=4, snap_rv=4)  # never matches disk's rv=5

    monkeypatch.setattr(
        WriteAheadLog, "_recover_once", staticmethod(always_stale)
    )
    exhausted0 = metrics.counter(COUNTER_RETRIES_EXHAUSTED)
    report = WriteAheadLog.recover_report(prefix)
    assert report.retries_exhausted
    assert metrics.counter(COUNTER_RETRIES_EXHAUSTED) == exhausted0 + 1


# ---------------------------------------------------------------------------
# fenced backup / restore: disaster recovery without split-brain
# ---------------------------------------------------------------------------

def test_restore_structurally_rejects_every_pre_restore_fence(tmp_path):
    # a live cluster with pods and a scheduler holding the lease
    src = APIServer()
    for i in range(4):
        src.create("pods", make_pod(f"p{i}"))
    src.create("leases", Lease(
        metadata=v1.ObjectMeta(name="sched", namespace="kube-system"),
        holder_identity="sched-1",
        lease_transitions=3,
    ))
    zombie_fence = BindFence(
        namespace="kube-system", name="sched", identity="sched-1",
        transitions=3,
    )
    # sanity: the fence is valid against the LIVE cluster
    errs = src.bind_pods(
        [v1.Binding(pod_name="p0", pod_namespace="default",
                    target_node="n0")],
        fence=zombie_fence,
    )
    assert errs == [None]

    # disaster: online backup, restore into a fresh WAL, recover
    image = backup.backup_from_server(src, str(tmp_path / "img.json"))
    summary = backup.restore_into(
        backup.load_backup(str(tmp_path / "img.json")),
        str(tmp_path / "restored"),
    )
    assert summary["term"] == image["term"] + 1  # durable epoch bump
    assert summary["fenced_leases"] == 1
    restored = APIServer.recover(str(tmp_path / "restored"))
    assert restored.resource_version == image["rv"]
    assert not restored.disk_corrupt

    # EVERY pre-restore token is structurally rejected: the restored
    # lease has no holder and a bumped transition count, so the zombie's
    # identity AND transitions both mismatch — no wall clocks involved
    with pytest.raises(LeaderFenced):
        restored.bind_pods(
            [v1.Binding(pod_name="p1", pod_namespace="default",
                        target_node="n1")],
            fence=zombie_fence,
        )
    # the restored cluster itself is fully writable (unfenced paths)
    restored.create("pods", make_pod("post-restore"))
    assert restored.bind_pods([
        v1.Binding(pod_name="p1", pod_namespace="default", target_node="n1")
    ]) == [None]


def test_restore_refuses_to_clobber_without_force(tmp_path):
    src = APIServer()
    src.create("pods", make_pod("keep"))
    image = backup.backup_from_server(src, str(tmp_path / "img.json"))
    wal = make_wal(tmp_path, "occupied")
    wal.append(1, "create", "pods", make_pod("resident"))
    wal.close()
    with pytest.raises(FileExistsError):
        backup.restore_into(image, str(tmp_path / "occupied"))
    with pytest.raises(NotFound):
        # the resident log was NOT touched by the refused restore
        APIServer.recover(str(tmp_path / "occupied")).get(
            "pods", "default", "keep"
        )
    backup.restore_into(image, str(tmp_path / "occupied"), force=True)
    restored = APIServer.recover(str(tmp_path / "occupied"))
    assert restored.get("pods", "default", "keep").metadata.name == "keep"


def test_offline_backup_of_corrupt_wal_flags_the_image(tmp_path):
    prefix = str(tmp_path / "sick")
    wal = make_wal(tmp_path, "sick")
    for i in range(6):
        wal.append(i + 1, "create", "pods", make_pod(f"p{i}"))
    wal.close()
    bit_flip_record(prefix + ".wal", 2)
    image = backup.backup_from_wal(prefix, str(tmp_path / "sick.json"))
    assert image.get("source_corrupt") is True
    assert image["rv"] == 2  # honest: the longest valid prefix only
