"""Compaction from the WAL's own files (cut -> fold in a child process ->
publish; runtime/walfold.py, WriteAheadLog.cut / fold / publish,
APIServer._compact_async).

What must hold: the files a compaction leaves recover to exactly the
store's acknowledged state, whichever way it compacted; every crash point
of the sequence recovers; the compaction takes the `store` lock never and
parses no log line in the server's process; a cut overtaken by another
rewrite of the log publishes nothing; damaged files fall back to memory,
once, and heal.
"""

import copy
import importlib.util
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import threading
import time

import pytest

from kubernetes_tpu.api import objects as v1
from kubernetes_tpu.api import serialization
from kubernetes_tpu.client.apiserver import APIServer, NotFound
from kubernetes_tpu.runtime import wal as wal_mod
from kubernetes_tpu.runtime import walfold
from kubernetes_tpu.runtime.wal import FoldFailed, LogDamaged, WriteAheadLog
from kubernetes_tpu.testing.diskfaults import bit_flip_record
from kubernetes_tpu.utils.metrics import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_check():
    """benchmark/harness/check.py, the benchmark's plain reader of the
    WAL's files (imports nothing of the program)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_harness_check",
        os.path.join(REPO, "benchmark", "harness", "check.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


check = _load_check()


def wait_until(fn, timeout=30.0, period=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(period)
    return False


def passes(task):
    """(count, seconds) of the store's background passes of that task."""
    h = metrics.histogram("store_background_pass_seconds", {"task": task})
    return (h.n, h.total) if h is not None else (0, 0.0)


def make_pod(name, namespace="default", finalizers=()):
    return v1.Pod(
        metadata=v1.ObjectMeta(
            name=name, namespace=namespace, finalizers=list(finalizers)
        ),
        spec=v1.PodSpec(containers=[v1.Container(name="c", image="img")]),
    )


def make_node(name):
    return v1.Node(metadata=v1.ObjectMeta(name=name, namespace=""))


def open_store(directory, **kw):
    """A store over `<directory>/cluster.*`, the benchmark's layout; no
    compaction of its own unless the test asks for one."""
    os.makedirs(directory, exist_ok=True)
    kw.setdefault("compact_every", 10**9)
    kw.setdefault("fsync", False)
    wal = WriteAheadLog(os.path.join(str(directory), "cluster"), **kw)
    return APIServer(wal=wal), wal


def encoded(objects):
    """{kind: {key: encoded}} without the kinds that hold nothing."""
    return {
        kind: {k: serialization.encode(o) for k, o in d.items()}
        for kind, d in objects.items()
        if d
    }


def snapshot_on_disk(prefix):
    with open(prefix + ".snapshot.json", encoding="utf-8") as f:
        snap = json.load(f)
    return snap["rv"], {
        kind: {walfold.object_key(o): o for o in objs}
        for kind, objs in snap["objects"].items()
        if objs
    }


class History:
    """A seeded run of everything the store logs: creates in two
    namespaces and of a cluster-scoped kind, updates, batch binds (which
    mutate the stored pod in place), deletes, graceful deletes that wait
    for a finalizer, re-creates under a deleted key, and commit records
    that share a data record's rv."""

    def __init__(self, server, seed, tag=""):
        self.server = server
        self.tag = tag  # tells the names of two histories of one store apart
        self.rng = random.Random(seed)
        self.n = 0
        self.live = []     # (namespace, name) of pods that exist
        self.deleted = []  # keys free to be re-created
        self.nodes = []

    def step(self):
        s, rng = self.server, self.rng
        op = rng.choice(
            ["create"] * 4
            + ["node", "update", "bind", "bind", "delete", "graceful",
               "recreate", "commit"]
        )
        if op == "create" or not self.live:
            self.n += 1
            ns = rng.choice(["default", "other"])
            s.create("pods", make_pod(f"{self.tag}p{self.n}", ns))
            self.live.append((ns, f"{self.tag}p{self.n}"))
        elif op == "node":
            self.n += 1
            s.create("nodes", make_node(f"{self.tag}n{self.n}"))
            self.nodes.append(f"{self.tag}n{self.n}")
        elif op == "update":
            ns, name = rng.choice(self.live)
            pod = s.get("pods", ns, name)
            pod.metadata.labels[f"k{rng.randrange(3)}"] = str(rng.random())
            s.update("pods", pod)
        elif op == "bind":
            picks = rng.sample(self.live, min(len(self.live), 3))
            s.bind_pods([
                v1.Binding(pod_name=name, pod_namespace=ns,
                           target_node=rng.choice(self.nodes or ["n0"]))
                for ns, name in picks
            ])  # an already-bound pod's error entry is part of the history
        elif op == "delete":
            key = self.live.pop(rng.randrange(len(self.live)))
            s.delete("pods", *key)
            self.deleted.append(key)
        elif op == "graceful":
            self.n += 1
            name = f"{self.tag}g{self.n}"
            s.create("pods", make_pod(name, finalizers=["test/hold"]))
            s.delete("pods", "default", name)  # marks, keeps
            if rng.random() < 0.7:
                pod = s.get("pods", "default", name)
                pod.metadata.finalizers = []
                s.update("pods", pod)  # the last strip removes it
                with pytest.raises(NotFound):
                    s.get("pods", "default", name)
            # else it stays, deletion pending, for the rest of the history
        elif op == "recreate" and self.deleted:
            key = self.deleted.pop(rng.randrange(len(self.deleted)))
            s.create("pods", make_pod(key[1], key[0]))
            self.live.append(key)
        elif op == "commit":
            s.wal.append_commit(s.resource_version, s.resource_version, 1, "restored")

    def run(self, steps):
        for _ in range(steps):
            self.step()


def compact_both_ways(server, a_dir, b_dir):
    """Compact the live store from its files, and a copy of those same
    files from memory: what each leaves behind."""
    prefix = server.wal.path
    shutil.rmtree(b_dir, ignore_errors=True)
    shutil.copytree(a_dir, b_dir)
    files0 = metrics.counter("wal_compactions_total", {"how": "files"})
    server._compact_async()
    assert metrics.counter("wal_compactions_total", {"how": "files"}) == files0 + 1
    b_wal = WriteAheadLog(os.path.join(b_dir, "cluster"), fsync=False)
    b_wal.write_snapshot(
        server.resource_version,
        {k: [copy.deepcopy(o) for o in d.values()]
         for k, d in server._objects.items()},
    )
    b_wal.close()
    return prefix, os.path.join(b_dir, "cluster")


# -- (a) equivalence ----------------------------------------------------------


@pytest.mark.parametrize("native", [False, True], ids=["python-sink", "native-sink"])
@pytest.mark.parametrize("seed", [1, 7, 2**31 + 11])
def test_file_fold_equals_memory_snapshot_equals_live_store(tmp_path, seed, native):
    a_dir, b_dir = str(tmp_path / "a"), str(tmp_path / "b")
    server, wal = open_store(a_dir, native=native)
    history = History(server, seed)
    for round_no in range(2):  # the second fold starts from the first's snapshot
        history.run(150)
        a, b = compact_both_ways(server, a_dir, b_dir)
        live = encoded(server._objects)
        rv_a, objs_a = snapshot_on_disk(a)
        rv_b, objs_b = snapshot_on_disk(b)
        assert objs_a == live, f"round {round_no}: the fold is not the store"
        assert objs_b == live
        assert rv_a == rv_b == server.resource_version
        # both logs now hold nothing the snapshot covers
        assert os.path.getsize(a + ".wal") == os.path.getsize(b + ".wal") == 0
        assert check.read_wal_pods(a_dir) == check.read_wal_pods(b_dir)
    history.run(60)  # a tail on top of the second snapshot
    report = WriteAheadLog.recover_report(wal.path)
    assert encoded(report.objects) == encoded(server._objects)
    assert report.rv == server.resource_version
    assert not report.corrupt and not report.torn_tail
    pods, damaged = check.read_wal_pods(a_dir)
    assert damaged == 0
    assert pods == {
        # check.py keys a pod by the namespace as written: the encoder
        # leaves "default" out
        f"{'' if p.metadata.namespace == 'default' else p.metadata.namespace}"
        f"/{p.metadata.name}": p.spec.node_name or ""
        for p in server._objects["pods"].values()
    }
    wal.close()


def test_fold_module_imports_nothing_of_the_object_model():
    """The child's start is part of every compaction: json, zlib, os, and
    what the `runtime` package itself imports (the metrics registry)."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, kubernetes_tpu.runtime.walfold; "
         "print(sorted(m for m in sys.modules if m.startswith("
         "('kubernetes_tpu.api', 'kubernetes_tpu.client', 'jax', 'numpy'))))"],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


# -- (b) crash points ---------------------------------------------------------


def _recovers_everything(prefix, server):
    report = WriteAheadLog.recover_report(prefix)
    assert encoded(report.objects) == encoded(server._objects)
    assert report.rv == server.resource_version
    assert not report.corrupt and not report.torn_tail


def test_child_killed_mid_fold_loses_nothing_and_backs_off(tmp_path, monkeypatch):
    server, wal = open_store(tmp_path / "d")
    History(server, 3).run(120)
    real_run = subprocess.run

    def killed_mid_write(argv, **kw):
        # the child got as far as half a snapshot, then SIGKILL
        with open(wal.snap_path + ".tmp", "w") as f:
            f.write('{"rv": 5, "objects": {"pods": [{"metadata": {"na')
        return subprocess.CompletedProcess(argv, -signal.SIGKILL, b"", b"")

    monkeypatch.setattr(wal_mod.subprocess, "run", killed_mid_write)
    fails0 = metrics.counter("wal_compaction_failures_total")
    server._compact_async()
    assert metrics.counter("wal_compaction_failures_total") == fails0 + 1
    assert server._compact_backoff_until > time.monotonic()
    assert not os.path.exists(wal.snap_path)
    assert not os.path.exists(wal.snap_path + ".tmp"), "half a snapshot left"
    _recovers_everything(wal.path, server)
    server.create("pods", make_pod("after-the-failure"))  # appends go on
    monkeypatch.setattr(wal_mod.subprocess, "run", real_run)
    server._compact_async()
    assert snapshot_on_disk(wal.path)[1] == encoded(server._objects)
    wal.close()


def test_parent_stops_between_snapshot_replace_and_log_replace(tmp_path, monkeypatch):
    server, wal = open_store(tmp_path / "d")
    history = History(server, 4)
    history.run(120)
    log_before = os.path.getsize(wal.log_path)

    def stop(keep):
        raise KeyboardInterrupt("the parent stops here")

    monkeypatch.setattr(wal, "_rewrite_log_locked", stop)
    with pytest.raises(KeyboardInterrupt):
        server._compact_async()
    monkeypatch.undo()
    # new snapshot, old (whole) log: replay skips what the snapshot covers
    assert os.path.exists(wal.snap_path)
    assert os.path.getsize(wal.log_path) == log_before
    _recovers_everything(wal.path, server)
    # and the restarted server compacts on top of it
    wal.close()
    restarted = APIServer.recover(wal.path)
    assert encoded(restarted._objects) == encoded(server._objects)
    History(restarted, 5, tag="again-").run(40)
    restarted._compact_async()
    assert snapshot_on_disk(wal.path)[1] == encoded(restarted._objects)
    _recovers_everything(wal.path, restarted)
    restarted.wal.close()


def test_tmp_left_behind_is_swept_and_never_read(tmp_path):
    server, wal = open_store(tmp_path / "d")
    History(server, 6).run(80)
    wal.close()
    for suffix in (".snapshot.json.tmp", ".wal.tmp"):
        with open(wal.path + suffix, "w") as f:
            f.write('{"rv": 999999, "objects": {}}')
    _recovers_everything(wal.path, server)
    reopened = WriteAheadLog(wal.path, fsync=False)
    assert reopened.swept_tmp_files == 2
    _recovers_everything(wal.path, server)
    reopened.close()


def test_orphaned_child_writes_nothing(tmp_path):
    """A fold whose parent went away (the benchmark SIGKILLs its apiserver
    at the end of every run) leaves the `.tmp` path to whoever comes next."""
    server, wal = open_store(tmp_path / "d")
    History(server, 8).run(40)
    cut = wal.cut()
    code, started = str(tmp_path / "code"), str(tmp_path / "started")
    fold_slowly = (
        "import sys, time\n"
        "from kubernetes_tpu.runtime import walfold\n"
        "real = walfold.fold\n"
        "def slow(prefix, cut):\n"
        "    open(sys.argv[4], 'w').close()\n"
        "    time.sleep(1.0)\n"
        "    return real(prefix, cut)\n"
        "walfold.fold = slow\n"
        "rc = walfold.main(sys.argv[1:3])\n"
        "open(sys.argv[3], 'w').write(str(rc))\n"
    )
    # a parent that starts the fold and exits while it runs
    leave_it_behind = (
        "import os, subprocess, sys, time\n"
        "subprocess.Popen([sys.executable, '-c', sys.argv[1]] + sys.argv[2:],\n"
        "                 stderr=subprocess.DEVNULL)\n"
        "while not os.path.exists(sys.argv[5]):\n"
        "    time.sleep(0.01)\n"
    )
    subprocess.run(
        [sys.executable, "-c", leave_it_behind, fold_slowly,
         wal.path, str(cut.offset), code, started],
        cwd=REPO, check=True, timeout=60,
    )
    assert wait_until(lambda: os.path.exists(code), 30)
    assert open(code).read() == str(walfold.EXIT_ORPHANED)
    assert not os.path.exists(wal.snap_path + ".tmp")
    wal.close()


# -- (c) writers run through it -----------------------------------------------


class RecordingLock:
    """The `store` lock, remembering which threads took it."""

    def __init__(self, inner):
        self.inner = inner
        self.takers = set()

    def acquire(self, *a, **kw):
        ok = self.inner.acquire(*a, **kw)
        if ok:
            self.takers.add(threading.current_thread().name)
        return ok

    def release(self):
        self.inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()


def test_compaction_under_writers_never_takes_the_store_lock_nor_parses_the_log(
    tmp_path, monkeypatch
):
    n_before = 20_000
    server, wal = open_store(tmp_path / "d", compact_every=n_before)
    server._lock = RecordingLock(server._lock)
    parsed = [0]
    real_parse = wal_mod.parse_wal_line

    def counting_parse(line):
        parsed[0] += 1
        return real_parse(line)

    monkeypatch.setattr(wal_mod, "parse_wal_line", counting_parse)
    publish0 = passes("wal_compact_publish")
    copies0 = passes("wal_compact_copy")
    files0 = metrics.counter("wal_compactions_total", {"how": "files"})
    stop = threading.Event()
    created = [0, 0, 0, 0]

    def writer(i):
        n = 0
        while not stop.is_set():
            server.create("pods", make_pod(f"w{i}-{n}", "other"))
            n += 1
            created[i] = n

    for i in range(n_before - 1):
        server.create("pods", make_pod(f"p{i}"))
    assert not server._compacting.is_set()
    writers = [threading.Thread(target=writer, args=(i,), name=f"writer-{i}")
               for i in range(len(created))]
    for t in writers:
        t.start()  # the first of their creates is the 20,000th record
    try:
        assert wait_until(
            lambda: metrics.counter("wal_compactions_total", {"how": "files"})
            > files0, 120, 0.005,
        ), "no compaction from the files"
        during = sum(created)
    finally:
        stop.set()
        for t in writers:
            t.join(30)
    assert not any(t.is_alive() for t in writers)
    assert during > 0, "no write was acknowledged while the fold ran"
    assert "wal-compact" not in server._lock.takers
    assert {f"writer-{i}" for i in range(len(created))} <= server._lock.takers
    assert parsed[0] == 0, "the publish parsed the log"
    assert passes("wal_compact_copy") == copies0
    n_pub, sum_pub = passes("wal_compact_publish")
    assert n_pub == publish0[0] + 1
    # the hold is the tail's, not the log's: the log is ~7 MB here, and
    # re-parsing it took 0.2 s on its own
    assert sum_pub - publish0[1] < 0.2
    rv_snap, objs = snapshot_on_disk(wal.path)
    assert n_before <= rv_snap < server.resource_version
    assert len(objs["pods"]) == rv_snap
    # the log is the tail: exactly the records past the snapshot
    with open(wal.log_path, encoding="utf-8") as f:
        tail = [real_parse(line.rstrip("\n")) for line in f]
    assert [r["rv"] for r in tail] == list(
        range(rv_snap + 1, server.resource_version + 1))
    assert wal._since_compact == len(tail)
    _recovers_everything(wal.path, server)
    wal.close()


# -- (d) a cut overtaken ------------------------------------------------------


def test_cut_overtaken_by_write_snapshot_publishes_nothing(tmp_path):
    server, wal = open_store(tmp_path / "d")
    History(server, 9).run(100)
    cut = wal.cut()
    wal.fold(cut)
    assert os.path.exists(wal.snap_path + ".tmp")
    # a backup restore / a follower's snapshot install rewrites both files
    server.create("pods", make_pod("later"))
    wal.write_snapshot(
        server.resource_version,
        {k: list(d.values()) for k, d in server._objects.items()},
    )
    assert wal.cut().generation == cut.generation + 1
    snap_then = open(wal.snap_path).read()
    server.create("pods", make_pod("later-still"))
    log_then = open(wal.log_path).read()
    assert wal.publish(cut) is False
    assert open(wal.snap_path).read() == snap_then
    assert open(wal.log_path).read() == log_then
    assert not os.path.exists(wal.snap_path + ".tmp")
    _recovers_everything(wal.path, server)
    # the store's own compaction counts nothing for it
    total0 = metrics.counter("wal_compactions_total", {"how": "files"})
    real_fold = wal.fold

    def fold_then_overtaken(c):
        real_fold(c)
        wal.write_snapshot(
            server.resource_version,
            {k: list(d.values()) for k, d in server._objects.items()},
        )

    wal.fold = fold_then_overtaken
    server._compact_async()
    assert metrics.counter("wal_compactions_total", {"how": "files"}) == total0
    _recovers_everything(wal.path, server)
    wal.close()


def test_closed_or_poisoned_wal_gives_no_cut(tmp_path):
    server, wal = open_store(tmp_path / "d")
    server.create("pods", make_pod("p"))
    cut = wal.cut()
    assert cut.offset == os.path.getsize(wal.log_path) and cut.rv == 1
    wal.fold(cut)
    wal.close()
    assert wal.cut() is None
    assert wal.publish(cut) is False  # shut down mid-compaction
    assert not os.path.exists(wal.snap_path)
    server._compact_async()  # nothing to compact into, nothing raised
    assert not server._compacting.is_set()


# -- (e) damage before the cut ------------------------------------------------


@pytest.mark.parametrize("damage", ["bit-flip", "snapshot", "rv-mismatch"])
def test_damage_before_the_cut_compacts_from_memory_once_and_heals(tmp_path, damage):
    server, wal = open_store(tmp_path / "d", native=False)
    history = History(server, 10)
    history.run(60)
    if damage == "snapshot":
        server._compact_async()
        history.run(30)
        with open(wal.snap_path, "r+") as f:
            f.seek(20)
            f.write("\x00garbage")
    elif damage == "bit-flip":
        bit_flip_record(wal.log_path, 5)
    else:
        # a log whose prefix is not what this process acknowledged
        wal._last_rv += 1
    with pytest.raises(LogDamaged):
        wal.fold(wal.cut())
    if damage == "rv-mismatch":
        wal._last_rv = None  # a restarted process: not held to an rv
        wal.fold(wal.cut())
        wal._last_rv = server.resource_version + 1
    memory0 = metrics.counter("wal_compactions_total", {"how": "memory"})
    files0 = metrics.counter("wal_compactions_total", {"how": "files"})
    fails0 = metrics.counter("wal_compaction_failures_total")
    server._compact_async()
    assert metrics.counter("wal_compactions_total", {"how": "memory"}) == memory0 + 1
    assert metrics.counter("wal_compactions_total", {"how": "files"}) == files0
    assert metrics.counter("wal_compaction_failures_total") == fails0
    # healed: both files whole again, nothing lost, no damage left to find
    assert snapshot_on_disk(wal.path)[1] == encoded(server._objects)
    assert os.path.getsize(wal.log_path) == 0
    _recovers_everything(wal.path, server)
    # once: the next compaction is from the files again
    history.run(30)
    server._compact_async()
    assert metrics.counter("wal_compactions_total", {"how": "files"}) == files0 + 1
    assert metrics.counter("wal_compactions_total", {"how": "memory"}) == memory0 + 1
    assert snapshot_on_disk(wal.path)[1] == encoded(server._objects)
    wal.close()


def test_fold_failure_that_is_not_damage_is_an_io_error(tmp_path):
    server, wal = open_store(tmp_path / "d")
    server.create("pods", make_pod("p"))
    cut = wal.cut()
    os.rename(wal.log_path, wal.log_path + ".gone")  # the child cannot open it
    with pytest.raises(FoldFailed) as e:
        wal.fold(cut)
    assert "FileNotFoundError" in str(e.value)
    os.rename(wal.log_path + ".gone", wal.log_path)
    wal.close()


# -- moved here from test_chaos_disk.py / test_kubelet_and_wal.py --------------


def test_compaction_failure_backs_off_then_recovers(tmp_path, monkeypatch):
    server, wal = open_store(tmp_path / "d", compact_every=3, native=False)
    real_fold = wal.fold
    fails0 = metrics.counter("wal_compaction_failures_total")

    def exploding_fold(cut):
        raise OSError("simulated snapshot I/O error")

    monkeypatch.setattr(wal, "fold", exploding_fold)
    for i in range(4):
        server.create("pods", make_pod(f"p{i}"))
    # the failed compaction must clear the in-flight flag (no wedge)...
    assert wait_until(lambda: not server._compacting.is_set(), 10)
    assert wait_until(
        lambda: metrics.counter("wal_compaction_failures_total") > fails0, 10
    )
    assert server._compact_backoff_until > time.monotonic(), (
        "failure must arm backoff, not retry hot"
    )
    # ...and the append path kept working throughout
    server.create("pods", make_pod("during-backoff"))
    # past the backoff with a healthy disk, the next write compacts
    monkeypatch.setattr(wal, "fold", real_fold)
    server._compact_backoff_until = 0.0
    server.create("pods", make_pod("trigger"))
    assert wait_until(
        lambda: os.path.exists(wal.snap_path), 10
    ), "compaction never recovered after the backoff"
    assert wait_until(lambda: not server._compacting.is_set(), 10)
    assert server._compact_failures == 0
    wal.close()


def test_wal_recover_races_live_compaction(tmp_path):
    """Regression: a reader whose snapshot read lands before a compaction
    publish and whose log read lands after that compaction's log rewrite
    silently lost the records in between (observed as 14/25 pods). The
    staleness re-check must compare against the LOADED snapshot's rv —
    replayed tail records can push the recovered rv past the new
    snapshot's rv and mask the stale read. The publish of a fold keeps
    write_snapshot's order (snapshot, then log), so the re-check holds
    for it as it did."""
    for trial in range(15):
        path = str(tmp_path / f"c{trial}")
        wal = WriteAheadLog(path, compact_every=10, fsync=False)
        server = APIServer(wal=wal)
        for i in range(25):
            server.create("pods", make_pod(f"p{i}"))
            if i == 12:
                # let the first compaction land mid-stream (a fold is a
                # process: ~0.1 s, where the copy took a millisecond)
                wait_until(lambda: os.path.exists(wal.snap_path), 10, 0.002)
        server._maybe_compact()  # second compaction races the recover below
        # recover at every stage of it: before the fold ends, around the
        # snapshot's replace, around the log's (from the files alone: a
        # second WriteAheadLog on the path would sweep the fold's .tmp)
        while True:
            racing = server._compacting.is_set()
            report = WriteAheadLog.recover_report(path)
            lost = 25 - len(report.objects["pods"])
            assert lost == 0, f"trial {trial}: lost {lost} records"
            if not racing:
                break
        wal.close()
        recovered = APIServer.recover(path)
        pods, _ = recovered.list("pods")
        assert len(pods) == 25
        recovered.wal.close()
