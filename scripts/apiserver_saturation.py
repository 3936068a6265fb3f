#!/usr/bin/env python3
"""What ONE apiserver process sustains, without a scheduler and without a
chip: the lock or the interpreter?

Starts `python -m kubernetes_tpu.cmd.apiserver --data-dir <tmp>` (WAL +
fsync, as the benchmark does) and drives it with the benchmark's own
client (benchmark/harness/rest.py): N closed-loop senders each create
the measured pod of a benchmark configuration, one after another; two
pod watches hold a stream each (the scheduler's and the client's, in a
cell); one binder sends what was created as BindingLists of at most 256
with one request in flight, as the scheduler has done since PR 28. Two
scrapes of /metrics and two reads of /proc/<pid>/stat bracket the window.

  python scripts/apiserver_saturation.py [--senders 64] [--seconds 20]
      [--config perf5k-podaffinity] [--across-compaction [--after 10]]

Prints one JSON line: pods created and seen bound a second, the child's
user + sys cores, the store's lock wait per op and its stage means, WAL
records a fsync, and the binding request as the binder saw it. A child at
~1.0 core whatever N is a saturated interpreter (one GIL), and the lock
wait is its queue; a child well under a core with a long lock wait is the
lock. CPU only: a reading aid for PERF.md, not part of the benchmark, and
no number of it is a device metric.

`--across-compaction` drives the same load until the WAL's 50,000th record
has made the server compact (`cluster.snapshot.json` appears in its data
directory) and `--after` seconds more, `--seconds` at most: the line then
also holds the writes acknowledged in each second of the window (a create,
or each binding of a binding request), the second in which the snapshot
appeared, and the longest gap between two acknowledgements with its second.
A compaction that stops the writers is a hole in that series.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import shutil
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness.children import http_get, start_serving  # noqa: E402
from harness.rest import BindWatch, Rest  # noqa: E402
from harness.scrape import Scrape  # noqa: E402
from readers import counter_ratio, hist_mean  # noqa: E402

BIND_CHUNK = 256  # RESTClient.bind_pods' chunk
NODES = 5000  # names only: a bind does not look its node up
COMMIT_STAGES = ("apply", "wal_append", "fsync", "notify")


def _cpu_seconds(pid: int) -> tuple:
    """(user, sys) seconds of the process so far, all its threads."""
    with open(f"/proc/{pid}/stat") as f:
        fields = f.read().rsplit(") ", 1)[1].split()
    tick = os.sysconf("SC_CLK_TCK")
    return int(fields[11]) / tick, int(fields[12]) / tick


def _ack_series(acks: list, t0: float, t1: float) -> dict:
    """Writes acknowledged in each whole second of [t0, t1), and the
    longest gap between two consecutive acknowledgements in it."""
    times = sorted(t for t, _ in acks if t0 <= t < t1)
    per_s = [0] * int(t1 - t0)
    for t, n in acks:
        if t0 <= t < t0 + len(per_s):
            per_s[int(t - t0)] += n
    gap, at = max(((b - a, a) for a, b in zip(times, times[1:])),
                  default=(0.0, t0))
    return {"acked_writes_per_s": per_s,
            "longest_ack_gap_ms": round(gap * 1e3, 1),
            "longest_ack_gap_at_s": round(at - t0, 2)}


def _rounded(x, digits: int):
    return None if x is None else round(x, digits)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--senders", type=int, default=64)
    ap.add_argument("--seconds", type=float, default=None,
                    help="20; with --across-compaction at most 180 "
                    "(50,000 records are ~60 s of this load)")
    ap.add_argument("--config", default="perf5k-podaffinity")
    ap.add_argument("--across-compaction", action="store_true")
    ap.add_argument("--after", type=float, default=10.0)
    args = ap.parse_args()
    seconds = args.seconds or (180.0 if args.across_compaction else 20.0)

    with open(os.path.join(ROOT, "benchmark", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    ns = config["namespace"]
    template = json.dumps(config["pod_templates"]["measured"]).replace(
        "$NAMESPACE", ns)
    pods_path = f"/api/v1/namespaces/{ns}/pods"

    work = tempfile.mkdtemp(prefix="apisat_")
    api = rest = None
    watches = []
    try:
        api, port = start_serving(
            "apiserver",
            lambda p: ["-m", "kubernetes_tpu.cmd.apiserver", "--port", str(p),
                       "--data-dir", os.path.join(work, "wal")],
            dict(os.environ, JAX_PLATFORMS="cpu"),
            os.path.join(work, "apiserver.log"), "/healthz", 60.0)
        rest = Rest(port)
        rest.warm(args.senders + 1, "/healthz")
        watches = [BindWatch(port), BindWatch(port)]
        for w in watches:
            if not w.opened.wait(30.0):
                raise SystemExit(f"a pod watch did not open: {w.errors}")

        created: queue.Queue = queue.Queue()
        stop = threading.Event()
        bind_ms, bind_sizes, bind_failed = [], [], [0]
        acks = []  # (instant, writes acknowledged by that reply)

        def sender(i: int) -> None:
            n = 0
            while not stop.is_set():
                name = f"s{i}-{n}"
                if rest.create(pods_path, template.replace(
                        "$NAME", name).encode()):
                    acks.append((time.monotonic(), 1))
                    created.put(name)
                n += 1

        def binder() -> None:
            k = 0
            while not stop.is_set():
                names = [created.get()]
                while len(names) < BIND_CHUNK:
                    try:
                        names.append(created.get_nowait())
                    except queue.Empty:
                        break
                names = [n for n in names if n is not None]
                if not names:
                    continue
                items = []
                for name in names:
                    items.append({"podName": name, "podNamespace": ns,
                                  "targetNode": f"node-{k % NODES}"})
                    k += 1
                t = time.monotonic()
                status, reply = rest.request(
                    "POST", "/api/v1/bindings",
                    json.dumps({"items": items}).encode())
                bind_ms.append((time.monotonic() - t) * 1e3)
                bind_sizes.append(len(items))
                ok = status == 200 and all(
                    it.get("status") == "Success"
                    for it in json.loads(reply)["items"])
                if ok:
                    acks.append((time.monotonic(), len(items)))
                else:
                    bind_failed[0] += 1

        threads = [threading.Thread(target=sender, args=(i,), daemon=True)
                   for i in range(args.senders)]
        threads.append(threading.Thread(target=binder, daemon=True))
        for t in threads:
            t.start()
        time.sleep(2.0)  # every plan built, every connection in use

        metrics_url = f"http://127.0.0.1:{port}/metrics"
        first = Scrape(http_get(metrics_url, timeout=30.0))
        cpu0, t0, bound0 = (_cpu_seconds(api.proc.pid), time.monotonic(),
                            len(watches[0].bound))
        n_bind0 = len(bind_ms)
        snapshot = os.path.join(work, "wal", "cluster.snapshot.json")
        snapshot_at = None
        if args.across_compaction:
            while (now := time.monotonic() - t0) < seconds:
                if snapshot_at is None and os.path.exists(snapshot):
                    snapshot_at = now
                if snapshot_at is not None and now >= snapshot_at + args.after:
                    break
                time.sleep(0.02)
        else:
            time.sleep(seconds)
        cpu1, t1, bound1 = (_cpu_seconds(api.proc.pid), time.monotonic(),
                            len(watches[0].bound))
        n_bind1 = len(bind_ms)
        pair = (first, Scrape(http_get(metrics_url, timeout=30.0)))
        stop.set()
        created.put(None)  # wakes the binder
        for t in threads:
            t.join(timeout=35.0)

        took = t1 - t0
        ctx = {"api": pair}

        def mean_ms(name: str, labels: dict):
            return _rounded(hist_mean.read(ctx, "api", name, labels, 1e3), 3)

        sizes = bind_sizes[n_bind0:n_bind1]
        result = {
            "config": args.config,
            "senders": args.senders,
            "seconds": round(took, 2),
            "bound_pods_per_s": round((bound1 - bound0) / took, 1),
            "apiserver_user_cores": round((cpu1[0] - cpu0[0]) / took, 3),
            "apiserver_sys_cores": round((cpu1[1] - cpu0[1]) / took, 3),
            "store_lock_wait_ms": {
                op: mean_ms("store_lock_wait_seconds", {"op": op})
                for op in ("create", "bind")},
            "store_stage_ms": {
                op: {st: mean_ms("store_commit_stage_seconds",
                                 {"op": op, "stage": st})
                     for st in COMMIT_STAGES}
                for op in ("create", "bind")},
            "api_create_ms": mean_ms("apiserver_request_duration_seconds",
                                     {"verb": "POST", "resource": "pods"}),
            "wal_records_per_fsync": _rounded(counter_ratio.read(
                ctx,
                {"source": "api", "name": "wal_records_appended_total"},
                {"source": "api", "name": "wal_fsyncs_total"}), 2),
            "binding_request_ms": round(
                sum(bind_ms[n_bind0:n_bind1]) / len(sizes), 1)
            if sizes else None,
            "bindings_per_request": round(sum(sizes) / len(sizes), 1)
            if sizes else None,
            "codec_plans_built_in_window": (
                pair[1].total("api_codec_plans_built_total")
                - pair[0].total("api_codec_plans_built_total")),
            "refused": len(rest.refused) + bind_failed[0],
            "watch_errors": [w.errors for w in watches if w.errors],
        }
        if args.across_compaction:
            result.update(_ack_series(acks, t0, t1), snapshot_at_s=_rounded(
                snapshot_at, 2), wal_records=pair[1].total(
                    "wal_records_appended_total"),
                compactions=pair[1].by_label("wal_compactions_total", "how"),
                background_pass_s={
                    task: round(v, 3) for task, v in pair[1].by_label(
                        "store_background_pass_seconds_sum", "task").items()})
        print(json.dumps(result))
        return 0 if not result["refused"] else 1
    finally:
        for w in watches:
            w.stop()
        if rest is not None:
            rest.close()
        if api is not None:
            api.stop()
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
