"""Same-process serving-tier A/Bs.

Two experiments, each against one live in-process REST apiserver:

  A. **Bind RTT under concurrency: transport arms.** 8 client threads
     each drive sequential bind POSTs:
       legacy   — the pre-PR wire path: one urllib request per bind, a
                  fresh TCP connect every time (byte-for-byte what
                  RESTClient._request did before the pool: connect +
                  accept + a server thread spawned PER REQUEST);
       connect  — the new transport minus the pool (pool_connections=0:
                  fresh no-delay connection per request);
       pooled   — the new default (persistent keep-alive pool).
     The ISSUE-14 acceptance compares `legacy` (per-request connect as
     actually shipped) against `pooled`; the `connect` arm isolates
     reuse from the rest of the transport work. Concurrency is the
     honest regime for a serving tier — single-threaded loopback hides
     the accept/thread-spawn churn that per-request connections cost a
     threaded server.

  B. **Watch fan-out codec.** N real REST watch streams against one
     server, an event storm flows, and WIRE-LEVEL delivered events/s is
     measured (frames/lines counted and skipped, no client-side object
     materialization — the drains run in the measuring process, and
     decoding there would bill the server's fan-out win to the GIL):
     newline-JSON (per-delivery `codec.encode`+`json.dumps` in every
     stream thread) vs the negotiated length-prefixed binary codec (ONE
     memoized frame per event shared across every stream).

Usage: JAX_PLATFORMS=cpu python scripts/serving_overhead_ab.py
"""

from __future__ import annotations

import json
import os
import struct
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubernetes_tpu.api import serialization as codec  # noqa: E402
from kubernetes_tpu.api.objects import (  # noqa: E402
    Binding,
    Container,
    Node,
    NodeSpec,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
)
from kubernetes_tpu.apiserver.client import RESTClient  # noqa: E402
from kubernetes_tpu.apiserver.rest import serve  # noqa: E402
from kubernetes_tpu.apiserver.watchcodec import (  # noqa: E402
    WATCH_CONTENT_TYPE,
)

_HDR = struct.Struct(">cI")


def make_pod(name):
    return Pod(
        metadata=ObjectMeta(name=name, namespace="default"),
        spec=PodSpec(containers=[Container(requests={"cpu": "1m"})]),
    )


def pct(lat, q):
    if not lat:
        return 0.0
    return lat[min(int(q * len(lat)), len(lat) - 1)] * 1e3


# -- A: bind RTT --------------------------------------------------------------


def _legacy_bind(base_url: str, binding) -> None:
    """The pre-PR wire path: urllib, fresh connection per request."""
    req = urllib.request.Request(
        base_url
        + f"/api/v1/namespaces/{binding.pod_namespace}/pods/"
        + f"{binding.pod_name}/binding",
        data=json.dumps(codec.encode(binding)).encode(),
        method="POST",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30.0) as resp:
        resp.read()


def _run_arm(url, tag, bind_factory, seed_client, nthreads=8, per=60):
    names = [
        [f"{tag}-{t}-{i}" for i in range(per)] for t in range(nthreads)
    ]
    for row in names:
        for n in row:
            seed_client.create("pods", make_pod(n))
    lats: list = []
    lock = threading.Lock()

    def worker(t):
        bind = bind_factory()
        mine = []
        for n in names[t]:
            b = Binding(
                pod_name=n, pod_namespace="default", target_node="ab-n1"
            )
            t0 = time.perf_counter()
            bind(b)
            mine.append(time.perf_counter() - t0)
        with lock:
            lats.extend(mine)

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(nthreads)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lats.sort()
    return {
        "arm": tag,
        "threads": nthreads,
        "binds": len(lats),
        "p50_ms": round(pct(lats, 0.5), 3),
        "p99_ms": round(pct(lats, 0.99), 3),
        "binds_per_s": round(len(lats) / wall, 1) if wall else 0.0,
    }


def run_bind_ab(nthreads: int = 8, per: int = 60) -> list:
    srv, port, store = serve(port=0, bookmark_period_s=30.0)
    url = f"http://127.0.0.1:{port}"
    store.create(
        "nodes",
        Node(
            metadata=ObjectMeta(name="ab-n1", namespace=""),
            spec=NodeSpec(),
            status=NodeStatus(
                allocatable={"cpu": "999", "memory": "9Ti", "pods": 99999}
            ),
        ),
    )
    seed = RESTClient(url, timeout=30.0)
    # warmup: server thread pool + codec caches, discarded
    _run_arm(
        url, "warmup", lambda: (lambda b: seed.bind_pods([b])), seed,
        nthreads=4, per=15,
    )
    rows = [
        _run_arm(
            url, "legacy", lambda: (lambda b: _legacy_bind(url, b)), seed,
            nthreads=nthreads, per=per,
        ),
        _run_arm(
            url,
            "connect",
            lambda: (
                lambda c=RESTClient(url, timeout=30.0, pool_connections=0):
                lambda b: c.bind_pods([b])
            )(),
            seed,
            nthreads=nthreads,
            per=per,
        ),
        _run_arm(
            url,
            "pooled",
            lambda: (
                lambda c=RESTClient(url, timeout=30.0):
                lambda b: c.bind_pods([b])
            )(),
            seed,
            nthreads=nthreads,
            per=per,
        ),
    ]
    seed.close()
    srv.shutdown()
    legacy_p50, pooled_p50 = rows[0]["p50_ms"], rows[2]["p50_ms"]
    connect_p50 = rows[1]["p50_ms"]
    rows.append(
        {
            "arm": "cuts",
            "pooled_vs_legacy_p50_pct": round(
                100.0 * (1 - pooled_p50 / legacy_p50), 1
            )
            if legacy_p50
            else 0.0,
            "pooled_vs_connect_p50_pct": round(
                100.0 * (1 - pooled_p50 / connect_p50), 1
            )
            if connect_p50
            else 0.0,
        }
    )
    return rows


# -- B: watch fan-out codec ---------------------------------------------------


def _open_stream(port: int, binary: bool):
    headers = {"Accept": WATCH_CONTENT_TYPE} if binary else {}
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1/pods?watch=1&resourceVersion=0",
        headers=headers,
    )
    return urllib.request.urlopen(req, timeout=30.0)


def run_codec_ab(n_streams: int = 64, n_events: int = 400) -> list:
    rows = []
    for binary in (False, True):
        srv, port, store = serve(port=0, bookmark_period_s=30.0)
        streams = [_open_stream(port, binary) for _ in range(n_streams)]
        counts = [0] * n_streams
        wire_bytes = [0] * n_streams
        stop = threading.Event()

        def drain(idx, resp):
            # wire-level drain: count + skip, never materialize objects
            try:
                if binary:
                    while not stop.is_set():
                        head = resp.read(_HDR.size)
                        if len(head) < _HDR.size:
                            return
                        code, length = _HDR.unpack(head)
                        resp.read(length)
                        wire_bytes[idx] += _HDR.size + length
                        if code not in (b"B", b"T"):  # T: a commit instant
                            counts[idx] += 1
                else:
                    for line in resp:
                        if stop.is_set():
                            return
                        if not line.strip():
                            continue
                        wire_bytes[idx] += len(line)
                        if b'"BOOKMARK"' not in line[:24]:
                            counts[idx] += 1
            except Exception:
                pass

        threads = [
            threading.Thread(target=drain, args=(i, r), daemon=True)
            for i, r in enumerate(streams)
        ]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        for i in range(n_events):
            store.create("pods", make_pod(f"ev-{i}"))
        target = n_streams * n_events
        deadline = time.monotonic() + 120.0
        while sum(counts) < target and time.monotonic() < deadline:
            time.sleep(0.005)
        duration = time.perf_counter() - t0
        stop.set()
        srv.shutdown()
        delivered = sum(counts)
        rows.append(
            {
                "arm": "binary" if binary else "json",
                "streams": n_streams,
                "events": n_events,
                "delivered": delivered,
                "wire_mb": round(sum(wire_bytes) / 1e6, 2),
                "duration_s": round(duration, 3),
                "deliveries_per_s": round(delivered / duration, 1)
                if duration
                else 0.0,
            }
        )
    if rows[0]["deliveries_per_s"]:
        rows.append(
            {
                "arm": "binary-vs-json",
                "speedup_x": round(
                    rows[1]["deliveries_per_s"]
                    / rows[0]["deliveries_per_s"],
                    2,
                ),
                "wire_size_ratio": round(
                    rows[0]["wire_mb"] / rows[1]["wire_mb"], 2
                )
                if rows[1]["wire_mb"]
                else 0.0,
            }
        )
    return rows


def main() -> int:
    out = {
        "bind_rtt": run_bind_ab(),
        "watch_codec": run_codec_ab(),
    }
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
