"""The one general load generator and the arithmetic of a window.

Open loop: every pod has an instant at which its create is DUE, fixed
before the window opens, and is timed from that instant whether or not
the generator or the server kept up. The arrival law's parameters come
from the traffic file; `--seed` only orders them.
"""

from __future__ import annotations

import math
import random
import threading
import time


def schedule(rate_per_s: float, seconds: float, seed: int,
             law: str = "poisson") -> list:
    """Offsets (seconds from the window's start) at which creates are due.

    `poisson`: the n = floor(rate * seconds) gaps are the exponential
    law's n stratified quantiles, the same SET for every seed, in an
    order drawn from the seed: every seed offers the same work with the
    same bursts and lulls, at other instants. `uniform`: equal gaps.
    All n fall inside the window."""
    n = int(math.floor(rate_per_s * seconds + 1e-9))
    if n <= 0:
        return []
    if law == "uniform":
        return [(i + 0.5) / rate_per_s for i in range(n)]
    if law != "poisson":
        raise ValueError(f"unknown arrival law {law!r}")
    gaps = [-math.log1p(-(i + 0.5) / n) / rate_per_s for i in range(n)]
    random.Random(seed).shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g
        out.append(t)
    return out


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    s = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return s[min(k, len(s)) - 1]


class Window:
    """What the generator and the watch saw of one window's pods."""

    def __init__(self, t0: float, seconds: float, due: list, keys: list):
        self.t0, self.seconds = t0, seconds
        self.due, self.keys = due, keys
        self.t_sent = [None] * len(due)   # monotonic, when the POST left
        self.acked = [False] * len(due)   # the server acknowledged it


def send_open_loop(window: Window, post, senders: int) -> None:
    """Send window.keys[i] at t0 + due[i] from `senders` threads;
    `post(i)` returns True when the create was acknowledged. Returns when
    every create was sent."""
    lock = threading.Lock()
    nxt = [0]

    def worker():
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= len(window.due):
                return
            wait = window.t0 + window.due[i] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            window.t_sent[i] = time.monotonic()
            window.acked[i] = bool(post(i))

    threads = [threading.Thread(target=worker, daemon=True, name=f"send-{k}")
               for k in range(senders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def latencies_ms(window: Window, t_bound: dict, t_gave_up: float) -> list:
    """Create-to-bound of every pod of the window, in the order they were
    due: watch saw it bound - create DUE; a pod never bound counts up to
    `t_gave_up`, the end of the drain."""
    out = []
    for due, key in zip(window.due, window.keys):
        due_at = window.t0 + due
        tb = t_bound.get(key)
        out.append(((max(t_gave_up, due_at) if tb is None else tb) - due_at)
                   * 1e3)
    return out


def window_stats(window: Window, t_bound: dict, t_gave_up: float,
                 lat_ms: list | None = None) -> dict:
    """The client's side of one window. `t_bound`: key -> monotonic time
    the watch saw the pod bound. `t_gave_up`: when the drain ended; a pod
    unbound by then is a failure and its latency counts up to there.
    `lat_ms`: latencies_ms() of the same arguments, if already taken."""
    t_end = window.t0 + window.seconds
    n = len(window.due)
    if lat_ms is None:
        lat_ms = latencies_ms(window, t_bound, t_gave_up)
    t_seen = [t_bound.get(key) for key in window.keys]
    failed = t_seen.count(None)
    bound_in_window = sum(1 for t in t_seen if t is not None and t <= t_end)
    lag_ms = [(sent - (window.t0 + due)) * 1e3
              for sent, due in zip(window.t_sent, window.due)
              if sent is not None]
    out = {
        "attempted": n,
        "failed": failed,
        "refused_creates": sum(
            1 for i in range(n)
            if window.t_sent[i] is not None and not window.acked[i]),
        "bound_in_window": bound_in_window,
        "bound_pods_per_s": bound_in_window / window.seconds,
        "pending_at_end": n - bound_in_window,
    }
    if n:
        out.update(
            create_to_bound_mean_ms=sum(lat_ms) / n,
            create_to_bound_p50_ms=percentile(lat_ms, 50),
            create_to_bound_p90_ms=percentile(lat_ms, 90),
            create_to_bound_p95_ms=percentile(lat_ms, 95),
            create_to_bound_p99_ms=percentile(lat_ms, 99),
            create_to_bound_max_ms=max(lat_ms),
            bound_share=100.0 * bound_in_window / n,
        )
    if lag_ms:
        out.update(
            loadgen_lag_p50_ms=percentile(lag_ms, 50),
            loadgen_lag_p99_ms=percentile(lag_ms, 99),
        )
    return out
