"""The benchmark's launcher of the ONE process that holds the chip.

It calls `kubernetes_tpu.cmd.scheduler.main(argv)` unchanged in the main
thread. A side thread reads one-line commands from stdin:

  trace-start <dir> [<max_launches>]
                      jax.profiler.start_trace(<dir>). The trace ends ONCE,
                      at `trace-stop` or after <max_launches> launches of
                      the wave kernel counted since the start (0 or absent:
                      no cap), whichever comes first
  trace-stop          ends the trace, if the cap has not
  memstats <path>     the devices as JAX reports them and their peak bytes,
                      written to <path> as JSON
  (stdin closes)      the scheduler is interrupted and the process exits:
                      it can never outlive its supervisor

A trace is ended on a thread of its own (TraceSession), never on the
command thread: the profiler can take minutes to write a trace that holds
thousands of launches, and `memstats` has to be answered meanwhile. It
writes <dir>/stopping before it calls jax.profiler.stop_trace() and
<dir>/stopped when that has returned, both JSON: `window_s` (start_trace
returned -> the stop was decided, this process's clock), `launches` (the
program's own count over that span), `stopped_by` ("span" or
"launches"), and in `stopped` also `stop_s`, what stop_trace took.

That is all it adds: a trace and a memory reading from outside the
program, around the call into it. `--fault <name>` (tests and control
runs only; run.py never passes it) breaks the bind call underneath, so
that the checks can be seen to fail.
"""

from __future__ import annotations

import functools
import json
import os
import signal
import sys
import threading
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _memstats(path: str) -> None:
    import jax

    devs = jax.devices()
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    out = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "peak_bytes": peaks,
    }
    _write_json(path, out)


def _launches() -> float:
    """The program's own count of wave-kernel launches (the counter that
    `pods_per_wave` divides by): one short hold of the registry's lock,
    as any scrape takes; nothing of the loop thread is touched."""
    from kubernetes_tpu.utils.metrics import metrics

    return metrics.counter("scheduler_wave_batches_total")


def _start_trace(trace_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # device + XLA host events only
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


def _write_json(path: str, obj: dict) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


class TraceSession:
    """One device trace. `start()` starts it and a watcher thread;
    `ask_stop()` is the `trace-stop` command. The watcher alone ends the
    trace, so it ends once: by "span" when asked to, by "launches" when
    `max_launches` have been counted since the start, whichever it sees
    first. `done` is set when <dir>/stopped is written."""

    POLL_S = 0.02

    def __init__(self, trace_dir: str, max_launches: int = 0,
                 launches=_launches, start_trace=_start_trace,
                 stop_trace=_stop_trace, clock=time.monotonic):
        self.trace_dir, self.max_launches = trace_dir, max_launches
        self._launches, self._clock = launches, clock
        self._start_trace, self._stop_trace = start_trace, stop_trace
        self._asked = threading.Event()
        self.done = threading.Event()

    def start(self) -> None:
        self._start_trace(self.trace_dir)
        self._t0, self._base = self._clock(), self._launches()
        threading.Thread(target=self._watch, daemon=True,
                         name="trace-watch").start()

    def ask_stop(self) -> None:
        self._asked.set()

    def _watch(self) -> None:
        by = "span"
        while not self._asked.wait(self.POLL_S):
            if (self.max_launches > 0 and
                    self._launches() - self._base >= self.max_launches):
                by = "launches"
                break
        try:
            self._end(by)
        except Exception as e:  # a boundary: the supervisor names the trace
            print(f"sched_child: ending the trace failed: {e!r}",
                  file=sys.stderr, flush=True)

    def _end(self, by: str) -> None:
        # the length of the traced window, on this process's clock
        record = {"window_s": self._clock() - self._t0,
                  "launches": int(self._launches() - self._base),
                  "stopped_by": by}
        _write_json(os.path.join(self.trace_dir, "stopping"), record)
        t = self._clock()
        self._stop_trace()
        record["stop_s"] = self._clock() - t
        _write_json(os.path.join(self.trace_dir, "stopped"), record)
        self.done.set()


def _commands(lines, new_session=TraceSession, memstats=_memstats) -> None:
    """Serve the commands of `lines` (stdin) until it closes."""
    session = None
    for line in lines:
        words = line.split()
        if not words:
            continue
        try:
            if words[0] == "trace-start":
                if session is not None and not session.done.is_set():
                    raise RuntimeError("the trace before it has not stopped")
                cap = int(words[2]) if len(words) > 2 else 0
                started = new_session(words[1], cap)
                started.start()
                session = started
            elif words[0] == "trace-stop" and session is not None:
                session.ask_stop()
            elif words[0] == "memstats":
                memstats(words[1])
        except Exception as e:  # a boundary that must keep reading
            print(f"sched_child: command {words} failed: {e!r}",
                  file=sys.stderr, flush=True)


def _serve_stdin(new_session) -> None:
    _commands(sys.stdin, new_session)
    # stdin closed: the supervisor is done with us, or gone
    os.kill(os.getpid(), signal.SIGINT)
    time.sleep(15)
    os._exit(0)


def _plant_fault(name: str) -> None:
    """Break the scheduler's bind call, or switch its own host path on
    (tests and control runs)."""
    from kubernetes_tpu.apiserver import client as c

    if name == "host-lane":
        # the program's own path off the device, switched on: batches of
        # up to 32 pods go down the per-pod host chain at any cluster size
        from kubernetes_tpu.scheduler import config as cfg_mod

        real_init = cfg_mod.KubeSchedulerConfiguration.__init__

        def init(self, *a, **kw):
            real_init(self, *a, **kw)
            self.small_batch_host_max = 32
            self.small_batch_host_node_max = 10**9

        cfg_mod.KubeSchedulerConfiguration.__init__ = init
        return

    real_many, real_one = c.RESTClient.bind_pods, c.RESTClient.bind_pod
    seen = [0]

    def altered(b):
        import dataclasses

        return dataclasses.replace(b, target_node="node-0")

    if name == "alter-bind":
        # every answer altered where it is produced: all on one node
        def bind_pods(self, bindings, fence=None):
            return real_many(self, [altered(b) for b in bindings], fence)

        def bind_pod(self, binding, fence=None):
            return real_one(self, altered(binding), fence)
    elif name in ("drop-half", "no-bind"):
        # every second answer (or every one) never sent, reported as sent
        def keep(_b) -> bool:
            seen[0] += 1
            return name == "drop-half" and seen[0] % 2 == 0

        def bind_pods(self, bindings, fence=None):
            kept = [b for b in bindings if keep(b)]
            errs = iter(real_many(self, kept, fence))
            return [next(errs) if b in kept else None for b in bindings]

        def bind_pod(self, binding, fence=None):
            return real_one(self, binding, fence) if keep(binding) else None
    else:
        raise SystemExit(f"sched_child: unknown fault {name!r}")
    c.RESTClient.bind_pods, c.RESTClient.bind_pod = bind_pods, bind_pod


def main(argv: list) -> int:
    sys.path.insert(0, CHECKOUT)
    fault = None
    if "--fault" in argv:
        i = argv.index("--fault")
        fault = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    from kubernetes_tpu.cmd import scheduler

    new_session = TraceSession
    if fault == "slow-trace-stop":
        # the profiler that never finishes writing: the supervisor's
        # deadline for the trace has to end the run, with the trace named
        new_session = functools.partial(
            TraceSession, stop_trace=lambda: time.sleep(3600))
    elif fault:
        _plant_fault(fault)
    threading.Thread(target=_serve_stdin, args=(new_session,), daemon=True,
                     name="commands").start()
    return scheduler.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
