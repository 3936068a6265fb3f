"""The benchmark's launcher of the ONE process that holds the chip.

It calls `kubernetes_tpu.cmd.scheduler.main(argv)` unchanged in the main
thread. A side thread reads one-line commands from stdin:

  trace-start <dir>   jax.profiler.start_trace(<dir>)
  trace-stop          jax.profiler.stop_trace(), then <dir>/stopped is written
                      (JSON: the length of the traced window, this clock)
  memstats <path>     the devices as JAX reports them and their peak bytes,
                      written to <path> as JSON
  (stdin closes)      the scheduler is interrupted and the process exits:
                      it can never outlive its supervisor

That is all it adds: a trace and a memory reading from outside the
program, around the call into it. `--fault <name>` (tests and control
runs only; run.py never passes it) breaks the bind call underneath, so
that the checks can be seen to fail.
"""

from __future__ import annotations

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
    with open(path + ".tmp", "w") as f:
        json.dump(out, f)
    os.replace(path + ".tmp", path)


def _commands() -> None:
    import jax

    trace_dir, t_started = None, 0.0
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        try:
            if words[0] == "trace-start" and trace_dir is None:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0  # device + XLA host events only
                opts.host_tracer_level = 2
                jax.profiler.start_trace(words[1], profiler_options=opts)
                trace_dir, t_started = words[1], time.monotonic()
            elif words[0] == "trace-stop" and trace_dir is not None:
                window_s = time.monotonic() - t_started
                jax.profiler.stop_trace()
                # the length of the traced window, on this process's clock
                with open(os.path.join(trace_dir, "stopped.tmp"), "w") as f:
                    json.dump({"window_s": window_s}, f)
                os.replace(os.path.join(trace_dir, "stopped.tmp"),
                           os.path.join(trace_dir, "stopped"))
                trace_dir = None
            elif words[0] == "memstats":
                _memstats(words[1])
        except Exception as e:  # a boundary that must keep reading
            print(f"sched_child: command {words} failed: {e!r}",
                  file=sys.stderr, flush=True)
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

    if fault:
        _plant_fault(fault)
    threading.Thread(target=_commands, daemon=True, name="commands").start()
    return scheduler.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
