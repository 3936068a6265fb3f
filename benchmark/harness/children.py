"""The processes of a run, copied from chip_smoke.py's proven plumbing.

A child is started in a session of its own, its output goes to a log
file, and `stop()` does not return until the whole group is gone. Ports
are never fixed: a child that cannot bind its port is started once more
on a new one (`start_serving`).
"""

from __future__ import annotations

import atexit
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))

_LIVE: list = []  # every child not yet stopped, for the atexit sweep


class RunFailure(Exception):
    """The run has no result: no chip, a child that never came up."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(url: str, timeout: float = 5.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


class Child:
    def __init__(self, name: str, argv: list, env: dict, log_path: str,
                 stdin_pipe: bool = False):
        self.name, self.log_path = name, log_path
        self._log = open(log_path, "wb")
        self.t_start = time.monotonic()
        # faulthandler: SIGABRT makes a hung child print every thread's
        # stack into its log before it dies
        self.proc = subprocess.Popen(
            [sys.executable, "-X", "faulthandler", *argv],
            cwd=CHECKOUT,
            env=env,
            stdin=subprocess.PIPE if stdin_pipe else subprocess.DEVNULL,
            stdout=self._log,
            stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        _LIVE.append(self)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def command(self, line: str) -> None:
        """One line to the child's stdin (the scheduler launcher's side
        thread reads them)."""
        try:
            self.proc.stdin.write(line.encode() + b"\n")
            self.proc.stdin.flush()
        except (OSError, ValueError):
            pass  # a dead child is found by alive(), not here

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")

    def log_tail(self, n: int = 4000) -> str:
        # the CPU backend's AOT loader writes multi-KB warning lines
        lines = [ln[:400] for ln in self.log_text().splitlines()]
        return "\n".join(lines)[-n:]

    def stop(self, how: int | None = signal.SIGINT,
             grace_s: float = 20.0) -> None:
        """Ask (stdin EOF, and the signal `how` if given), wait, then kill
        the group; returns only when the process is gone."""
        if self.proc.stdin is not None:
            try:
                self.proc.stdin.close()
            except OSError:
                pass
        if self.proc.poll() is None:
            try:
                if how is not None:
                    os.killpg(self.proc.pid, how)
                self.proc.wait(timeout=grace_s)
            except (subprocess.TimeoutExpired, ProcessLookupError):
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)  # stragglers of the group
        except (ProcessLookupError, PermissionError):
            pass
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        if not self._log.closed:
            self._log.close()
        if self in _LIVE:
            _LIVE.remove(self)


@atexit.register
def _sweep() -> None:
    for c in list(_LIVE):
        c.stop(how=signal.SIGKILL, grace_s=5.0)


def start_serving(name: str, argv_of_port, env: dict, log_path: str,
                  health_path: str, deadline_s: float,
                  stdin_pipe: bool = False):
    """Start a child that serves HTTP on a port chosen here and wait for
    `health_path` to answer. A child that dies before it answers (the
    port was taken between the probe and the bind) is started once more
    on a new port. Returns (child, port)."""
    last = ""
    for attempt in (1, 2):
        port = free_port()
        child = Child(name, argv_of_port(port), env,
                      log_path if attempt == 1 else log_path + ".retry",
                      stdin_pipe=stdin_pipe)
        deadline = time.monotonic() + deadline_s
        while child.alive() and time.monotonic() < deadline:
            try:
                # 5 s, not 1: a poll that gives up on a child busy starting
                # leaves a BrokenPipeError traceback in the child's log
                http_get(f"http://127.0.0.1:{port}{health_path}", timeout=5.0)
                return child, port
            except OSError:
                time.sleep(0.1)
        last = (f"{name} did not answer {health_path} (exit code "
                f"{child.proc.poll()}); its log ends:\n{child.log_tail()}")
        gave_up_waiting = child.alive()
        child.stop(how=signal.SIGKILL, grace_s=5.0)
        if gave_up_waiting:
            break  # it ran and never answered: a second try would too
    raise RunFailure(last)
