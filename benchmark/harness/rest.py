"""The benchmark's own client of the apiserver's REST surface: plain
http.client and JSON manifests, nothing of the program's. What a user's
client would do: POST objects and hold one watch on pods."""

from __future__ import annotations

import http.client
import json
import socket
import threading
import time


class Rest:
    """Kept-alive connections, shared by every thread that asks: one is
    taken for a request and put back after it. `warm(n, path)` opens n of
    them one after another, each answered once before the next is opened;
    after that a window's senders open none, however many start at once.
    The apiserver listens with a backlog of 5: 64 connects in one instant
    had 3 creates reset at the start of a window (1 run of 16 on the chip,
    PR 29), and 64 one after another, unanswered, still overflowed it and
    cost set-up 7 s of SYN retransmissions."""

    def __init__(self, port: int, host: str = "127.0.0.1",
                 timeout: float = 30.0):
        self.host, self.port, self.timeout = host, port, timeout
        self._idle: list = []  # open connections nobody is using
        self.refused: list = []  # why a create was not acknowledged

    def _open(self) -> http.client.HTTPConnection:
        c = http.client.HTTPConnection(self.host, self.port,
                                       timeout=self.timeout)
        c.connect()
        c.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return c

    def warm(self, n: int, path: str) -> None:
        for _ in range(n - len(self._idle)):
            c = self._open()
            c.request("GET", path)
            c.getresponse().read()  # accepted and served: now the next
            self._idle.append(c)

    def request(self, method: str, path: str, body: bytes | None = None):
        """(status, body bytes) over a kept-alive connection. One that
        died idle is replaced once for a GET; a write is never replayed
        (it may have been applied)."""
        for attempt in (1, 2):
            try:
                c = self._idle.pop()
            except IndexError:
                c = self._open()
            try:
                c.request(method, path, body=body,
                          headers={"Content-Type": "application/json"})
                r = c.getresponse()
                data = r.read()
            except (OSError, http.client.HTTPException):
                c.close()
                if method != "GET" or attempt == 2:
                    raise
                continue
            self._idle.append(c)
            return r.status, data

    def create(self, path: str, body: bytes) -> bool:
        """POST one object; True when the server acknowledged it."""
        try:
            status, _ = self.request("POST", path, body)
        except (OSError, http.client.HTTPException) as e:
            self.refused.append(repr(e))
            return False
        if not 200 <= status < 300:
            self.refused.append(f"HTTP {status}")
            return False
        return True

    def close(self) -> None:
        while self._idle:
            self._idle.pop().close()


class BindWatch:
    """This client's own pod watch (newline-JSON wire): the node each pod
    was first seen bound to, when (this process's monotonic clock), and
    the order in which the binds were seen."""

    def __init__(self, port: int, from_version: int = 0,
                 host: str = "127.0.0.1"):
        self.host, self.port = host, port
        self.bound: dict = {}    # "ns/name" -> node
        self.t_bound: dict = {}  # "ns/name" -> monotonic seconds
        self.order: list = []    # (key, node), as seen
        self.rebinds: list = []  # a pod seen bound to two different nodes
        self.errors: list = []   # why a stream ended, for the log
        self.stopped = False     # set by stop() or by a stream that ended
        self._closing = False
        self._rv = from_version
        self._sock = None
        self.opened = threading.Event()  # the first stream is established
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bind-watch")
        self._thread.start()

    def _open(self):
        c = http.client.HTTPConnection(self.host, self.port, timeout=30.0)
        c.request("GET", f"/api/v1/pods?watch=1&resourceVersion={self._rv}")
        r = c.getresponse()
        if r.status != 200:
            body = r.read()[:300]
            c.close()
            raise OSError(f"watch from rv {self._rv}: HTTP {r.status} {body!r}")
        self._sock = c.sock
        self._sock.settimeout(None)  # an idle stream is healthy
        self.opened.set()
        return c, r

    def _run(self) -> None:
        failures = 0
        while not self._closing and failures < 5:
            try:
                conn, resp = self._open()
            except (OSError, http.client.HTTPException) as e:
                self.errors.append(f"open: {e!r}")
                failures += 1
                time.sleep(0.2)
                continue
            try:
                for line in resp:
                    if self._closing:
                        break
                    line = line.strip()
                    if line:
                        self._event(json.loads(line))
                        failures = 0
            except (OSError, ValueError, http.client.HTTPException,
                    AttributeError) as e:
                # a closed or cut stream: resume from the last rv
                self.errors.append(f"stream: {e!r}")
            finally:
                conn.close()
            failures += 1
        self.stopped = True

    def _event(self, msg: dict) -> None:
        obj = msg.get("object") or {}
        meta = obj.get("metadata") or {}
        rv = meta.get("resourceVersion")
        if rv is not None:
            self._rv = max(self._rv, int(rv))
        node = (obj.get("spec") or {}).get("nodeName")
        if not node or msg.get("type") not in ("ADDED", "MODIFIED"):
            return
        key = f"{meta.get('namespace', '')}/{meta.get('name', '')}"
        old = self.bound.get(key)
        if old is None:
            self.bound[key] = node
            self.t_bound[key] = time.monotonic()
            self.order.append((key, node))
        elif old != node:
            self.rebinds.append((key, old, node))

    def stop(self) -> None:
        self._closing = True
        sock = self._sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        self._thread.join(timeout=5.0)
        self.stopped = True
