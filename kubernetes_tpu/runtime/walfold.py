"""The WAL's files read with `json`, `zlib` and `os` alone: the record
framing, and the fold that makes the next snapshot from them.

    python -m kubernetes_tpu.runtime.walfold <prefix> <cut> [<rv>]

A compaction used to copy, encode and dump every live object inside the
apiserver's interpreter (runtime/wal.py: write_snapshot), ~5 s of one
core that every request shares. But the files already hold all of it:
the previous snapshot plus the first `<cut>` bytes of the log, replayed
by recovery's own rules, ARE the store's acknowledged state at the cut.
So a child process folds them into `<prefix>.snapshot.json.tmp` and the
parent only publishes (WriteAheadLog.publish). The child never publishes:
one whose parent has gone leaves at most a `.tmp`, which the next open
sweeps.

Objects stay the raw dicts the records carry: nothing is decoded, so this
module imports nothing of the object model (`import
kubernetes_tpu.runtime.wal` costs 0.6 s of CPU, this one under 0.1).

Exit codes: 0 the `.tmp` is written and fsynced; EXIT_DAMAGED (3) the
snapshot or a record before the cut is damaged, or the fold does not end
at `<rv>` (memory is the truth then: the parent compacts from it, which
heals the log); EXIT_ORPHANED (4) the parent went away; anything else: an
I/O error, the traceback on stderr.
"""

from __future__ import annotations

import json
import os
import sys
import zlib
from typing import Dict, Optional

SNAPSHOT_SUFFIX = ".snapshot.json"
LOG_SUFFIX = ".wal"

# v2 frame: "K2 " + 8 hex chars of crc32(payload) + " " + payload
FRAME_PREFIX = "K2 "

EXIT_DAMAGED = 3
EXIT_ORPHANED = 4


def frame_record(payload: str) -> str:
    """CRC32-frame one JSON payload into a v2 WAL line."""
    crc = zlib.crc32(payload.encode("utf-8")) & 0xFFFFFFFF
    return f"{FRAME_PREFIX}{crc:08x} {payload}\n"


def parse_wal_line(line: str) -> Optional[dict]:
    """Parse one WAL line (either framing version) or None if damaged.

    v2 (`K2 <crc8> <json>`): the CRC must match the payload bytes — a
    bit-flip inside a string value still parses as JSON, only the CRC
    catches it. v1 (starts with `{`): plain JSON, best-effort. Anything
    else is damage."""
    if line.startswith(FRAME_PREFIX):
        body = line[len(FRAME_PREFIX):]
        if len(body) < 10 or body[8] != " ":
            return None
        try:
            want = int(body[:8], 16)
        except ValueError:
            return None
        payload = body[9:]
        if zlib.crc32(payload.encode("utf-8")) & 0xFFFFFFFF != want:
            return None
        try:
            rec = json.loads(payload)
        except json.JSONDecodeError:
            return None
        return rec if isinstance(rec, dict) else None
    if line.startswith("{"):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            return None
        return rec if isinstance(rec, dict) else None
    return None


class Damaged(Exception):
    """The files do not hold what the parent acknowledged."""


def object_key(obj: dict) -> str:
    """`ObjectMeta.key` of an encoded object: the encoder leaves the
    default namespace out and writes a cluster-scoped one as ""."""
    meta = obj["metadata"]
    namespace = meta.get("namespace", "default")
    return f"{namespace}/{meta['name']}" if namespace else meta["name"]


def fold(prefix: str, cut: int) -> dict:
    """The snapshot `{"rv", "objects": {kind: [encoded, ...]}}` that
    recovery would rebuild from `<prefix>.snapshot.json` + the first `cut`
    bytes of `<prefix>.wal` (WriteAheadLog._recover_once's rules, on the
    raw dicts). Raises Damaged where recovery would stop replaying."""
    rv = 0
    objects: Dict[str, Dict[str, dict]] = {}
    try:
        with open(prefix + SNAPSHOT_SUFFIX, encoding="utf-8") as f:
            snap = json.load(f)
        rv = snap["rv"]
        for kind, objs in snap["objects"].items():
            objects[kind] = {object_key(o): o for o in objs}
    except FileNotFoundError:
        pass
    except (ValueError, KeyError, TypeError, AttributeError) as e:
        raise Damaged(f"snapshot unreadable: {e!r}") from e
    with open(prefix + LOG_SUFFIX, "rb") as f:
        data = f.read(cut)
    if len(data) < cut or (data and not data.endswith(b"\n")):
        raise Damaged(
            f"the log's first {cut} bytes are not whole records "
            f"({len(data)} read)"
        )
    lines = data.decode("utf-8", errors="replace").split("\n")
    for n, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        rec = parse_wal_line(line)
        if rec is None:
            raise Damaged(f"damaged record at line {n}")
        try:
            if rec.get("verb") == "commit":
                continue  # consensus epoch record: no object change
            if rec["rv"] <= rv:
                continue  # already in the snapshot
            rv = rec["rv"]
            d = objects.setdefault(rec["kind"], {})
            if rec["verb"] == "delete":
                d.pop(object_key(rec["obj"]), None)
            else:
                d[object_key(rec["obj"])] = rec["obj"]
        except (KeyError, TypeError) as e:
            raise Damaged(f"malformed record at line {n}: {e!r}") from e
    return {
        "rv": rv,
        "objects": {kind: list(d.values()) for kind, d in objects.items()},
    }


def main(argv) -> int:
    prefix, cut = argv[0], int(argv[1])
    want_rv = int(argv[2]) if len(argv) > 2 else None
    parent = os.getppid()
    try:
        snap = fold(prefix, cut)
        if want_rv is not None and snap["rv"] != want_rv:
            raise Damaged(
                f"the log's first {cut} bytes end at rv {snap['rv']}, the "
                f"last record acknowledged before the cut was rv {want_rv}"
            )
    except Damaged as e:
        print(f"walfold: {e}", file=sys.stderr)
        return EXIT_DAMAGED
    body = json.dumps(snap).encode("utf-8")
    if os.getppid() != parent:
        # nobody is left to publish, and a restarted server's own fold
        # may be writing this very path
        print("walfold: parent gone, nothing written", file=sys.stderr)
        return EXIT_ORPHANED
    with open(prefix + SNAPSHOT_SUFFIX + ".tmp", "wb") as f:
        f.write(body)
        f.flush()
        os.fsync(f.fileno())
    return 0


if __name__ == "__main__":
    import gc

    # one pass that builds ~a million containers and no cycle
    gc.disable()
    sys.exit(main(sys.argv[1:]))
