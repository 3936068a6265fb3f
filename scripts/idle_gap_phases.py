#!/usr/bin/env python3
"""Which phase of the scheduling loop does the device's idle time belong
to? Reads one .xplane.pb (a `benchmark/run.py --trace 1 --keep-trace`
run) with jax.profiler.ProfileData: the gaps of `/device:TPU:0` in which
no operation ran, and for each of the longest the `ktpu.loop.<phase>`
annotations of the scheduler's loop thread that overlap it — both on the
trace's own clock. Prints one JSON object.

  python scripts/idle_gap_phases.py <file.xplane.pb | trace dir> [--gaps 5]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
PREFIX = "ktpu.loop."


def _merge(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def analyse(path: str, n_gaps: int) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    busy, loop_lines = [], []
    for plane in pd.planes:
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events]
            if DEVICE_PLANE.match(plane.name) and plane.name.endswith(":0") \
                    and line.name == "XLA Ops":
                busy = _merge([(s, s + d) for _, s, d in evs if d > 0])
            mine = [(n[len(PREFIX):], s, s + d) for n, s, d in evs
                    if n.startswith(PREFIX)]
            if mine:
                loop_lines.append((plane.name, line.name, mine))
    # the loop thread's line: the one that launched
    loop_lines.sort(key=lambda pl: -sum(1 for n, _, _ in pl[2] if n == "launch"))
    out = {"file": os.path.basename(path), "device_busy_intervals": len(busy),
           "loop_lines": [(p, ln, len(evs)) for p, ln, evs in loop_lines],
           "gaps": []}
    if not busy or not loop_lines:
        return out
    plane, line, phases = loop_lines[0]
    out["loop_line"] = f"{plane} | {line}"
    t0 = busy[0][0]
    gaps = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _) in
                   zip(busy, busy[1:])), reverse=True)[:n_gaps]
    for length, g0, g1 in gaps:
        cover: dict = {}
        for name, s, e in phases:
            lo, hi = max(s, g0), min(e, g1)
            if hi > lo:
                cover[name] = cover.get(name, 0.0) + (hi - lo)
        covered = sum(cover.values())
        out["gaps"].append({
            "start_s": (g0 - t0) / 1e9, "end_s": (g1 - t0) / 1e9,
            "length_s": length / 1e9,
            "covered_by_annotations_share": covered / length,
            "phase_share": {k: v / length for k, v in
                            sorted(cover.items(), key=lambda kv: -kv[1])},
            "belongs_to": max(cover, key=cover.get) if cover else None,
        })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--gaps", type=int, default=5)
    args = ap.parse_args(argv)
    path = args.path
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            print(json.dumps({"error": f"no .xplane.pb under {path}"}))
            return 1
        path = found[-1]
    print(json.dumps(analyse(path, args.gaps), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
