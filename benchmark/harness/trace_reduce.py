"""From a profiler trace (.xplane.pb) to numbers, read with
jax.profiler.ProfileData only.

Device planes are the `/device:TPU:<n>` planes. On each, the `XLA Ops`
line holds one event per operation that ran on the device and the
`XLA Modules` line one event per launch of a compiled program, named
`<module>(<fingerprint>)`, e.g. `jit_wave_kernel(123...)`.

  busy_s      union of the intervals in which an operation ran on the
              device, averaged over the device planes
  window_s    the traced window: what the launcher wrote beside the trace
              (start_trace returned -> stop_trace called, its clock), else
              first start to last end over all planes
  programs    per module name: launches and summed device seconds
              (averaged over the device planes)
  breakdown   the operations that took most device time, and the longest
              gaps in which no operation ran. Where the host planes hold
              the program's `ktpu.loop.<phase>` annotations (the scheduling
              loop's phases, on the trace's own clock) a gap is named after
              the phase that covers most of it (`bind`, `pop`, ...); where
              they hold none, or cover under half of the gap, after the
              program that ended it (`before <program>`)
  stopped     (reduce_dir) what the launcher wrote beside the trace:
              `window_s`, `launches`, `stopped_by`, `stop_s`
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
PHASE_PREFIX = "ktpu.loop."


def _union(intervals: list) -> tuple:
    """(total covered length, merged intervals) of [(start, end)]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def module_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """`%fusion.580 = pred[...] fusion(...)` -> `fusion.580`."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def loop_phases(host_lines: list) -> list:
    """[(phase, start, end)] of the scheduling loop's thread: of the host
    lines ([(name, start, duration)] each) that hold `ktpu.loop.<phase>`
    annotations, the one with most `launch` phases."""
    best, best_key = [], (-1, -1)
    for evs in host_lines:
        mine = [(n[len(PHASE_PREFIX):], s, s + d) for n, s, d in evs
                if n.startswith(PHASE_PREFIX)]
        key = (sum(1 for n, _, _ in mine if n == "launch"), len(mine))
        if mine and key > best_key:
            best, best_key = mine, key
    return best


def phase_cover(phases: list, g0: float, g1: float) -> dict:
    """phase -> the length of [g0, g1] that its annotations cover."""
    cover: dict = {}
    for name, s, e in phases:
        lo, hi = max(s, g0), min(e, g1)
        if hi > lo:
            cover[name] = cover.get(name, 0.0) + (hi - lo)
    return cover


def reduce_profile(pd, window_s: float | None = None) -> dict:
    """`pd`: a jax.profiler.ProfileData; `window_s`: the traced window's
    length where the launcher gave it."""
    t_min, t_max = None, None
    device_planes, host_lines = [], []
    for plane in pd.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events]
            for _, s, d in evs:
                t_min = s if t_min is None else min(t_min, s)
                t_max = s + d if t_max is None else max(t_max, s + d)
            if on_device:
                lines[line.name] = evs
            else:
                host_lines.append(evs)  # threads share names: never by name
        if on_device:
            device_planes.append((plane.name, lines))
    out = {"devices": len(device_planes), "busy_s": 0.0, "programs": {},
           "window_s": (window_s if window_s else
                        (t_max - t_min) / 1e9 if t_min is not None else 0.0),
           "breakdown": {"device_ops": [], "idle_gaps": []}}
    if not device_planes:
        return out
    n = len(device_planes)
    op_time: dict = {}
    for k, (_, lines) in enumerate(sorted(device_planes)):
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy, merged = _union([(s, s + d) for _, s, d in ops if d > 0])
        out["busy_s"] += busy / 1e9 / n
        for name, _, d in ops:
            name = op_name(name)
            op_time[name] = op_time.get(name, 0.0) + d / 1e9 / n
        mods = sorted(lines.get(MODULES_LINE) or [], key=lambda e: e[1])
        for name, _, d in mods:
            p = out["programs"].setdefault(
                module_name(name), {"launches": 0.0, "device_s": 0.0})
            p["launches"] += 1.0 / n
            p["device_s"] += d / 1e9 / n
        if k == 0:
            edges = [[t_min, t_min]] + merged + [[t_max, t_max]]
            gaps = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _)
                           in zip(edges, edges[1:])
                           if s1 - e0 > 1e5),  # gaps over 0.1 ms
                          reverse=True)[:10]
            phases = loop_phases(host_lines)
            for length, g0, g1 in gaps:
                cover = phase_cover(phases, g0, g1)
                if sum(cover.values()) >= 0.5 * length:
                    name = max(cover, key=cover.get)
                else:
                    name = "before " + next(
                        (module_name(nm) for nm, s, _ in mods if s >= g1 - 1),
                        "end of trace")
                out["breakdown"]["idle_gaps"].append([name, length / 1e9])
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    out["breakdown"]["device_ops"] = [[k, v] for k, v in top]
    if out["window_s"] > 0:
        out["idle_share"] = 100.0 * (1.0 - out["busy_s"] / out["window_s"])
    return out


def find_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduce_dir(trace_dir: str) -> dict:
    """Reduce the trace written under `trace_dir`; {} if there is none."""
    path = find_xplane(trace_dir) if trace_dir else None
    if path is None:
        return {}
    from jax.profiler import ProfileData

    stopped = {}
    try:
        with open(os.path.join(trace_dir, "stopped")) as f:
            stopped = dict(json.load(f))
        window_s = float(stopped["window_s"])
    except (OSError, ValueError, KeyError, TypeError):
        window_s = None
    out = reduce_profile(ProfileData.from_file(path), window_s)
    out["stopped"] = stopped
    return out
