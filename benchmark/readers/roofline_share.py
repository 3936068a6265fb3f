"""A program's share of its roofline: the least time the chip could take
for one launch (the larger of operations / peak FLOP/s and bytes / peak
bytes/s, from harness/roofline.py and harness/peaks.json) over the
device time per launch from the trace. Never a 0: nothing traced, or
nothing to divide, reads nothing."""

from harness import roofline


def read(ctx, program, work):
    trace = ctx.get("trace") or {}
    p = (trace.get("programs") or {}).get(program)
    if not p or p["launches"] <= 0 or p["device_s"] <= 0:
        return None
    least_s = roofline.least_seconds(work, ctx["config"], ctx["device"]["kind"],
                                     ctx["device"]["count"])
    return 100.0 * least_s / (p["device_s"] / p["launches"])
