"""Device time per launch of one compiled program, from the device
trace: summed durations of its events on the device's `XLA Modules`
line over its launches in the traced span. No launch traced: nothing."""


def read(ctx, program):
    trace = ctx.get("trace") or {}
    p = (trace.get("programs") or {}).get(program)
    if not p or p["launches"] <= 0:
        return None
    return p["device_s"] / p["launches"] * 1e3
