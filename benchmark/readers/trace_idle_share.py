"""Share of the traced span in which no operation ran on the device."""


def read(ctx):
    trace = ctx.get("trace") or {}
    if not trace.get("devices") or not trace.get("window_s"):
        return None
    return trace.get("idle_share")
