"""Mean of a histogram over the window: delta sum / delta count of
`<name>_sum` and `<name>_count` between the two scrapes of one child,
times `scale` (1000: seconds -> ms). Nothing observed: nothing read."""


def read(ctx, source, name, labels=None, scale=1.0):
    start, end = ctx[source]
    n = end.total(name + "_count", labels) - start.total(name + "_count", labels)
    if n <= 0:
        return None
    s = end.total(name + "_sum", labels) - start.total(name + "_sum", labels)
    return s / n * scale
