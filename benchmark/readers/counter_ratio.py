"""A ratio of two counts over the window. Each side is either
{"client": <stat>} (the generator's and the watch's own count) or
{"source": "sched"|"api", "name": <counter>, "labels": {...}} (delta of
the two scrapes). A denominator of nought: nothing read."""


def _side(ctx, spec):
    if "client" in spec:
        return ctx["client"].get(spec["client"])
    start, end = ctx[spec["source"]]
    labels = spec.get("labels")
    return end.total(spec["name"], labels) - start.total(spec["name"], labels)


def read(ctx, num, den, scale=1.0):
    n, d = _side(ctx, num), _side(ctx, den)
    if n is None or not d or d <= 0:
        return None
    return n / d * scale
