"""The reducers a per-layer metric's file (``benchmark/metrics/<name>.json``)
can name. Each takes the file's ``args`` and the run's context — host
spans, units, the numbers reduced from the trace, counters — and returns a
number, or ``None`` when there is nothing to read (the metric is then left
out of the line). A new metric is a new file naming one of these."""

import statistics


def _block_units(ctx):
    return [(i, t1 - t0) for k, i, t0, t1, ok in ctx["units"]
            if k == "block" and ok]


def span_ms_per_step(args, ctx):
    """Host time in the named spans inside blocks, per step."""
    blocks = _block_units(ctx)
    spans = ctx["spans"]
    if not blocks or not spans.count(args["spans"], "block"):
        return None
    steps = len(blocks) * ctx["block_steps"]
    return 1e3 * spans.total(args["spans"], "block") / steps


def block_remainder_ms_per_step(args, ctx):
    """Block time not inside any of the named spans, per step."""
    blocks = _block_units(ctx)
    if not blocks:
        return None
    steps = len(blocks) * ctx["block_steps"]
    inside = ctx["spans"].total(args["spans"], "block")
    return 1e3 * (sum(b for _, b in blocks) - inside) / steps


def unit_median(args, ctx):
    """The median unit of ``args["kind"]`` (``block``: ms per step;
    ``output``: seconds). The end-to-end metrics are taken over all the
    units; beside them this says what a unit costs when the machine does
    not stall."""
    secs = [t1 - t0 for k, _, t0, t1, ok in ctx["units"]
            if k == args["kind"] and ok]
    if not secs:
        return None
    median = statistics.median(secs)
    return 1e3 * median / ctx["block_steps"] if args["kind"] == "block" \
        else median


def span_ms_per_output(args, ctx):
    """Median over the window's outputs of the time in the named spans."""
    spans = ctx["spans"]
    units = [i for k, i, _, _, ok in ctx["units"] if k == "output" and ok]
    if not units or not spans.count(args["spans"], "output"):
        return None
    return 1e3 * statistics.median(
        spans.total(args["spans"], "output", units={i}) for i in units)


def traced(args, ctx):
    """A number the trace reduction produced (a device number: never in
    a rehearsal)."""
    if ctx["rehearse"]:
        return None
    return ctx["traced"].get(args["key"])


def counter(args, ctx):
    return ctx["counters"].get(args["key"])


REDUCERS = {f.__name__: f for f in (
    span_ms_per_step, block_remainder_ms_per_step, unit_median,
    span_ms_per_output, traced, counter)}


def read(spec, ctx):
    value = REDUCERS[spec["reducer"]](spec.get("args", {}), ctx)
    return None if value is None else float(value)
