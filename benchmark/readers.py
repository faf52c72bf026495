"""The reader kinds a per-layer metric's JSON file may name. A metric that
needs arithmetic of its own (a roofline share, a utilization) is a
``layer_metrics/<name>.py`` with ``read(ctx)`` instead.

A reader takes the metric's own file (``m``) and what the run collected
(``ctx``) and returns a number, or None where it finds nothing to read:
the harness then leaves the metric out of the line. ``ctx`` holds

    samples   {name: [numbers]} the drivers collected (client clocks)
    counters  {name: number} read from the program or the harness
    trace     the dict ``trace_reduce.reduce_trace`` returns (traced runs)
    e2e       {name: value} of this run's end-to-end metrics
    family    the configuration's family module (``spec.load_family``), and
    widths    its ``widths(config)``; ``config``, ``traffic``, ``peaks``,
    chips     the cell's number of chips
    operands  {metric: {...}} filled here, printed when a share passes 105%
"""

from __future__ import annotations

from benchmark.harness import log, percentile


def _scaled(m: dict, value):
    return None if value is None else float(value) * float(m.get("scale", 1.0))


def counter(m, ctx):
    return _scaled(m, ctx["counters"].get(m["counter"]))


def counter_ratio(m, ctx):
    num = ctx["counters"].get(m["numerator"])
    den = ctx["counters"].get(m["denominator"])
    if num is None or not den:
        return None
    return _scaled(m, num / den)


def sample_percentile(m, ctx):
    values = ctx["samples"].get(m["sample"])
    if not values:
        return None
    log(f"{m['name']}: n={len(values)} median={percentile(values, 50)}")
    return _scaled(m, percentile(values, m["percentile"]))


def _program(m, ctx):
    trace = ctx.get("trace") or {}
    return (trace.get("programs") or {}).get(m["program"])


def trace_program_median(m, ctx):
    """The median device duration of one program family."""
    p = _program(m, ctx)
    return None if p is None else _scaled(m, p["median_s"])


def trace_program_per_step(m, ctx):
    """The device time of a program family over the steps it made."""
    p = _program(m, ctx)
    steps = ctx["counters"].get(m["steps_counter"])
    if p is None or not steps:
        return None
    return _scaled(m, p["total_s"] / steps)


def trace_field(m, ctx):
    trace = ctx.get("trace") or {}
    return _scaled(m, trace.get(m["field"]))


KINDS = {f.__name__: f for f in (
    counter, counter_ratio, sample_percentile, trace_program_median,
    trace_program_per_step, trace_field)}


def read(m: dict, ctx: dict):
    if m["reader"] == "python":
        return m["read"](ctx)
    return KINDS[m["reader"]](m, ctx)
