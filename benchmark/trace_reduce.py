"""From a profiler trace to numbers: device busy and idle time, time by
program and by kernel, collective time that no compute hides, the device
operations that took most time and the longest idle gaps named by what the
host was doing. Pure Python over a plain dict, so that a small recorded
trace kept as JSON checks it (tests/benchmark).

The names a trace uses for planes, lines, programs, kernels and collectives
are data: ``layer_metrics/programs.json``.
"""

from __future__ import annotations

import re
from statistics import median


def load_xplane(path: str) -> dict:
    """An ``.xplane.pb`` as plain data: planes, their lines, and events as
    ``[name, start_ns, duration_ns]``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def merge(intervals: list) -> list:
    """The union of (start, end) intervals as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """The part of the disjoint sorted intervals ``a`` that ``b`` (the same)
    does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _matches(name: str, patterns: list) -> bool:
    return any(re.search(p, name) for p in patterns)


def _family(name: str, families: dict) -> str | None:
    for fam, patterns in families.items():
        if _matches(name, patterns):
            return fam
    return None


def _instruction(name: str) -> str:
    """An event's own name: the profiler names a device operation by its
    whole HLO text, ``%fusion.20 = f32[..] fusion(%all-reduce.5, ..)``; what
    precedes `` = `` is the instruction, the rest its result and operands."""
    return name.split(" = ", 1)[0].lstrip("%")


def _label(name: str, width: int = 72) -> str:
    """The instruction with the start of its result's shape, for a table."""
    head, _, rest = name.partition(" = ")
    return (head.lstrip("%") + " " + rest)[:width].strip()


def reduce_trace(trace: dict, table: dict) -> dict:
    """Every number the per-layer readers and the result line take from a
    trace. Times in seconds; ``busy_s`` and ``idle_pct`` are means over the
    device planes, ``window_s`` the span of all events of all planes."""
    dev_re = re.compile(table["device_plane"])
    devices = [p for p in trace["planes"] if dev_re.search(p["name"])]
    hosts = [p for p in trace["planes"] if re.search(table["host_plane"], p["name"])]
    starts = [e[1] for p in trace["planes"] for l in p["lines"] for e in l["events"]]
    ends = [e[1] + e[2] for p in trace["planes"] for l in p["lines"] for e in l["events"]]
    if not devices or not starts:
        return {"devices": 0}
    t0, t1 = min(starts), max(ends)
    window_s = (t1 - t0) / 1e9

    busy, programs, unknown, kernels = [], {}, {}, {}
    op_seconds, coll_total, coll_exposed = {}, [], []
    first_busy = None
    for plane in devices:
        ops = [e for l in plane["lines"] if l["name"] in table["op_lines"]
               for e in l["events"]]
        asyncs = [e for l in plane["lines"] if l["name"] in table["async_lines"]
                  for e in l["events"]]
        mods = [e for l in plane["lines"] if l["name"] in table["module_lines"]
                for e in l["events"]]
        union = merge([[e[1], e[1] + e[2]] for e in ops])
        busy.append(total(union) / 1e9)
        if first_busy is None:
            first_busy = union
        for name, _start, dur in mods:
            fam = _family(name, table["programs"])
            if fam is None:
                unknown[name] = unknown.get(name, 0.0) + dur / 1e9
            else:
                programs.setdefault(fam, []).append(dur / 1e9)
        # a collective is hidden while a compute operation runs on the core;
        # asynchronous ones have a line of their own, their -start and -done
        # halves sit among the core's operations
        coll = [[e[1], e[1] + e[2]] for e in asyncs
                if _matches(_instruction(e[0]), table["collectives"])]
        compute = []
        for name, start, dur in ops:
            op_seconds[_label(name)] = op_seconds.get(_label(name), 0.0) + dur / 1e9
            fam = _family(name, table["kernels"])
            if fam is not None:
                kernels.setdefault(fam, []).append(dur / 1e9)
            if _matches(_instruction(name), table["collectives"]):
                coll.append([start, start + dur])
            elif not _matches(_instruction(name), table["containers"]):
                compute.append([start, start + dur])
        coll = merge(coll)
        coll_total.append(total(coll) / 1e9)
        coll_exposed.append(total(subtract(coll, merge(compute))) / 1e9)

    n = len(devices)
    busy_s = sum(busy) / n
    host_events = [
        (e[0], e[1], e[1] + e[2]) for p in hosts for l in p["lines"]
        for e in l["events"] if e[2] > 0]
    gaps = subtract([[t0, t1]], first_busy)
    return {
        "devices": n, "window_s": window_s, "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "programs": {
            fam: {"count": len(d) / n, "total_s": sum(d) / n,
                  "median_s": median(d)}
            for fam, d in programs.items()},
        "unknown_programs": unknown,
        "kernels": {fam: {"count": len(d) / n, "total_s": sum(d) / n}
                    for fam, d in kernels.items()},
        "collective_s": sum(coll_total) / n,
        "collective_exposed_s": sum(coll_exposed) / n,
        "device_ops": top({k: v / n for k, v in op_seconds.items()}),
        "idle_gaps": top(_name_gaps(gaps, host_events, table)),
    }


def top(seconds_by_name: dict, k: int = 10) -> list:
    rows = sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:k]
    return [[name, secs] for name, secs in rows]


def _name_gaps(gaps: list, host_events: list, table: dict,
               longest: int = 400) -> dict:
    """Idle seconds by what the host was doing: each of the longest gaps is
    named by the shortest host span that covers at least half of it, a span
    of the benchmark's or the program's own (``host_span_prefixes``) before
    any other."""
    out = {}
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:longest]
    prefixes = tuple(table["host_span_prefixes"])
    for s, e in gaps:
        need = 0.5 * (e - s)
        own, other = None, None
        for name, hs, he in host_events:
            if min(e, he) - max(s, hs) < need:
                continue
            if name.startswith(prefixes):
                if own is None or he - hs < own[1]:
                    own = (name, he - hs)
            elif other is None or he - hs < other[1]:
                other = (name, he - hs)
        pick = own or other
        name = pick[0] if pick else "no host span"
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out
