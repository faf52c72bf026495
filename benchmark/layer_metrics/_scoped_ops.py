"""Device time by the program's own named scopes, and the routing counters
of the ``serving/collect`` span: what the four metrics of the latent-attention
/ routed-expert block share. No entry of BENCHMARK.json names this file, so
it is no metric.

The serving program wraps the block's parts in ``jax.named_scope`` (``mla``,
``moe/route``, ``moe/experts``, ``moe/shared``); the profiler keeps the scope
path of every device operation as the ``tf_op`` stat of the event's metadata
(found by looking at one trace of the cell by hand, PR 28). An operation
belongs to a decode step when it starts inside an ``XLA Modules`` event of the
``decode_step`` family. ``ctx`` holds the reduced trace only, so the profile is
found where ``harness.Profile.path()`` finds it. The arithmetic is pure Python
over plain data, so that a small recorded fixture checks it.

Plain form::

    {"programs": {"decode_step": [[start_ns, dur_ns], ...],
                  "prefill_chunk": [[start_ns, dur_ns], ...]},
     "ops": [[scope, start_ns, dur_ns], ...],   # of decode steps, scoped only
     "collect": [{experts_hit, expert_load_max, experts_total,
                  routed_tokens}, ...]}

A program that names no such scope (the parent of PR 28, any other family)
gives no ``ops`` and no counters: the readers return None and the metric is
left out of the line.
"""

from __future__ import annotations

import functools
import glob
import os
import re
import struct
from statistics import median

from benchmark import spec
from benchmark.trace_reduce import merge, total

# XLA's own grouped-product kernels (``jax.lax.ragged_dot`` on the TPU) carry
# the operation's name in place of the scope path; only the expert layer has
# such products
SCOPES = {"moe": re.compile(r"(^|/)moe/|^ragged-dot"),
          "mla": re.compile(r"(^|/)mla(/|$)")}
SCOPE_STAT = "tf_op"
PROGRAMS = ("decode_step", "prefill_chunk")


def run_profile(root: str = spec.ROOT) -> dict | None:
    """The plain form of the profile the run has just written under
    ``root``; None without one."""
    found = glob.glob(os.path.join(
        root, ".bench_trace", "plugins", "profile", "*", "*.xplane.pb"))
    return load(found[0]) if found else None


def scope_of(text: str) -> str | None:
    """``moe`` or ``mla`` where an operation's scope path names one."""
    for scope, pattern in SCOPES.items():
        if pattern.search(text):
            return scope
    return None


# ---------------------------------------------------- the profile, raw
#
# ``jax.profiler.ProfileData`` gives an event's own stats; the scope path of a
# device operation is a stat of the event's *metadata* (what is said once
# about every run of that operation), which it does not give. So the file is
# read here as what it is, a protocol buffer (tsl/profiler/protobuf/
# xplane.proto), with the few field numbers this needs and nothing installed.


def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(number, wire type, value)`` of every field of one message; a
    length-delimited value is a ``memoryview`` of the bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view):
    key, value = None, None
    for number, _wire, v in _fields(view):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(view, stat_names):
    """One XStat as ``(name, value)``; a ``ref_value`` names its string."""
    name, value = None, None
    for number, wire, v in _fields(view):
        if number == 1:
            name = stat_names.get(v)
        elif number == 2:
            (value,) = struct.unpack("<d", v)
        elif number in (5, 6):
            value = _text(v)
        elif number == 7:
            value = stat_names.get(v)
        elif wire == 0:
            value = v
    return name, value


def read_planes(path: str, wanted) -> list:
    """The planes of an ``.xplane.pb`` whose name ``wanted`` accepts, as
    ``{"name", "lines": [{"name", "events": [[event name, scope text,
    start_ns, duration_ns, {stat: value}], ...]}]}``; the scope text is
    the metadata's ``SCOPE_STAT``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for number, _w, plane in _fields(space):
        if number != 1:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for num, _w2, v in _fields(plane):
            if num == 2:
                name = _text(v)
            elif num == 3:
                lines.append(v)
            elif num == 4:
                metas.append(v)
            elif num == 5:
                key, value = _map_entry(v)
                for n3, _w3, v3 in _fields(value):
                    if n3 == 2:
                        stat_names[key] = _text(v3)
        if not wanted(name):
            continue
        meta = {}
        for entry in metas:
            key, value = _map_entry(entry)
            ev_name, scope_text = "", ""
            for n3, _w3, v3 in _fields(value):
                if n3 == 2:
                    ev_name = _text(v3)
                elif n3 == 5:
                    stat, text = _stat(v3, stat_names)
                    if stat == SCOPE_STAT and isinstance(text, str):
                        scope_text = text
            meta[key] = (ev_name, scope_text)
        out_lines = []
        for line in lines:
            line_name, t0, events = "", 0, []
            for n3, _w3, v3 in _fields(line):
                if n3 == 2:
                    line_name = _text(v3)
                elif n3 == 3:
                    t0 = v3
                elif n3 == 4:
                    events.append(v3)
            rows = []
            for event in events:
                mid, offset, dur, stats = None, 0, 0, {}
                for n4, _w4, v4 in _fields(event):
                    if n4 == 1:
                        mid = v4
                    elif n4 == 2:
                        offset = v4
                    elif n4 == 3:
                        dur = v4
                    elif n4 == 4:
                        stat, value = _stat(v4, stat_names)
                        stats[stat] = value
                ev_name, scope_text = meta.get(mid, ("", ""))
                rows.append([ev_name, scope_text, t0 + offset / 1000.0,
                             dur / 1000.0, stats])
            out_lines.append({"name": line_name, "events": rows})
        planes.append({"name": name, "lines": out_lines})
    return planes


@functools.lru_cache(maxsize=1)
def load(path: str) -> dict:
    table = spec.load_trace_table()
    device = re.compile(table["device_plane"])
    host = re.compile(table["host_plane"])
    programs = {p: [] for p in PROGRAMS}
    ops, collect = [], []
    planes = read_planes(
        path, lambda name: bool(device.search(name) or host.search(name)))
    for plane in planes:
        if host.search(plane["name"]):
            for line in plane["lines"]:
                for name, _scope, _start, _dur, stats in line["events"]:
                    if name == "serving/collect" and "experts_hit" in stats:
                        collect.append({k: float(v) for k, v in stats.items()
                                        if isinstance(v, (int, float, str))})
            continue
        if programs["decode_step"]:
            continue  # the first device plane is enough: one chip a cell
        for line in plane["lines"]:
            if line["name"] in table["module_lines"]:
                for name, _scope, start, dur, _stats in line["events"]:
                    for p in PROGRAMS:
                        if any(re.search(x, name) for x in table["programs"][p]):
                            programs[p].append([start, dur])
        steps = merge([[s, s + d] for s, d in programs["decode_step"]])
        for line in plane["lines"]:
            if line["name"] not in table["op_lines"]:
                continue
            for name, scope_text, start, dur, _stats in line["events"]:
                scope = scope_of(scope_text)
                if scope and any(s <= start < t for s, t in steps):
                    ops.append([scope, start, dur])
    return {"programs": programs, "ops": ops, "collect": collect}


def of_run(ctx: dict) -> dict | None:
    """The plain form of the run whose ``ctx`` this is; None for an untraced
    run or one that wrote no profile."""
    if not ctx.get("trace"):
        return None
    return run_profile()


def scope_seconds_a_step(plain: dict, scope: str) -> float | None:
    """Device seconds a decode step under ``scope``: the union of the
    scope's operations (a loop and its body are both events: the union
    counts the time once) over the decode steps traced."""
    steps = plain["programs"].get("decode_step") or []
    spans = [[s, s + d] for name, s, d in plain["ops"] if name == scope]
    if not steps or not spans:
        return None
    return total(merge(spans)) / 1e9 / len(steps)


def program_median_s(plain: dict, program: str) -> float | None:
    runs = plain["programs"].get(program) or []
    return median(d for _s, d in runs) / 1e9 if runs else None
