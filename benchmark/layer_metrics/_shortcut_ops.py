"""What the two metrics of the shortcut-connected expert layer share: the
device time of a decode step under the program's ``ffn/dense`` scope (both
dense MLPs of a layer), beside the step programs and the ``serving/collect``
counters that ``_scoped_ops`` already reads. ``_scoped_ops.SCOPES`` is fixed
to the two scopes of PR 28, so the dense path's pattern lives here; the
profile is parsed by that file's ``read_planes``. No entry of BENCHMARK.json
names this file, so it is no metric.

Plain form: ``_scoped_ops``'s, with ``ops`` the operations of decode steps
whose scope path names ``ffn/dense``, each ``["dense", start_ns, dur_ns]``,
and ``collect`` rows that carry ``zero_picks``, ``held_picks`` and ``picks``
where the program counts them. A program that names no such scope and counts
no such pick (any other family, the parent of the PR that brought this) gives
neither: the readers return None and the metrics are left out of the line.
"""

from __future__ import annotations

import functools
import glob
import os
import re

from benchmark import spec
from benchmark.layer_metrics import _scoped_ops
from benchmark.trace_reduce import merge

DENSE = re.compile(r"(^|/)ffn/dense(/|$)")


def run_profile(root: str = spec.ROOT) -> dict | None:
    """The plain form of the profile the run has just written under
    ``root``; None without one."""
    found = glob.glob(os.path.join(
        root, ".bench_trace", "plugins", "profile", "*", "*.xplane.pb"))
    return load(found[0]) if found else None


@functools.lru_cache(maxsize=1)
def load(path: str) -> dict:
    base = _scoped_ops.load(path)
    table = spec.load_trace_table()
    device = re.compile(table["device_plane"])
    steps = merge([[s, s + d] for s, d in base["programs"]["decode_step"]])
    ops = []
    for plane in _scoped_ops.read_planes(
            path, lambda name: bool(device.search(name))):
        if ops:
            break  # the first device plane is enough: one chip a cell
        for line in plane["lines"]:
            if line["name"] not in table["op_lines"]:
                continue
            for _name, scope_text, start, dur, _stats in line["events"]:
                if DENSE.search(scope_text) and any(
                        s <= start < t for s, t in steps):
                    ops.append(["dense", start, dur])
    return {"programs": base["programs"], "ops": ops,
            "collect": base["collect"]}


def of_run(ctx: dict) -> dict | None:
    """The plain form of the run whose ``ctx`` this is; None for an untraced
    run or one that wrote no profile."""
    if not ctx.get("trace"):
        return None
    return run_profile()
