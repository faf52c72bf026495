"""What the three metrics of the grouped-query block share: the device time
of a decode step under the program's ``attn/full`` and ``attn/window`` scopes,
the paged kernel's own custom calls inside the step program, and the window
pool's counters on the ``serving/iter`` span. ``_scoped_ops.SCOPES`` is fixed
to the two scopes of PR 28 and ``programs.json`` files every Mosaic call under
one family, so the attention scopes' pattern and the kernel's name live here;
the profile is parsed by ``_scoped_ops.read_planes`` and the iterations are
``_program_spans``'s. No entry of BENCHMARK.json names this file, so it is no
metric.

Plain form::

    {"programs": {"decode_step": [[start_ns, dur_ns], ...], ...},
     "ops": [["attn" | "kernel", start_ns, dur_ns], ...],  # of decode steps
     "iterations": [{"window_pages_in_use", "window_pages_total"}, ...]}

An operation is ``attn`` where its scope path names ``attn/full`` or
``attn/window``, and ``kernel`` (as well) where its HLO text or its scope path
names the kernel, ``paged_decode_attention``. A program that names no such
scope, runs no such kernel and counts no window pool (any other family, the
parent of the PR that brought this) gives none: the readers return None and
the metrics are left out of the line.
"""

from __future__ import annotations

import functools
import glob
import os
import re

from benchmark import flops, spec
from benchmark.harness import log
from benchmark.layer_metrics import _program_spans, _scoped_ops
from benchmark.trace_reduce import merge

ATTN = re.compile(r"(^|/)attn/(full|window)(/|$)")
KERNEL = re.compile(r"paged_decode_attention")


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
            for name, scope_text, start, dur, _stats in line["events"]:
                if not any(s <= start < t for s, t in steps):
                    continue
                if ATTN.search(scope_text):
                    ops.append(["attn", start, dur])
                if KERNEL.search(name) or KERNEL.search(scope_text):
                    ops.append(["kernel", start, dur])
    its = _program_spans.iterations(_program_spans.load(path))
    return {"programs": base["programs"], "ops": ops,
            "iterations": [
                {k: it["args"][k] for k in
                 ("window_pages_in_use", "window_pages_total")}
                for it in its if it["args"].get("window_pages_total")]}


def of_run(ctx: dict) -> dict | None:
    """The plain form of the run whose ``ctx`` this is; None for an untraced
    run or one that wrote no profile."""
    if not ctx.get("trace"):
        return None
    return run_profile()


def roofline(ctx: dict, metric: str, ops_name: str, need_of):
    """``_part_roofline.read`` over this file's plain form: the entry of the
    family's ``decode_step`` count that ``need_of`` picks, at the window's
    mean batch and cached length, against the device time a decode step
    spends in the operations filed as ``ops_name``; None where the run has
    no profile, the family no count, or the program no such operation."""
    plain = of_run(ctx)
    c = ctx["counters"]
    count = getattr(ctx["family"], "decode_step", None)
    if not plain or count is None or not c.get("mean_batch") \
            or not c.get("mean_cached"):
        return None
    seconds = _scoped_ops.scope_seconds_a_step(plain, ops_name)
    serving = ctx["config"]["serving"]
    need = need_of(count(
        ctx["widths"], c["mean_batch"], c["mean_cached"],
        weight_bytes=serving["weight_bytes"], kv_bytes=serving["kv_bytes"],
    ))
    if seconds is None or need is None:
        return None
    share = flops.roofline_share(
        need["flops"], need["bytes"], seconds,
        ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"])
    operands = {**need, **share, "mean_batch": c["mean_batch"],
                "mean_cached": c["mean_cached"],
                f"{ops_name}_calls": sum(
                    1 for name, _s, _d in plain["ops"] if name == ops_name),
                "decode_steps_traced": len(plain["programs"]["decode_step"])}
    ctx["operands"][metric] = operands
    log(f"{metric}: {share['bound']}-bound; {operands}")
    return share["pct"]
