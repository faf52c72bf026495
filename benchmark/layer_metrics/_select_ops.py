"""What the four metrics of a block that selects its keys share: the device
time of a decode step and of a prefill chunk under the program's ``attn/index``
and ``attn/sparse`` scopes, and the selection's counters on the
``serving/collect`` span. ``_scoped_ops.SCOPES`` is fixed to the two scopes of
PR 28, so these scopes' patterns live here; the profile is parsed by
``_scoped_ops.read_planes``. No entry of BENCHMARK.json names this file, so it
is no metric.

Plain form::

    {"programs": {"decode_step": [[start_ns, dur_ns], ...],
                  "prefill_chunk": [[start_ns, dur_ns], ...]},
     "ops": [["index" | "sparse", start_ns, dur_ns], ...],   # of decode steps
     "chunk_ops": [["index" | "sparse", start_ns, dur_ns], ...],  # of chunks
     "collect": [{"keys_cached", "keys_selected"}, ...]}

An operation is ``index`` where its scope path names ``attn/index`` (the
indexer's projections, the selector key's write, the scores, the selection;
also where that scope is opened inside ``attn/sparse``), else ``sparse`` where
it names ``attn/sparse`` (norm to ``wo``, the gather of the selected rows, the
attention over them). A program that names no such scope and counts no
selection (any other family, the parent of the PR that brought this) gives
none: the readers return None and the metrics are left out of the line.
"""

from __future__ import annotations

import functools
import glob
import os
import re

from benchmark import flops, spec
from benchmark.harness import log
from benchmark.layer_metrics import _scoped_ops
from benchmark.trace_reduce import merge, total

INDEX = re.compile(r"(^|/)attn/index(/|$)")
SPARSE = re.compile(r"(^|/)attn/sparse(/|$)")
COUNTERS = ("keys_cached", "keys_selected")


def run_profile(root: str = spec.ROOT) -> dict | None:
    """The plain form of the profile the run has just written under
    ``root``; None without one."""
    found = glob.glob(os.path.join(
        root, ".bench_trace", "plugins", "profile", "*", "*.xplane.pb"))
    return load(found[0]) if found else None


def kind_of(scope_text: str) -> str | None:
    if INDEX.search(scope_text):
        return "index"
    return "sparse" if SPARSE.search(scope_text) else None


@functools.lru_cache(maxsize=1)
def load(path: str) -> dict:
    base = _scoped_ops.load(path)
    table = spec.load_trace_table()
    device = re.compile(table["device_plane"])
    host = re.compile(table["host_plane"])
    inside = {name: merge([[s, s + d] for s, d in base["programs"][program]])
              for name, program in (("ops", "decode_step"),
                                    ("chunk_ops", "prefill_chunk"))}
    out = {"ops": [], "chunk_ops": []}
    collect, seen_device = [], False
    for plane in _scoped_ops.read_planes(
            path, lambda name: bool(device.search(name) or host.search(name))):
        if host.search(plane["name"]):
            for line in plane["lines"]:
                for name, _scope, _start, _dur, stats in line["events"]:
                    if name == "serving/collect" and COUNTERS[0] in stats:
                        collect.append({k: float(stats[k]) for k in COUNTERS})
            continue
        if seen_device:
            continue  # the first device plane is enough: one chip a cell
        seen_device = True
        for line in plane["lines"]:
            if line["name"] not in table["op_lines"]:
                continue
            for _name, scope_text, start, dur, _stats in line["events"]:
                kind = kind_of(scope_text)
                if kind is None:
                    continue
                for where, spans in inside.items():
                    if any(s <= start < t for s, t in spans):
                        out[where].append([kind, start, dur])
    return {"programs": base["programs"], **out, "collect": collect}


def of_run(ctx: dict) -> dict | None:
    """The plain form of the run whose ``ctx`` this is; None for an untraced
    run or one that wrote no profile."""
    if not ctx.get("trace"):
        return None
    return run_profile()


def chunk_ms(plain: dict, kind: str) -> float | None:
    """Device ms a prefill chunk under the ``kind`` scope: the union of the
    scope's operations inside the chunk programs over the chunks traced."""
    chunks = plain["programs"].get("prefill_chunk") or []
    spans = [[s, s + d] for name, s, d in plain["chunk_ops"] if name == kind]
    if not chunks or not spans:
        return None
    return total(merge(spans)) / 1e6 / len(chunks)


def roofline(ctx: dict, metric: str, kind: str, part: str):
    """The ``part`` of the family's ``decode_step`` count at the window's
    mean batch and cached length against the device time a decode step
    spends under the ``kind`` scope; None where the run has no profile, the
    family no such part, or the program no such scope."""
    plain = of_run(ctx)
    c = ctx["counters"]
    count = getattr(ctx["family"], "decode_step", None)
    if not plain or count is None or not c.get("mean_batch") \
            or not c.get("mean_cached"):
        return None
    seconds = _scoped_ops.scope_seconds_a_step(plain, kind)
    serving = ctx["config"]["serving"]
    need = count(
        ctx["widths"], c["mean_batch"], c["mean_cached"],
        weight_bytes=serving["weight_bytes"], kv_bytes=serving["kv_bytes"],
    ).get("parts", {}).get(part)
    if seconds is None or need is None:
        return None
    share = flops.roofline_share(
        need["flops"], need["bytes"], seconds,
        ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"])
    operands = {**need, **share, "mean_batch": c["mean_batch"],
                "mean_cached": c["mean_cached"],
                "decode_steps_traced": len(plain["programs"]["decode_step"])}
    ctx["operands"][metric] = operands
    log(f"{metric}: {share['bound']}-bound; {operands}")
    return share["pct"]
