"""What the three device metrics of a block that holds a state a slot share:
the device time of a decode step and of a prefill chunk under the program's
``ssm/proj``, ``ssm/update`` and ``ssm/scan`` scopes, the real tokens a
chunk carried (``tokens`` on the ``serving/prefill_chunk`` span) and the
bytes of state a step moved (``state_bytes`` on the ``serving/step`` span).
``_scoped_ops.SCOPES`` is fixed to the two scopes of PR 28, so this scope's
pattern lives here; the profile is parsed by ``_scoped_ops.read_planes``. No
entry of BENCHMARK.json names this file, so it is no metric.

Plain form::

    {"programs": {"decode_step": [[start_ns, dur_ns], ...],
                  "prefill_chunk": [[start_ns, dur_ns], ...]},
     "ops": [["ssm", start_ns, dur_ns], ...],        # of decode steps
     "chunk_ops": [["ssm", start_ns, dur_ns], ...],  # of prefill chunks
     "chunk_tokens": [n, ...],                       # a traced chunk's tokens
     "step_state_bytes": [bytes, ...]}               # a traced step's state

An operation is ``ssm`` where its scope path names ``ssm/<part>`` (the
mixer's projections, convolution, gated norm; the step's update of the state;
the chunk's blocks and the state they hand on). XLA's own fusions of the
recurrence are the kernels: there is no custom call to tell apart. A program
that names no such scope (any other family, the parent of the PR that
brought this) gives none: the readers return None and the metrics are left
out of the line.
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

SSM = re.compile(r"(^|/)ssm/(proj|update|scan)(/|$)")


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
    host = re.compile(table["host_plane"])
    inside = {name: merge([[s, s + d] for s, d in base["programs"][program]])
              for name, program in (("ops", "decode_step"),
                                    ("chunk_ops", "prefill_chunk"))}
    out = {"ops": [], "chunk_ops": []}
    tokens, state_bytes, seen_device = [], [], False
    for plane in _scoped_ops.read_planes(
            path, lambda name: bool(device.search(name) or host.search(name))):
        if host.search(plane["name"]):
            for line in plane["lines"]:
                for name, _scope, _start, _dur, stats in line["events"]:
                    if name == "serving/prefill_chunk" and "tokens" in stats:
                        tokens.append(float(stats["tokens"]))
                    if name == "serving/step" and "state_bytes" in stats:
                        state_bytes.append(float(stats["state_bytes"]))
            continue
        if seen_device:
            continue  # the first device plane is enough: one chip a cell
        seen_device = True
        for line in plane["lines"]:
            if line["name"] not in table["op_lines"]:
                continue
            for _name, scope_text, start, dur, _stats in line["events"]:
                if not SSM.search(scope_text):
                    continue
                for where, spans in inside.items():
                    if any(s <= start < t for s, t in spans):
                        out[where].append(["ssm", start, dur])
    return {"programs": base["programs"], **out, "chunk_tokens": tokens,
            "step_state_bytes": state_bytes}


def of_run(ctx: dict) -> dict | None:
    """The plain form of the run whose ``ctx`` this is; None for an untraced
    run or one that wrote no profile."""
    if not ctx.get("trace"):
        return None
    return run_profile()


def chunk_seconds(plain: dict) -> float | None:
    """Device seconds a prefill chunk under the ``ssm`` scopes: the union of
    their operations inside the chunk programs over the chunks traced."""
    chunks = plain["programs"].get("prefill_chunk") or []
    spans = [[s, s + d] for _name, s, d in plain["chunk_ops"]]
    if not chunks or not spans:
        return None
    return total(merge(spans)) / 1e9 / len(chunks)


def step_share(plain: dict) -> float | None:
    """Of the decode steps' device time, the share under the ``ssm`` scopes."""
    steps = plain["programs"].get("decode_step") or []
    seconds = _scoped_ops.scope_seconds_a_step(plain, "ssm")
    if not steps or seconds is None:
        return None
    return 100.0 * seconds * len(steps) / (sum(d for _s, d in steps) / 1e9)


def _count(ctx: dict):
    c = ctx["counters"]
    count = getattr(ctx["family"], "decode_step", None)
    if count is None or not c.get("mean_batch") or not c.get("mean_cached"):
        return None
    serving = ctx["config"]["serving"]
    return count(ctx["widths"], c["mean_batch"], c["mean_cached"],
                 weight_bytes=serving["weight_bytes"],
                 kv_bytes=serving["kv_bytes"])


def _share(ctx: dict, metric: str, need: dict, seconds: float, **more):
    share = flops.roofline_share(
        need["flops"], need["bytes"], seconds,
        ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"])
    operands = {**need, **share, **more}
    ctx["operands"][metric] = operands
    log(f"{metric}: {share['bound']}-bound; {operands}")
    return share["pct"]


def decode_roofline(ctx: dict, metric: str):
    """The ``ssm`` part of the family's ``decode_step`` count (the mixers'
    matrices, every decoding slot's state and tail in and out) at the
    window's mean batch against the device time a decode step spends under
    the ``ssm`` scopes; None where the run has no profile, the family no such
    part, or the program no such scope."""
    plain, count = of_run(ctx), _count(ctx)
    need = (count or {}).get("parts", {}).get("ssm")
    seconds = plain and _scoped_ops.scope_seconds_a_step(plain, "ssm")
    if not seconds or need is None:
        return None
    c = ctx["counters"]
    return _share(ctx, metric, need, seconds, mean_batch=c["mean_batch"],
                  decode_steps_traced=len(plain["programs"]["decode_step"]))


def scan_roofline(ctx: dict, metric: str):
    """The family's ``scan`` count (a real token's operations in the chunk
    form with the mixers' two matrices; a chunk's bytes: those matrices and
    one slot's state in and out) at the traced chunks' mean real tokens
    against the device time a prefill chunk spends under the ``ssm``
    scopes."""
    plain, count = of_run(ctx), _count(ctx)
    scan = (count or {}).get("scan")
    seconds = plain and chunk_seconds(plain)
    if not seconds or scan is None or not plain["chunk_tokens"]:
        return None
    mean = sum(plain["chunk_tokens"]) / len(plain["chunk_tokens"])
    need = {"flops": scan["flops_a_token"] * mean,
            "bytes": scan["bytes_a_chunk"]}
    return _share(ctx, metric, need, seconds, mean_chunk_tokens=mean,
                  chunk_spans=len(plain["chunk_tokens"]),
                  chunks_traced=len(plain["programs"]["prefill_chunk"]))
