"""Finds a cell's files by the names in BENCHMARK.json. Nothing here knows a
cell, a configuration, a model family, a traffic mix or a metric by name: a
later PR adds files and entries, and edits no file that is there (see
README.md)."""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json ({known})")


def find_traffic(name: str, root: str = ROOT, bench: dict | None = None) -> str:
    """The data file of a traffic mix: ``<a path of the benchmark>/traffic/
    <name>.json`` in the first directory of ``paths`` that has it."""
    bench = bench or load_benchmark(root)
    for base in bench["paths"]:
        path = os.path.join(root, base, "traffic", name + ".json")
        if os.path.exists(path):
            return path
    raise SpecError(f"no traffic/{name}.json under {bench['paths']}")


def _load_module(name: str, path: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_family(name: str, root: str = ROOT, bench: dict | None = None):
    """A model family's module: ``<a path of the benchmark>/families/
    <name>.py`` in the first directory of ``paths`` that has it. The one
    place that knows a model (README.md, "A model family")."""
    bench = bench or load_benchmark(root)
    if not NAME.match(str(name)):
        raise SpecError(f"family {name!r}: not a name")
    for base in bench["paths"]:
        path = os.path.join(root, base, "families", name + ".py")
        if os.path.exists(path):
            return _load_module(
                "benchmark_family_" + re.sub(r"\W", "_", name), path)
    raise SpecError(f"no families/{name}.py under {bench['paths']}")


def family_of(config: dict, root: str = ROOT, bench: dict | None = None):
    """The family a configuration file names. A file that names none is an
    error, not the first family there was."""
    if "family" not in config:
        raise SpecError(
            f"configuration {config.get('name', '?')!r} names no family: "
            f"add \"family\": \"<name>\" for a families/<name>.py under "
            f"one of the benchmark's paths")
    return load_family(config["family"], root, bench)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """Everything one run needs: the cell, its configuration file, the
    family that file names, its traffic file, the metrics it reports, each
    resolved by name."""
    bench = load_benchmark(root)
    cell = _by_name(bench["workloads"], workload, "workload")
    config_entry = _by_name(bench["configs"], cell["config"], "config")
    config = _load_json(os.path.join(root, config_entry["file"]))
    traffic = _load_json(find_traffic(cell["traffic"], root, bench))

    # a metric with a ``workloads`` list is reported in those cells; one
    # without, in every cell (end to end) or in every cell that reports the
    # end-to-end metric it moves (per layer)
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return {
        "root": root, "bench": bench, "cell": cell, "config": config,
        "family": family_of(config, root, bench), "traffic": traffic,
        "end_to_end": end_to_end, "per_layer": per_layer,
        "run_seconds": bench["run_seconds"],
    }


def load_layer_metric(name: str, root: str = ROOT, bench: dict | None = None):
    """A per-layer metric's reader: ``layer_metrics/<name>.json`` (a reader
    kind of ``readers.py`` with its parameters) or ``layer_metrics/<name>.py``
    (a module with ``read(ctx)``), whichever a path of the benchmark holds."""
    bench = bench or load_benchmark(root)
    for base in bench["paths"]:
        stem = os.path.join(root, base, "layer_metrics", name)
        if os.path.exists(stem + ".json"):
            return _load_json(stem + ".json")
        if os.path.exists(stem + ".py"):
            mod = _load_module(
                "layer_metric_" + re.sub(r"\W", "_", name), stem + ".py")
            return {"reader": "python", "read": mod.read}
    raise SpecError(f"no layer_metrics/{name}.json or .py under {bench['paths']}")


def load_trace_table(root: str = ROOT, bench: dict | None = None) -> dict:
    """How the trace names things: ``layer_metrics/programs.json``, with the
    program and kernel families that any ``layer_metrics/*.json`` of any
    path adds under its own ``programs`` and ``kernels`` keys."""
    bench = bench or load_benchmark(root)
    table = load_layer_metric("programs", root, bench)
    for base in bench["paths"]:
        folder = os.path.join(root, base, "layer_metrics")
        if not os.path.isdir(folder):
            continue
        for fname in sorted(os.listdir(folder)):
            if fname.endswith(".json") and fname != "programs.json":
                extra = _load_json(os.path.join(folder, fname))
                for group in ("programs", "kernels"):
                    table[group] = {**table[group], **extra.get(group, {})}
    return table


def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks. A kind the table lacks is an error."""
    table = _load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["chips"]:
        raise SpecError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(table['chips'])}); add it with its source")
    return table["chips"][device_kind]
