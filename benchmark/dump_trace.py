"""A trace as JSON, to look at by hand (how ``layer_metrics/programs.json``
was found) and to cut fixtures from.

    python3 benchmark/dump_trace.py <out-stem> <from_s> <to_s> [<max events a line>]

reads the newest ``.bench_trace`` of this checkout and writes
``<out-stem>.summary.json`` (every plane and line with its commonest event
names) and ``<out-stem>.cut.json`` (the events that start between the two
offsets from the trace's first event, in ``trace_reduce``'s plain form).
"""

from __future__ import annotations

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402


def main(argv) -> int:
    out, t_from, t_to = argv[0], float(argv[1]), float(argv[2])
    cap = int(argv[3]) if len(argv) > 3 else 10**9
    paths = sorted(glob.glob(os.path.join(
        ROOT, ".bench_trace", "plugins", "profile", "*", "*.xplane.pb")))
    trace = trace_reduce.load_xplane(paths[-1])
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    summary = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            names = {}
            for e in line["events"]:
                names[e[0]] = names.get(e[0], 0) + 1
            summary.append({
                "plane": plane["name"], "line": line["name"],
                "n": len(line["events"]),
                "names": sorted(names.items(), key=lambda kv: -kv[1])[:25]})
    with open(out + ".summary.json", "w") as f:
        json.dump(summary, f)
    t0 = min(e[1] for p in trace["planes"] for l in p["lines"] for e in l["events"])
    cut = {"planes": []}
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            events = [[e[0], e[1] - t0, e[2]] for e in line["events"]
                      if t_from * 1e9 <= e[1] - t0 < t_to * 1e9][:cap]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            cut["planes"].append({"name": plane["name"], "lines": lines})
    with open(out + ".cut.json", "w") as f:
        json.dump(cut, f)
    print(f"dumped {out}: {os.path.getsize(out + '.cut.json')} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
