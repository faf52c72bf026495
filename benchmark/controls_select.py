"""Controls of ``correct`` for a serving cell whose block selects the keys it
reads. The PROGRAM is made coarser while the reference keeps the stated model:

- ``bits8`` / ``bits4``: the cell's weights rounded to that many bits a column
  (``controls_rounded.rounded``; ``controls_rounded.py --bits 8`` is the same
  control and gives the same verdict, but its ``--dump`` knows nothing of the
  margins this family's ``judged`` takes);
- ``selector8`` (the selector's cache rounded to 8 bits) and ``widen4`` (four
  times ``topk`` rows read), through the family's ``program_control``
  (``families/keye_vl2.py``);
- ``sound``: nothing changed, for ``--dump``.

Each but ``sound`` has to come out NOT correct, or ``PERF.md`` says which limit
cannot see it and why.

    python3 benchmark/controls_select.py --workload <cell> --control bits8|bits4|selector8|widen4|sound
        --seeds 11,12,13 [--seconds 12] [--check-requests 6] [--dump chiprun_out/<dir>]

``--dump`` writes, a file a seed, every checked request's gaps as the reference
gives them and the reference's own margin at each served position (before the
family judges them): what the check's limits are set from. Run by hand on the
chip; the benchmark's own runs never run this.

    python3 benchmark/controls_select.py --workload <cell> --replay chiprun_out/<dir> [...]

judges dumped requests again, anywhere, under the limits the configuration
states NOW and as ``drive_serve.run`` holds them (the widest of what the
family's ``judged`` gives back, over a run's requests, against ``gap_limit``);
of a dump of more requests than the cell checks, also every sample the harness
could have drawn (the longest and ``check.requests - 1`` others).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, spec  # noqa: E402
from benchmark.controls_rounded import RoundedFamily, rounded  # noqa: E402
from benchmark.harness import log  # noqa: E402


class ControlledFamily(RoundedFamily):
    """The cell's family, but for the hand-over: the program is built from
    the weights or the widths the control gives back; the reference
    (``make_weights``, ``served_gaps``, ``judged``) is untouched."""

    def __init__(self, family, control: str, dump: bool):
        super().__init__(family, None, dump)
        self._control = control

    def build_program_model(self, w, weights, traffic):
        if self._control.startswith("bits"):
            bits = int(self._control[4:])
            weights = rounded(weights, float(2 ** (bits - 1) - 1))
        elif self._control != "sound":
            w = self._family.program_control(self._control, w)
        return self._family.build_program_model(w, weights, traffic)

    def token_gaps(self, params, w, sequence, prompt_len, control=None):
        gaps, control_gaps, margins = self._family.served_gaps(
            params, w, sequence, prompt_len, control)
        if self._dump:
            self.rows.append({"prompt_len": int(prompt_len),
                              "gaps": [float(g) for g in gaps],
                              "margins": [float(m) for m in margins]})
        return self._family.judged(gaps, w, margins, prompt_len), control_gaps


def replay(cell: dict, paths: list) -> int:
    """The dumps under ``paths`` judged again; returns how many runs came
    out the other way (a ``sound`` dump not correct, another correct)."""
    import glob
    import itertools

    import numpy as np

    # not of the contract: what a family of this kind has beside it
    judged = getattr(cell["family"], "judged")
    w = cell["family"].widths(cell["config"])
    limit, take = w["gap_limit"], int(cell["traffic"]["check"]["requests"])
    other_way = 0
    for path in sorted(sum((glob.glob(os.path.join(ROOT, d, "*.json"))
                            for d in paths), [])):
        with open(path) as f:
            run = json.load(f)
        sound = run.get("control", f"bits{run.get('bits')}") in (
            "sound", "bits16")
        reads = [float(np.max(judged(
            np.asarray(r["gaps"]), w, np.asarray(r["margins"]),
            r["prompt_len"]))) for r in run["requests"]]
        correct = max(reads) <= limit
        other_way += correct != sound
        line = (f"replay {os.path.basename(path)}: {len(reads)} requests, "
                f"widest {max(reads):.6g} (limit {limit}): "
                f"{'correct' if correct else 'NOT correct'}")
        if len(reads) > take:
            drawn = [max(reads[0], *(reads[i] for i in c))
                     for c in itertools.combinations(
                         range(1, len(reads)), take - 1)]
            line += (f"; of {len(drawn)} samples of {take}, "
                     f"{sum(d <= limit for d in drawn)} correct "
                     f"({min(drawn):.6g} to {max(drawn):.6g})")
        log(line)
    log(f"dumps that came out the other way: {other_way}")
    return other_way


def main(argv=None) -> int:
    from benchmark import drive_serve

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--replay", nargs="+", default=[])
    ap.add_argument("--control", default="sound")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--check-requests", type=int, default=0)
    ap.add_argument("--dump", default="")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, ROOT)
    if args.replay:
        return 1 if replay(cell, args.replay) else 0
    if not args.seeds:
        ap.error("--seeds, or --replay")
    device = harness.require_tpu(int(cell["cell"]["chips"]))
    harness.enable_cache(device["platform"])
    family = cell["family"] = ControlledFamily(
        cell["family"], args.control, bool(args.dump))
    if args.check_requests:
        traffic = cell["traffic"]
        cell["traffic"] = {**traffic, "check": {
            **traffic["check"], "requests": args.check_requests}}
    came_out_correct = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        run = types.SimpleNamespace(seed=seed, seconds=args.seconds, trace=0)
        out = drive_serve.run(cell, run, time.perf_counter(),
                              harness.CompileWatch())
        row = {"seed": seed, "control": args.control,
               "correct": out["correct"],
               "compared": {n: [v, lim] for n, v, lim in out["compared"]},
               "attempted": out["attempted"], "failed": out["failed"],
               "serve_tokens_per_s": out["e2e"]["serve_tokens_per_s"]}
        log(f"control {args.workload}: {json.dumps(row)}")
        if args.dump:
            os.makedirs(os.path.join(ROOT, args.dump), exist_ok=True)
            with open(os.path.join(
                    ROOT, args.dump,
                    f"{args.control}_seed{seed}.json"), "w") as f:
                json.dump({**row, "requests": family.rows}, f)
            family.rows = []
        came_out_correct += bool(row["correct"])
    if args.control == "sound":
        log(f"sound runs that came out correct: {came_out_correct}")
        return 0 if came_out_correct == len(args.seeds.split(",")) else 1
    log(f"runs that came out correct (a control should have none): "
        f"{came_out_correct}")
    return 1 if came_out_correct else 0


if __name__ == "__main__":
    sys.exit(main())
