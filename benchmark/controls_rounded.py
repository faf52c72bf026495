"""A control of ``correct`` for a serving cell whose program has no lower
path of its own to switch on: the program is handed the cell's weights
rounded to ``--bits`` bits a column (symmetric, per output column, the
values kept in the weights' own dtype, so every shape and every program is
the cell's own), while the reference keeps the stated weights. It has to
come out NOT correct.

    python3 benchmark/controls_rounded.py --workload <cell> --seeds 11,12,13 [--seconds 12] [--bits 4]
        [--check-requests 24] [--dump chiprun_out/<dir>]

``--bits 16`` rounds nothing: the sound program through the same door, for
``--dump``, which writes every checked request's gaps as the reference gives
them (before the family judges them) and each served position's narrowest
routing margin, a file a seed: what the check's limits are set from.

``controls.py`` switches the program's own int4 path on, which is the
better control where the program has one for every matrix it holds; this
file is for a configuration whose stacked expert weights that path does not
take yet. Run by hand on the chip; the benchmark's own runs never run this.
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
from benchmark.harness import log  # noqa: E402


def rounded(weights, levels: float):
    """Every leaf of two or more dimensions rounded to ``levels`` steps each
    side of zero, a column (the last axis' entries share nothing; the scale
    is the largest magnitude over the axis before it), in its own dtype and
    in its own buffer: a second copy of a 10 GB tree fits no chip."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0)
    def one(x):
        f = x.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f), axis=-2, keepdims=True) / levels
        scale = jnp.where(scale == 0, 1.0, scale)
        return (jnp.clip(jnp.round(f / scale), -levels, levels) * scale
                ).astype(x.dtype)

    return jax.tree.map(lambda x: one(x) if x.ndim >= 2 else x, weights)


class RoundedFamily:
    """The cell's family, but for the hand-over: the program gets rounded
    weights (``levels`` None: the stated ones); the reference
    (``make_weights``, ``token_gaps``) is untouched. ``rows`` collects, a
    checked request, what ``--dump`` writes."""

    def __init__(self, family, levels, dump: bool):
        self._family, self._levels, self._dump = family, levels, dump
        self.rows = []

    def __getattr__(self, name):
        return getattr(self._family, name)

    def build_program_model(self, w, weights, traffic):
        if self._levels is not None:
            weights = rounded(weights, self._levels)
        return self._family.build_program_model(w, weights, traffic)

    def token_gaps(self, params, w, sequence, prompt_len, control=None):
        plain = getattr(self._family, "served_gaps", None)
        if not self._dump or plain is None:
            return self._family.token_gaps(params, w, sequence, prompt_len,
                                           control)
        gaps, control_gaps, margins = plain(params, w, sequence, prompt_len,
                                            control)
        self.rows.append({"prompt_len": int(prompt_len),
                          "gaps": [float(g) for g in gaps],
                          "margins": [float(m) for m in margins]})
        return self._family.judged(gaps, w), control_gaps


def main(argv=None) -> int:
    from benchmark import drive_serve

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--check-requests", type=int, default=0)
    ap.add_argument("--dump", default="")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, ROOT)
    device = harness.require_tpu(int(cell["cell"]["chips"]))
    harness.enable_cache(device["platform"])
    sound = args.bits >= 16
    family = cell["family"] = RoundedFamily(
        cell["family"], None if sound else float(2 ** (args.bits - 1) - 1),
        bool(args.dump))
    if args.check_requests:
        traffic = cell["traffic"]
        cell["traffic"] = {**traffic, "check": {
            **traffic["check"], "requests": args.check_requests}}
    failed_to_fail = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        run = types.SimpleNamespace(seed=seed, seconds=args.seconds, trace=0)
        out = drive_serve.run(cell, run, time.perf_counter(),
                              harness.CompileWatch())
        row = {"seed": seed, "bits": args.bits, "correct": out["correct"],
               "compared": {n: [v, lim] for n, v, lim in out["compared"]},
               "attempted": out["attempted"], "failed": out["failed"],
               "serve_tokens_per_s": out["e2e"]["serve_tokens_per_s"]}
        log(f"control {args.workload}: {json.dumps(row)}")
        if args.dump:
            os.makedirs(os.path.join(ROOT, args.dump), exist_ok=True)
            with open(os.path.join(ROOT, args.dump,
                                   f"bits{args.bits}_seed{seed}.json"), "w") as f:
                json.dump({**row, "requests": family.rows}, f)
            family.rows = []
        failed_to_fail += bool(row["correct"]) != sound
    log(f"runs that came out the other way (should be 0): {failed_to_fail}")
    return 1 if failed_to_fail else 0


if __name__ == "__main__":
    sys.exit(main())
