"""The training driver: the program's trainer, its compiled window and its
state, built once in set-up, driven from the seed through the first steps
(which the reference follows) and then handed to the measured window.

The benchmark makes the trainer (``SingleTrainer`` on one chip,
``SynchronousDistributedTrainer`` across chips), takes its ``WorkerCore``
and places state through the trainer's own methods, and then calls
``core.window`` on ``window`` staged batches a call, as the trainers' loops
do (the mix's ``window``: 1 is one optimizer step a call, k the scanned
window of k steps with one fetch of its k losses). The trainers' ``train()``
runs whole epochs and hands out no state on the way, which the check needs
(PERF.md, open questions).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import loadgen, reference
from benchmark.harness import Profile, annotate, log, peak_bytes


def _first_device(tree):
    import jax

    return jax.tree.map(lambda x: x.addressable_shards[0].data, tree)


def worst_leaf_gap(program: np.ndarray, ref: np.ndarray) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    scale = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(program - ref) / scale))


def compare(program: dict, ref: dict, limits: dict) -> tuple[bool, list]:
    """Each number compared beside its limit."""
    rows = [(f"loss_step{i + 1}_abs_diff", abs(p - r), limits["loss_abs_diff"])
            for i, (p, r) in enumerate(zip(program["losses"], ref["losses"]))]
    rows.append(("first_moment_norm_worst_leaf_gap",
                 worst_leaf_gap(program["moment_norms"], ref["moment_norms"]),
                 limits["moment_norm_gap"]))
    rows.append(("param_change_norm_worst_leaf_gap",
                 worst_leaf_gap(program["change_norms"], ref["change_norms"]),
                 limits["change_norm_gap"]))
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows


class TrainRig:
    """The program's trainer, core and placed state: the one object that
    set-up drives through its first steps and the window then takes."""

    def __init__(self, model, traffic: dict, chips: int, seed: int):
        """``model``: the program's own, as the cell's family built it from
        the seeded weights, with what the mix asks for attached."""
        import jax

        from distkeras_tpu import SingleTrainer, SynchronousDistributedTrainer
        from distkeras_tpu.utils.tree import host_copy

        self.rows = int(traffic["batch_per_chip"]) * chips
        common = dict(
            loss="next_token_crossentropy", learning_rate=traffic["learning_rate"],
            metrics=(), batch_size=int(traffic["batch_per_chip"]), num_epoch=1,
            window=int(traffic["window"]), seed=seed % (2**31),
            compute_dtype=traffic["compute_dtype"])
        if chips == 1:
            self.trainer = SingleTrainer(model, traffic["optimizer"], **common)
        else:
            self.trainer = SynchronousDistributedTrainer(
                model, traffic["optimizer"], num_workers=chips, **common)
        t = self.trainer
        self.core = t._make_core()
        if chips == 1:
            self.params = host_copy(model.params)
            self.state = host_copy(model.state)
            self.opt_state = self.core.init_opt_state(self.params)
            self.sharding = None
        else:
            from jax.sharding import NamedSharding, PartitionSpec

            from distkeras_tpu.parallel.mesh import replicate

            self.params = t._place_params(host_copy(model.params))
            self.state = replicate(host_copy(model.state), t.mesh)
            self.opt_state = t._place_opt_state(self.core, self.params)
            self.sharding = NamedSharding(t.mesh, PartitionSpec(None, "data"))
        self.rng = jax.random.PRNGKey(seed % (2**31))
        self.model = model

    def call(self, batches: list) -> list:
        """One call of the compiled window on its staged batches, one
        optimizer step each; returns every step's loss, fetched to the host
        together (which ends the call)."""
        import jax

        from distkeras_tpu.workers import _metrics_to_records, stack_window

        with annotate("bench/stage_batch"):
            xs, ys = stack_window(
                [{"features": b, "label": b} for b in batches],
                "features", "label")
            if self.sharding is not None:
                xs = jax.device_put(xs, self.sharding)
                ys = jax.device_put(ys, self.sharding)
        with annotate("bench/window_call"):
            (self.params, self.state, self.opt_state, self.rng,
             mets) = self.core.window(
                self.params, self.state, self.opt_state, self.rng, xs, ys)
        with annotate("bench/fetch_loss"):
            records = _metrics_to_records(mets)
        return [float(r["loss"]) for r in records]

    def adam_mu(self):
        for part in self.opt_state:
            if hasattr(part, "mu"):
                return part.mu
        raise RuntimeError("no Adam state in the trainer's optimizer state")


def run(cell: dict, args, t_start: float, watch) -> dict:
    import jax

    family = cell["family"]
    w = family.widths(cell["config"])
    traffic, chips = cell["traffic"], int(cell["cell"]["chips"])
    k = int(traffic["window"])
    check_steps = int(traffic["check"]["steps"])
    if k < 1 or check_steps % k:
        raise ValueError(f"check.steps {check_steps} is not a whole number "
                         f"of windows of {k} steps")
    rng = np.random.default_rng(args.seed)

    rig = TrainRig(
        family.build_program_model(w, family.make_weights(w, args.seed), traffic),
        traffic, chips, args.seed)
    rows = rig.rows

    def next_window():
        return [loadgen.training_batch(traffic, rng, rows, w["seq"], w["vocab"])
                for _ in range(k)]

    # the first steps, through the window's own call and feed. Adam's first
    # moment is read after the first call: at window 1 it is the first
    # gradient as the optimizer got it, times 1 - b1
    first_batches, program = [], {"losses": []}
    for i in range(check_steps // k):
        batches = next_window()
        first_batches += batches
        program["losses"] += rig.call(batches)
        if i == 0:
            program["moment_norms"] = np.asarray(
                reference.leaf_norms(_first_device(rig.adam_mu())))
    program["change_norms"] = np.asarray(reference.leaf_norms_of_difference(
        _first_device(rig.params), rig.model.params))
    for _ in range(int(traffic["warm_calls"])):
        rig.call(next_window())
    log(f"set-up: first losses {program['losses']}; compile {watch.snapshot()}")

    profile = Profile(bool(args.trace), traffic["trace"]["lead_s"],
                      traffic["trace"]["seconds"], cell["root"])
    compiles_before, compile_s = watch.compiles, watch.compile_seconds
    steps = traced_steps = 0
    losses = []
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    while True:
        tracing = profile.state == "tracing"
        losses += rig.call(next_window())
        steps += k
        traced_steps += k * tracing
        now = time.perf_counter()
        if now - t_open >= args.seconds:
            break
        profile.poll(now - t_open)
    window_s = now - t_open
    profile.stop()
    compiled_in_window = watch.compiles - compiles_before
    peak = peak_bytes()
    tokens = steps * rows * w["seq"]
    rate = tokens / window_s / chips
    log(f"window: {steps} steps, {tokens} tokens in {window_s:.4f} s; "
        f"loss first {losses[0]:.4f} last {losses[-1]:.4f}; "
        f"{window_s / steps:.5f} s a step (host clock, {k} step(s) a call, "
        f"the losses fetched each call)")

    # the reference, after the program's state is freed
    del rig
    gc.collect()
    t_ref = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = family.train_readings(
            w, args.seed, first_batches, float(traffic["learning_rate"]),
            moment_after=k)
    ok, compared = compare(program, ref, traffic["check"]["limits"])
    for name, value, limit in compared:
        log(f"check: {name} = {value:.6g} (limit {limit})")
    log(f"check: reference took {time.perf_counter() - t_ref:.2f} s; "
        f"reference losses {ref['losses']}")
    falls = losses[-1] < program["losses"][0]
    log(f"check: loss falls over the run: {falls}")

    return {
        "correct": ok and falls, "attempted": steps, "failed": 0,
        "setup_s": setup_s, "compiled_in_window": compiled_in_window,
        "peak_bytes": peak, "profile": profile, "compared": compared,
        "e2e": {"train_tokens_per_s_per_chip": rate},
        "counters": {"traced_steps": traced_steps, "batch": int(traffic["batch_per_chip"]),
                     "steps": steps, "compile_seconds_setup": compile_s},
        "samples": {},
    }
