"""``sched_offcpu_ms``: the mean, an iteration, of the time the scheduler's
thread stood still where it should have run: its ``serving/iter`` span's
duration less its ``cpu_ns`` (PR 37), less the same difference of the
``serving/collect`` and ``serving/prefill_chunk`` spans inside it, where the
thread waits for the device by design (the fetch, and a chunk's call queueing
behind the step in the air). What is left is the interpreter lock, a lock or
a system call. A mean because the clock ticks (``_thread_spans``). Its log
line gives the stood-still time by phase, the share of iterations dispatched
behind a step in the air (``ahead``) and the slot-steps their collects threw
away (``discarded``)."""

from benchmark.harness import log
from benchmark.layer_metrics import _thread_spans

BY_DESIGN = ("serving/collect", "serving/prefill_chunk")
PHASES = ("serving/admit", "serving/mask", "serving/step_args", "serving/step",
          "serving/collect", "serving/emit", "serving/prefill_chunk")


def _still(rows: list) -> float:
    return sum(d - a["cpu_ns"] for _s, d, a in rows if "cpu_ns" in a)


def read(ctx):
    v = _thread_spans.of_run(ctx)
    its = [it for it in (v or {}).get("its", []) if "cpu_ns" in it["args"]]
    if not its:
        return None
    n = len(its)
    by_phase = {name: sum(_still(it["spans"].get(name, [])) for it in its) / n
                for name in PHASES}
    whole = sum(it["dur_ns"] - it["args"]["cpu_ns"] for it in its) / n
    ahead = sum(it["args"].get("ahead", 0) for it in its)
    log(f"sched_offcpu_ms: n={n} iterations; stood still, mean ms an "
        f"iteration by phase (admit holds its prefill chunks): "
        f"{ {k: round(x / 1e6, 3) for k, x in by_phase.items()} }; whole "
        f"iteration {whole / 1e6:.3f}; ahead in {ahead} of them "
        f"({100.0 * ahead / n:.1f}%), discarded slot-steps "
        f"{sum(it['args'].get('discarded', 0) for it in its)}")
    return (whole - sum(by_phase[name] for name in BY_DESIGN)) / 1e6
