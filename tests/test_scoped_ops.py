"""``benchmark/layer_metrics/_scoped_ops.py``: the arithmetic of the four
metrics of the latent-attention / routed-expert block over plain data, the
profile read raw (no protocol-buffer library), and the recorded cut of a real
traced run that ``tests/conftest.py`` hands the benchmark's synthetic run."""

import glob
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import spec  # noqa: E402
from benchmark.layer_metrics import _scoped_ops  # noqa: E402

FIXTURE = os.path.join(REPO, "tests", "benchmark", "fixtures",
                       "scoped_ops_small.json")

PLAIN = {
    "programs": {"decode_step": [[0.0, 40e6], [100e6, 40e6]],
                 "prefill_chunk": [[40e6, 50e6], [140e6, 70e6], [300e6, 60e6]]},
    # a loop (10-30 ms) and its body (12-20 ms) are both events: counted once
    "ops": [["moe", 10e6, 20e6], ["moe", 12e6, 8e6], ["mla", 2e6, 4e6],
            ["moe", 110e6, 10e6], ["mla", 101e6, 2e6]],
    "collect": [{"experts_hit": 96.0, "expert_load_max": 7.0,
                 "experts_total": 128.0, "routed_tokens": 32.0},
                {"experts_hit": 64.0, "expert_load_max": 5.0,
                 "experts_total": 128.0, "routed_tokens": 16.0}],
}


def test_scope_time_is_the_union_of_its_operations_over_the_steps():
    assert _scoped_ops.scope_seconds_a_step(PLAIN, "moe") == pytest.approx(0.015)
    assert _scoped_ops.scope_seconds_a_step(PLAIN, "mla") == pytest.approx(0.003)
    assert _scoped_ops.scope_seconds_a_step(PLAIN, "flash") is None
    assert _scoped_ops.program_median_s(PLAIN, "prefill_chunk") == pytest.approx(0.06)
    assert _scoped_ops.program_median_s({"programs": {}}, "prefill_chunk") is None


@pytest.mark.parametrize("text,scope", [
    ("jit(step)/jit(main)/moe/experts/ragged_dot_general", "moe"),
    ("jit(step)/moe/route/top_k", "moe"),
    ("jit(chunk)/mla/dot_general", "mla"),
    ("jit(step)/mla", "mla"),
    ("jit(step)/smoe/x", None), ("jit(step)/mlab/dot", None), ("", None)])
def test_a_scope_path_names_its_part(text, scope):
    assert _scoped_ops.scope_of(text) == scope


def _ctx(cell, plain, monkeypatch):
    monkeypatch.setattr(_scoped_ops, "run_profile", lambda: plain)
    return {"trace": {"programs": {}}, "counters": {"mean_batch": 32.0,
                                                    "mean_cached": 1500.0},
            "family": cell["family"], "widths": cell["family"].widths(cell["config"]),
            "config": cell["config"], "peaks": spec.load_peaks("TPU v5 lite"),
            "operands": {}}


def test_the_four_readers_over_plain_data(monkeypatch):
    cell = spec.load_cell("serve_backlog_kanana", REPO)
    ctx = _ctx(cell, PLAIN, monkeypatch)
    read = {m: spec.load_layer_metric(m, REPO)["read"] for m in (
        "moe_decode_roofline", "mla_decode_roofline", "experts_hit_pct",
        "prefill_chunk_ms")}
    assert read["experts_hit_pct"](ctx) == pytest.approx(100 * 80.0 / 128.0)
    assert read["prefill_chunk_ms"](ctx) == pytest.approx(60.0)
    need = cell["family"].decode_step(
        ctx["widths"], 32.0, 1500.0, weight_bytes=2, kv_bytes=2)["parts"]
    moe = read["moe_decode_roofline"](ctx)
    assert moe == pytest.approx(100 * need["moe"]["bytes"] / 819e9 / 0.015)
    assert ctx["operands"]["moe_decode_roofline"]["bound"] == "memory"
    mla = read["mla_decode_roofline"](ctx)
    assert mla == pytest.approx(100 * need["mla"]["bytes"] / 819e9 / 0.003)
    # a program that names no scope and counts no routing (the parent of
    # PR 28, another family): nothing to read, nothing raised
    bare = {"programs": PLAIN["programs"], "ops": [], "collect": []}
    ctx = _ctx(cell, bare, monkeypatch)
    assert read["moe_decode_roofline"](ctx) is None
    assert read["mla_decode_roofline"](ctx) is None
    assert read["experts_hit_pct"](ctx) is None
    for reader in read.values():
        assert reader({**ctx, "trace": {}}) is None  # an untraced run


def test_the_profile_is_read_raw(tmp_path):
    """A profile written here, on the CPU: the ``serving/collect`` span's
    arguments come back from the file's own bytes, a float among them."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(
            "serving/collect", experts_hit=91.5, expert_load_max=7,
            experts_total=128, routed_tokens=26):
        jax.jit(lambda x: x @ x)(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    plain = _scoped_ops.load(path)
    assert plain["collect"] == [{"experts_hit": 91.5, "expert_load_max": 7.0,
                                 "experts_total": 128.0, "routed_tokens": 26.0}]
    assert plain["ops"] == [] and plain["programs"]["decode_step"] == []


def test_the_recorded_cut_feeds_every_reader():
    with open(FIXTURE) as f:
        recorded = json.load(f)
    plain = recorded["plain"]
    assert recorded["what"] and len(plain["programs"]["decode_step"]) >= 2
    for scope in ("moe", "mla"):
        assert _scoped_ops.scope_seconds_a_step(plain, scope) > 0
    assert _scoped_ops.program_median_s(plain, "prefill_chunk") > 0
    assert all(r["experts_total"] == 128 for r in plain["collect"])
