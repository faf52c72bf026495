"""The rest of a run with the harness's look for a chip skipped: the
drivers at a tiny size on the CPU, sound and with the timed path broken
underneath; and ``run.py`` refusing to measure without a TPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import benchmark_tiny_cells as tiny  # noqa: E402

from benchmark import drive_serve, drive_train, harness  # noqa: E402

REPO = tiny.REPO

# whole drivers, threads and compiles: the repo's harness tier, which
# tests/conftest.py schedules after the unit tier
pytestmark = pytest.mark.e2e


def _run(driver, traffic, chips=1, tmp="/tmp", cell=None, **kw):
    import time

    cell = cell or tiny.cell(traffic, chips, root=str(tmp))
    out = driver.run(cell, tiny.args(**kw), time.perf_counter(),
                     harness.CompileWatch())
    assert out["compiled_in_window"] == 0
    return out


def _readings(out) -> dict:
    return {name: value for name, value, _ in out["compared"]}


@pytest.mark.parametrize("chips,window", [(1, 1), (4, 1), (1, 4), (4, 2)],
                         ids=["single", "sync_dp4", "single_w4", "sync_dp4_w2"])
def test_training_run_is_correct_and_counts_its_tokens(chips, window, tmp_path):
    out = _run(drive_train, tiny.train_windowed(window), chips, tmp_path)
    assert out["correct"] is True and out["failed"] == 0
    steps = out["counters"]["steps"]
    assert out["attempted"] == steps > 0 and steps % window == 0
    rate = out["e2e"]["train_tokens_per_s_per_chip"]
    assert rate == pytest.approx(steps * 2 * 64 / 0.6, rel=0.25)


@pytest.mark.parametrize("window", [1, 4])
def test_training_step_that_returns_its_state_unchanged_is_not_correct(
        window, tmp_path, monkeypatch):
    real = drive_train.TrainRig.call

    def frozen(self, batches):
        import jax

        keep = jax.tree.map(jax.numpy.array, (self.params, self.opt_state))
        losses = real(self, batches)
        self.params, self.opt_state = keep
        return losses

    monkeypatch.setattr(drive_train.TrainRig, "call", frozen)
    out = _run(drive_train, tiny.train_windowed(window), 1, tmp_path)
    assert out["correct"] is False


@pytest.mark.parametrize("window", [1, 4])
def test_training_step_that_leaves_out_half_the_batch_is_not_correct(
        window, tmp_path, monkeypatch):
    real = drive_train.TrainRig.call

    def half(self, batches):
        return real(self, [np.concatenate([b[:1], b[:1]]) for b in batches])

    monkeypatch.setattr(drive_train.TrainRig, "call", half)
    out = _run(drive_train, tiny.train_windowed(window), 1, tmp_path)
    assert out["correct"] is False


def test_a_check_that_is_not_whole_windows_is_refused(tmp_path):
    mix = {**tiny.TRAIN, "window": 2}  # check.steps stays 3
    with pytest.raises(ValueError, match="whole number"):
        _run(drive_train, mix, 1, tmp_path)


def test_serving_run_is_correct_and_reports_its_metrics(tmp_path):
    out = _run(drive_serve, tiny.SERVE, 1, tmp_path)
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert set(out["e2e"]) == {"serve_tokens_per_s"}
    assert out["e2e"]["serve_tokens_per_s"] > 0
    c = out["counters"]
    assert 0 < c["occupancy_sum_window"] <= c["slot_steps_window"]
    assert 0 <= c["compile_seconds_setup"]
    gaps = out["samples"]["itl_gaps_s"]
    assert gaps and min(gaps) > 0


def test_an_open_loop_mix_has_no_driver_yet(tmp_path):
    with pytest.raises(ValueError, match="closed-loop"):
        _run(drive_serve, {**tiny.SERVE, "loop": "open"}, 1, tmp_path)


def test_serving_from_altered_weights_is_not_correct(tmp_path, monkeypatch):
    """The engine serves from a head the reference never saw: its tokens
    are altered where they are produced."""
    gpt2 = tiny.family("gpt2")
    real = gpt2.build_program_model

    def altered(w, weights, traffic):
        import jax

        head = str(w["layers"] + 2)
        noise = 0.05 * jax.random.normal(
            jax.random.PRNGKey(1), weights[head]["kernel"].shape)
        weights = {**weights, head: {**weights[head],
                                     "kernel": weights[head]["kernel"] + noise}}
        return real(w, weights, traffic)

    monkeypatch.setattr(gpt2, "build_program_model", altered)
    out = _run(drive_serve, tiny.SERVE, 1, tmp_path)
    assert out["correct"] is False
    assert _readings(out)["widest_logit_gap"] > tiny.CONFIG["serving"]["check"]["gap_limit"]


def test_serving_in_int4_the_program_s_own_lower_path_is_not_correct(tmp_path):
    import time

    out = drive_serve.run(
        tiny.cell(tiny.SERVE, root=str(tmp_path)), tiny.args(),
        time.perf_counter(), harness.CompileWatch(), overrides={"weight_bits": 4})
    assert out["correct"] is False


@pytest.mark.parametrize("driver,traffic", [
    (drive_train, tiny.TRAIN), (drive_serve, tiny.SERVE)], ids=["train", "serve"])
def test_a_family_added_by_files_alone_drives_a_correct_run(
        driver, traffic, tmp_path):
    """The tiny cells through a family that a directory added to ``paths``
    brings, under a configuration with other keys than GPT-2's: the drivers
    take the model from the cell's family and from nowhere else. It is the
    same block, so on the same seed the check reads what it reads through
    ``gpt2``."""
    tiny.add_throwaway_family(tmp_path)
    cell = tiny.cell(traffic, root=str(tmp_path), config=tiny.THROWAWAY_CONFIG,
                     bench_root=str(tmp_path))
    assert cell["family"].__file__.startswith(str(tmp_path))
    out = _run(driver, traffic, cell=cell)
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    same = _run(driver, traffic, 1, tmp_path)
    assert same["correct"] is True
    if driver is drive_train:  # the same steps on the same rows and weights
        assert _readings(out) == _readings(same)
    else:  # the window's sample differs with the threads' timing
        limit = tiny.CONFIG["serving"]["check"]["gap_limit"]
        assert 0 <= _readings(out)["widest_logit_gap"] <= limit
        assert 0 <= _readings(same)["widest_logit_gap"] <= limit


def test_a_share_above_105_percent_ends_the_run():
    ok = {"train_mfu_pct": {"value": 104.0, "unit": "%"},
          "device_idle_pct.train": {"value": 300.0, "unit": "%"}}
    harness.check_shares(ok, {})
    with pytest.raises(SystemExit):
        harness.check_shares({"flash_attn_roofline": {"value": 106.0, "unit": "%"}},
                             {"flash_attn_roofline": {"flops": 1.0}})


@pytest.mark.parametrize("workload", ["train_seq2048", "serve_backlog"])
def test_run_py_refuses_to_measure_without_a_tpu(workload):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "whatever"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload, "--seed",
         str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs" in proc.stderr and "TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert not line.startswith("{"), "no result may be printed"


def test_result_line_has_the_contract_s_keys_only():
    line = harness.result_line(
        correct=True, attempted=3, failed=0,
        metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1})
    assert set(json.loads(line)) == {"correct", "attempted", "failed",
                                     "metrics", "device"}


def test_result_line_ends_with_each_number_compared_beside_its_limit():
    line = harness.result_line(
        correct=False, attempted=3, failed=0, metrics={},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1},
        breakdown={"device_ops": [], "idle_gaps": []},
        compared=[("loss_step1_abs_diff", 0.25, 0.002)])
    result = json.loads(line)
    assert list(result)[-1] == "check"
    assert result["check"] == {
        "loss_step1_abs_diff": {"value": 0.25, "limit": 0.002}}
