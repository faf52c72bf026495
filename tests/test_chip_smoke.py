"""``chip_smoke.py`` rehearsed on the CPU mesh: its phases at a tiny size
(the same functions the chip runs at full width), its sizing rule, and its
refusal to run without a TPU."""

import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(
    vocab_size=512, seq_len=128, d_model=128, num_heads=4, depth=2,
    batch=4, window=2, windows=3, slots=4, new_tokens=6,
    prompt_lens=[3, 9, 20, 33, 70],
)


@pytest.fixture(scope="module")
def trained():
    model, report = chip_smoke.trainer_phase(TINY, seed=0)
    return model, report


def test_trainer_phase_loss_falls_on_the_flash_path(trained):
    _, rep = trained
    assert rep["ok"], rep
    assert rep["steps"] == TINY["windows"] * TINY["window"]
    assert rep["loss_last_window"] < rep["loss_first_window"]
    assert rep["effective_path"] == "flash"
    assert rep["flash_attached"] == TINY["depth"]
    assert rep["interpret"] is True  # the CPU mesh interprets, and says so


def test_server_phase_is_token_identical_over_tcp(trained):
    model, _ = trained
    rep = chip_smoke.server_phase(model, TINY, seed=0)
    assert rep["ok"], rep
    assert rep["identical"] == [True] * len(TINY["prompt_lens"])
    assert rep["stream_identical"] and rep["stream_chunks"] >= 1
    assert rep["health"]["status"] == "serving"
    assert rep["completed"] == len(TINY["prompt_lens"]) + 1
    assert rep["stopped_status"] == "draining"


def test_sync_trainer_phase_matches_single_on_four_devices():
    rep = chip_smoke.sync_trainer_phase(TINY, num_workers=4, seed=0)
    assert rep["ok"], rep
    assert rep["global_batch"] == 4
    assert rep["max_abs_diff"] <= chip_smoke.LOSS_TOL
    assert rep["spread"] is None  # the CPU reports no device memory


def test_tp_server_phase_is_identical_and_spread_on_four_devices():
    rep = chip_smoke.tp_server_phase(TINY, tp=4, seed=0)
    assert rep["ok"], rep
    assert rep["mesh"] == "tp:4" and rep["spread"] is True
    live = rep["tp"]["live_bytes"]
    assert min(live[:4]) > 0 and live[0] < rep["solo"]["live_bytes"][0]


@pytest.mark.parametrize("gib", [15.75, 31.25])
def test_choose_sizes_keeps_widths_and_fits(gib):
    limit = int(gib * 2**30)
    one = chip_smoke.choose_sizes(limit)
    four = chip_smoke.choose_sizes(limit, kernels=False, min_batch=4)
    for sizes in (one, four):
        assert {k: sizes[k] for k in chip_smoke.WIDTH} == chip_smoke.WIDTH
        assert sizes["depth"] >= 4 and 2 <= sizes["slots"] <= 8
        state = 20 * chip_smoke.param_count(sizes, sizes["depth"])
        assert state < 0.85 * limit
    assert four["batch"] >= 4 and four["depth"] <= one["depth"]


@pytest.mark.parametrize("alone", [False, True], ids=["in-repo", "alone"])
def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path, alone):
    """No accelerator: non-zero exit within seconds, before any model is
    built, and no result line — in the repo, and in a directory that
    holds the script and nothing else of the repo."""
    script = os.path.join(ROOT, "chip_smoke.py")
    cwd = ROOT
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
        cwd = str(tmp_path)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, script], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr
    assert time.monotonic() - t0 < 60
