"""The FLOP and byte functions (the GPT-2 family's counts, and the shared
``flops.roofline_share``) against hand counts."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import flops, spec  # noqa: E402
from benchmark.families import gpt2  # noqa: E402

ONE_BLOCK = {"d": 2048, "inner": 8192, "layers": 1, "vocab": 50257, "seq": 2048,
             "heads": 16}


def test_matmul_parameters_of_one_block_and_the_head():
    # q, k, v, o: 4 x 2048^2 = 16,777,216; the MLP: 2 x 2048 x 8192 = 33,554,432
    assert gpt2.matmul_params(ONE_BLOCK) == 16_777_216 + 33_554_432 + 2048 * 50257


def test_training_flops_of_a_token_by_hand():
    got = gpt2.train_flops_per_token(ONE_BLOCK)
    dense = 6 * (50_331_648 + 102_926_336)
    # scores and values, causal half: 2 products x 2 x 2048 x 1024 = 8,388,608
    # forward; the backward pass costs twice the forward
    attention = 3 * 8_388_608
    assert got["dense"] == dense and got["attention"] == attention
    assert got["flops"] == dense + attention


def test_cut_configuration_is_about_2_6_gflop_a_token():
    cell = spec.load_cell("train_seq2048", REPO)
    w = cell["family"].widths(cell["config"])
    got = cell["family"].train_flops_per_token(w)
    assert got["flops"] == pytest.approx(2.58e9, rel=0.01)
    assert cell["family"].param_count(w)["total"] == pytest.approx(512e6, rel=0.01)


def test_served_configuration_counts_1_42e9_parameters():
    cell = spec.load_cell("serve_backlog", REPO)
    w = cell["family"].widths(cell["config"])
    assert cell["family"].param_count(w)["total"] == pytest.approx(1.42e9, rel=0.01)


def test_flash_kernel_counts_by_hand():
    got = gpt2.flash_attention_train(ONE_BLOCK, batch=4)
    product = 2 * 2048 * 1024 * 2048  # one causal (T x T/2) x d product
    assert got["flops_fwd"] == 4 * 2 * product
    assert got["flops_bwd"] == 4 * 4 * product
    assert got["bytes_fwd"] == 4 * 4 * 2048 * 2048 * 2


def test_decode_step_counts_by_hand():
    got = gpt2.decode_step(ONE_BLOCK, batch=8, cached=100, weight_bytes=1, kv_bytes=2)
    n = gpt2.matmul_params(ONE_BLOCK)
    assert got["weight_bytes"] == n
    assert got["kv_bytes"] == 2 * 2048 * 100 * 8 * 2
    assert got["flops"] == 2 * n * 8 + 2 * 2 * 2048 * 100 * 8


def test_roofline_share_names_its_bound():
    share = flops.roofline_share(197e12, 819e9 * 2, 4.0, 197e12, 819e9)
    assert share["bound"] == "memory" and share["pct"] == pytest.approx(50.0)
    share = flops.roofline_share(197e12 * 3, 819e9, 4.0, 197e12, 819e9)
    assert share["bound"] == "compute" and share["pct"] == pytest.approx(75.0)
