"""The load generator: the same seed gives the same traffic, every seed the
same set of sizes and gaps in another order, and the lateness arithmetic."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import loadgen, spec  # noqa: E402

BACKLOG = spec.load_cell("serve_backlog", REPO)["traffic"]
# an open-loop mix: the generator gives each request a due time (no cell and
# no driver uses one yet: PERF.md section 7)
CHAT = {**BACKLOG, "loop": "open",
        "arrivals": {"process": "poisson", "rate": 4.0, "params": {}}}
PRETRAIN = spec.load_cell("train_seq2048", REPO)["traffic"]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_same_seed_same_traffic(seed):
    a = loadgen.make_requests(CHAT, seed, 50257, 300)
    b = loadgen.make_requests(CHAT, seed, 50257, 300)
    assert all(np.array_equal(x["prompt"], y["prompt"]) and x["due"] == y["due"]
               and x["max_new_tokens"] == y["max_new_tokens"]
               for x, y in zip(a, b))


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    n = CHAT["pool"]
    a = loadgen.make_requests(CHAT, 1, 50257, n)
    b = loadgen.make_requests(CHAT, 2, 50257, n)
    sizes = lambda reqs: sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs)
    assert sizes(a) == sizes(b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    gaps = lambda reqs: np.sort(np.diff([0.0] + [r["due"] for r in reqs]))
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9)
    assert abs(a[-1]["due"] - b[-1]["due"]) < 1e-6


@pytest.mark.parametrize("mix", [CHAT, BACKLOG], ids=["chat", "backlog"])
def test_lengths_keep_to_the_mix_s_limits(mix):
    pool = [pair for block in loadgen.request_pool(mix) for pair in block]
    prompts = np.array([p for p, _ in pool])
    outputs = np.array([o for _, o in pool])
    assert prompts.min() >= mix["prompt_len"]["min"]
    assert prompts.max() <= mix["prompt_len"]["max"]
    assert outputs.min() >= 1 and outputs.max() <= mix["output_len"]["max"]
    assert (prompts + outputs).max() <= mix["max_total"]
    assert abs(np.median(prompts) - mix["prompt_len"]["median"]) <= 2
    assert abs(np.median(outputs) - mix["output_len"]["median"]) <= 8


@pytest.mark.parametrize("process", ["poisson", "bursty", "diurnal", "heavy_tail"])
def test_arrival_processes_keep_their_mean_rate(process):
    gaps = loadgen.arrival_gaps(process, 10.0, 4000, seed=3)
    assert len(gaps) == 4000 and (gaps > 0).all()
    rate = len(gaps) / gaps.sum()
    lo, hi = (4.0, 25.0) if process in ("bursty", "heavy_tail") else (8.0, 12.5)
    assert lo < rate < hi
    np.testing.assert_array_equal(
        gaps, loadgen.arrival_gaps(process, 10.0, 4000, seed=3))


def test_any_block_of_requests_in_a_row_carries_nearly_the_same_work():
    blocks = loadgen.request_pool(BACKLOG)
    assert len(blocks) * BACKLOG["block"] == BACKLOG["pool"]
    prompt_sums = np.array([sum(p for p, _ in b) for b in blocks])
    output_sums = np.array([sum(o for _, o in b) for b in blocks])
    assert prompt_sums.std() / prompt_sums.mean() < 0.05
    assert output_sums.std() / output_sums.mean() < 0.05
    plain = np.array([p for b in blocks for p, _ in b])
    rng = np.random.default_rng(0)
    shuffled = rng.permutation(plain).reshape(len(blocks), -1).sum(axis=1)
    assert shuffled.std() > 3 * prompt_sums.std()  # what a plain shuffle gives


def test_lateness_is_sent_minus_due_and_never_negative():
    assert loadgen.lateness([1.0, 2.0, 3.0], [1.004, 1.9, 3.5]) == pytest.approx(
        [0.004, 0.0, 0.5])


def test_training_rows_differ_and_stay_inside_the_alphabet():
    rng = np.random.default_rng(5)
    batch = loadgen.training_batch(PRETRAIN, rng, 12, 2048, 50257)
    assert batch.shape == (12, 2048) and batch.dtype == np.int32
    assert len({row.tobytes() for row in batch}) == 12
    assert len(np.unique(batch)) <= PRETRAIN["alphabet"]
    assert batch.max() > PRETRAIN["alphabet"], "ids spread over the vocabulary"
    again = loadgen.training_batch(PRETRAIN, np.random.default_rng(5), 12, 2048, 50257)
    np.testing.assert_array_equal(batch, again)
