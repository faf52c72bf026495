"""The ``longcat_flash`` family and the two metrics of the shortcut-connected
expert layer: the contract's names, the decode step's count by part (the
identity picks move no byte), and the readers on a small recorded cut of a
traced run of ``serve_backlog_longcat`` (``fixtures/shortcut_ops_small.json``,
the plain form of ``benchmark/layer_metrics/_shortcut_ops.py``)."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import readers, spec  # noqa: E402
from benchmark.layer_metrics import _scoped_ops, _shortcut_ops  # noqa: E402

CELL = "serve_backlog_longcat"
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "shortcut_ops_small.json")
with open(FIXTURE) as _f:
    PLAIN = json.load(_f)["plain"]
CONTRACT = {"widths", "param_count", "make_weights", "build_program_model",
            "train_readings", "token_gaps", "decode_step"}


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL, REPO)


def test_the_family_keeps_the_contract_s_names_and_no_other_count(cell):
    """Every name of README.md's "A model family", ``decode_step`` as its one
    count (it trains in no cell and runs no flash kernel), and nothing of the
    program imported but the zoo entry."""
    family = cell["family"]
    for name in CONTRACT:
        assert callable(getattr(family, name, None)), name
    assert not hasattr(family, "train_flops_per_token")
    assert not hasattr(family, "flash_attention_train")
    with open(os.path.join(REPO, "benchmark", "families",
                           "longcat_flash.py")) as f:
        src = f.read()
    assert re.findall(r"^\s*(?:from|import) distkeras_tpu\S*.*$", src, re.M) == [
        "    from distkeras_tpu.models import zoo"]
    w = family.widths(cell["config"])
    assert {"vocab", "seq", "layers", "top_k"} <= set(w)
    assert (w["vocab"], w["seq"], w["layers"], w["top_k"]) == (16384, 8192, 4, 12)
    assert (w["experts"], w["experts_held"], w["zero"]) == (512, 16, 256)
    assert w["q_scale"] == 2.0 and w["kv_scale"] == pytest.approx(12 ** 0.5)


def test_the_cut_holds_the_issue_s_parameter_count(cell):
    """638.87e6 parameters a layer beside its experts, 37.75e6 an expert,
    5,172.7e6 in all (the norms' and the selection bias' 70 thousand
    aside)."""
    family = cell["family"]
    n = family.param_count(family.widths(cell["config"]))
    assert n["attention"] == 90_570_752 and n["dense_mlp"] == 226_492_416
    assert n["router"] == 4_718_592 and n["expert"] == 37_748_736
    beside = 2 * n["attention"] + 2 * n["dense_mlp"] + n["router"]
    assert round(beside / 1e6, 2) == 638.84
    assert n["total"] == pytest.approx(5_172.7e6, rel=2e-4)
    assert n["embedding"] == n["head"] == 16384 * 6144


def test_the_cell_is_the_issue_s(cell):
    t, s = cell["traffic"], cell["config"]["serving"]
    assert (t["loop"], t["clients"], s["num_slots"]) == ("closed", 256, 128)
    assert t["prompt_len"] == {"median": 512, "sigma": 0.9, "min": 64, "max": 4096}
    assert t["output_len"] == {"median": 512, "sigma": 0.7, "min": 64, "max": 2048}
    assert t["max_total"] == 8192 and cell["cell"]["chips"] == 1
    assert cell["config"]["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"]
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"decode_step_ms", "decode_step_roofline", "moe_decode_roofline",
            "mla_decode_roofline", "experts_hit_pct", "prefill_chunk_ms",
            "zero_pick_pct", "dense_ffn_decode_roofline"} <= reported


def test_decode_step_s_parts_sum_to_its_whole_and_identity_picks_move_nothing(
        cell):
    family = cell["family"]
    w = family.widths(cell["config"])
    need = family.decode_step(w, 126.0, 1100.0, weight_bytes=2, kv_bytes=2)
    parts = need["parts"]
    assert set(parts) == {"moe", "mla", "dense"}
    head = 6144 * 16384
    assert sum(p["bytes"] for p in parts.values()) + 2 * head == need["bytes"]
    assert sum(p["flops"] for p in parts.values()) + 2 * 126 * head \
        == pytest.approx(need["flops"])
    # the issue's count: 5.11e9 bytes of attention and dense weights, 4.19e9
    # of experts (87% of 64 held reached), 1.42e9 of cache, 0.20e9 of head
    assert need["experts_reached_a_layer"] == pytest.approx(16 * 0.8625, rel=1e-3)
    dense_weights = parts["dense"]["bytes"] + parts["mla"]["bytes"] - need["kv_bytes"]
    assert dense_weights == pytest.approx(5.07e9, rel=0.01)
    assert parts["moe"]["bytes"] == pytest.approx(4.20e9, rel=0.01)
    assert need["kv_bytes"] == pytest.approx(1.277e9, rel=0.01)  # 576 values
    assert need["bytes"] == pytest.approx(10.75e9, rel=0.01)
    # all-identity routing: no expert's byte, 2 x d operations a pick
    zero_only = family.decode_step({**w, "experts_held": 0}, 126.0, 1100.0,
                                   weight_bytes=2, kv_bytes=2)["parts"]["moe"]
    assert zero_only["bytes"] == 4 * 6144 * 768 * 2  # the routers alone
    picks = need["zero_picks_a_token_and_layer"]
    assert picks == pytest.approx(4.0)  # 12 x 256 / 768
    assert zero_only["flops"] == pytest.approx(
        4 * 126 * (2 * 6144 * 768 + picks * 2 * 6144))
    more_zero = family.decode_step({**w, "zero": 512}, 126.0, 1100.0,
                                   weight_bytes=2, kv_bytes=2)["parts"]["moe"]
    assert more_zero["bytes"] < parts["moe"]["bytes"] + 4 * 6144 * 256 * 2


def _ctx(cell, plain_trace=True):
    family = cell["family"]
    return {"trace": {"devices": 1} if plain_trace else None, "operands": {},
            "counters": {"mean_batch": 126.0, "mean_cached": 1100.0},
            "family": family, "widths": family.widths(cell["config"]),
            "config": cell["config"], "peaks": spec.load_peaks("TPU v5 lite")}


def _read(name, cell, plain, monkeypatch, **kw):
    monkeypatch.setattr(_shortcut_ops, "run_profile", lambda: plain)
    m = {"name": name, **spec.load_layer_metric(name, REPO)}
    ctx = _ctx(cell, **kw)
    return readers.read(m, ctx), ctx


def test_zero_pick_pct_on_the_recorded_cut(cell, monkeypatch, capsys):
    value, _ = _read("zero_pick_pct", cell, PLAIN, monkeypatch)
    rows = PLAIN["collect"]
    by_hand = sum(100.0 * r["zero_picks"] / r["picks"] for r in rows) / len(rows)
    assert value == pytest.approx(by_hand) and 25.0 < value < 42.0
    for r in rows:  # tokens x 12 picks x 4 layers; no pick counted twice
        assert r["picks"] == r["routed_tokens"] * 48
        assert r["zero_picks"] + r["held_picks"] < r["picks"]
    assert "picks a token 48.0" in capsys.readouterr().out


def test_dense_ffn_decode_roofline_on_the_recorded_cut(cell, monkeypatch):
    value, ctx = _read("dense_ffn_decode_roofline", cell, PLAIN, monkeypatch)
    seconds = _scoped_ops.scope_seconds_a_step(PLAIN, "dense")
    need = 8 * 3 * 6144 * 12288 * 2  # eight MLPs' bytes
    assert value == pytest.approx(100.0 * need / 819e9 / seconds, rel=1e-3)
    assert 0 < value < 100
    assert ctx["operands"]["dense_ffn_decode_roofline"]["bound"] == "memory"


@pytest.mark.parametrize("name", ["zero_pick_pct", "dense_ffn_decode_roofline"])
def test_a_program_without_the_scope_or_the_counters_reads_nothing(
        name, cell, monkeypatch):
    """The parent of the PR that brought them, or another family: the plain
    form has no ``ffn/dense`` operation and no ``zero_picks``."""
    bare = {"programs": PLAIN["programs"], "ops": [], "collect": [
        {"experts_hit": 120.0, "expert_load_max": 9.0, "experts_total": 128.0,
         "routed_tokens": 64.0}]}
    assert _read(name, cell, bare, monkeypatch)[0] is None
    assert _read(name, cell, None, monkeypatch)[0] is None
    assert _read(name, cell, PLAIN, monkeypatch, plain_trace=False)[0] is None


def test_the_dense_path_s_pattern_takes_its_scope_alone():
    take = _shortcut_ops.DENSE.search
    assert take("jit(step)/jit(main)/ffn/dense/dot_general")
    assert take("ffn/dense") and take("jit(chunk)/ffn/dense/mul")
    for other in ("jit(step)/mla/dot_general", "jit(step)/moe/experts/ragged",
                  "jit(step)/ffn/densest/x", "jit(step)/xffn/dense/x"):
        assert not take(other), other
    # and the accepted scopes do not take the dense path's operations
    assert _scoped_ops.scope_of("jit(step)/ffn/dense/dot_general") is None
    assert _scoped_ops.scope_of("jit(step)/moe/zero/mul") == "moe"


def test_no_profile_on_disk_reads_nothing(tmp_path):
    assert _shortcut_ops.run_profile(str(tmp_path)) is None
