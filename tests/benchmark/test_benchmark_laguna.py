"""The ``laguna`` family and the three metrics of the grouped-query block: the
contract's names, the configuration against the catalog's row, the cut's
parameter count, the decode step's count by part (a window layer's cache at
``min(cached, window)``), the paged kernel's own count, the readers on a small
recorded cut of a traced run of ``serve_backlog_laguna``
(``fixtures/gqa_ops_small.json``, the plain form of
``benchmark/layer_metrics/_gqa_ops.py``), and a tiny copy of the cell through
its own driver with the timed path broken underneath."""

import json
import os
import re
import sys
import time
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import readers, spec  # noqa: E402
from benchmark.layer_metrics import _gqa_ops, _scoped_ops  # noqa: E402

CELL = "serve_backlog_laguna"
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "gqa_ops_small.json")
with open(FIXTURE) as _f:
    PLAIN = json.load(_f)["plain"]
CONTRACT = {"widths", "param_count", "make_weights", "build_program_model",
            "train_readings", "token_gaps", "decode_step"}


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL, REPO)


def test_the_family_keeps_the_contract_s_names_and_no_other_count(cell):
    """Every name of README.md's "A model family", ``decode_step`` as its one
    count (the kernel's own count is its ``kernel`` entry), and nothing of
    the program imported but the zoo entry."""
    family = cell["family"]
    for name in CONTRACT:
        assert callable(getattr(family, name, None)), name
    assert not hasattr(family, "train_flops_per_token")
    assert not hasattr(family, "flash_attention_train")
    with open(os.path.join(REPO, "benchmark", "families", "laguna.py")) as f:
        src = f.read()
    assert re.findall(r"^\s*(?:from|import) distkeras_tpu\S*.*$", src, re.M) == [
        "    from distkeras_tpu.models import zoo"]
    w = family.widths(cell["config"])
    assert (w["vocab"], w["seq"], w["layers"], w["top_k"]) == (25088, 16384, 5, 10)
    assert (w["experts"], w["experts_held"]) == (256, 64)
    assert w["heads"] == (48, 72, 72, 72, 48) and w["kv_heads"] == 8
    assert w["window"] == 512 and w["head_dim"] == 128


def test_the_configuration_holds_the_catalog_s_row(cell):
    """Every key of the catalog row's ``config`` under its own name: equal,
    or listed in ``reduced`` with the published value beside it; no width
    among the cuts; every assumption with a reason."""
    cfg = cell["config"]
    pub = cfg["published"]
    assert pub["model_type"] == "laguna" and len(pub["layer_types"]) == 48
    for key, value in pub.items():
        if key in cfg["reduced"]:
            assert cfg["reduced_from"][key] == [value, cfg[key]]
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings", "layer_types", "mlp_layer_types",
        "gating_types", "num_attention_heads_per_layer"]
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    for width in ("hidden_size", "head_dim", "num_key_value_heads",
                  "intermediate_size", "moe_intermediate_size",
                  "shared_expert_intermediate_size", "num_experts_per_tok",
                  "sliding_window", "rope_parameters"):
        assert width not in cfg["reduced"] and cfg[width] == pub[width]
    assert cfg["layer_types"] == pub["layer_types"][:5]
    assert cfg["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert "48 chips" in cfg["deployment"] and "4 chips" in cfg["deployment"]
    for name in ("router_score", "shared_expert", "head_gate", "qk_norm",
                 "rope", "activation", "weights"):
        assert len(cfg["assumed"][name]) > 40, name


def test_the_cut_holds_the_issue_s_parameter_count(cell):
    """A full layer's attention 44.19e6, a window layer's 63.14e6, the dense
    MLP 113.25e6, an expert 9.437e6, 3,002.0e6 in all: 6.00e9 bytes."""
    family = cell["family"]
    n = family.param_count(family.widths(cell["config"]))
    assert n["attention_full"] == 44_187_648
    assert n["attention_window"] == 63_135_744
    assert n["dense_mlp"] == 113_246_208 and n["expert"] == 9_437_184
    assert n["router"] == 786_432 and n["shared"] == n["expert"]
    assert n["embedding"] == n["head"] == 25088 * 3072
    assert n["total"] == pytest.approx(3_002.0e6, rel=1e-4)


def test_the_cell_is_the_issue_s(cell):
    t, s = cell["traffic"], cell["config"]["serving"]
    assert (t["loop"], t["clients"], s["num_slots"]) == ("closed", 192, 96)
    assert t["prompt_len"] == {"median": 4096, "sigma": 0.8, "min": 256, "max": 14336}
    assert t["output_len"] == {"median": 512, "sigma": 0.7, "min": 64, "max": 2048}
    assert t["max_total"] == 16384 and cell["cell"]["chips"] == 1
    assert (t["shape_seed"], t["block"], t["pool"]) == (35, 16, 512)
    assert s["page_size"] == 16 and s["kv_dtype"] == "bfloat16"
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"decode_step_ms", "decode_step_roofline", "moe_decode_roofline",
            "experts_hit_pct", "prefill_chunk_ms", "kv_pages_in_use_pct",
            "attn_decode_roofline", "paged_gqa_roofline",
            "window_pages_in_use_pct"} <= reported
    assert "mla_decode_roofline" not in reported


def test_decode_step_s_parts_sum_to_its_whole_and_a_window_bounds_its_cache(
        cell):
    family = cell["family"]
    w = family.widths(cell["config"])
    need = family.decode_step(w, 96.0, 5800.0, weight_bytes=2, kv_bytes=2)
    parts = need["parts"]
    assert set(parts) == {"attn", "moe", "dense", "head"}
    assert sum(p["bytes"] for p in parts.values()) == need["bytes"]
    assert sum(p["flops"] for p in parts.values()) == pytest.approx(need["flops"])
    # the issue's count: 4.6e9 bytes of full-layer cache, 0.6e9 of window
    # cache, 4.7e9 of experts (98% of 64 held reached), 10.9e9 in all
    token = 2 * 8 * 128 * 2
    assert need["kv_bytes"] == 96 * token * (2 * 5800 + 3 * 512)
    assert need["kv_bytes"] == pytest.approx(5.17e9, rel=0.01)
    assert need["experts_reached_a_layer"] == pytest.approx(64 * 0.978, rel=1e-3)
    assert parts["moe"]["bytes"] == pytest.approx(4.81e9, rel=0.01)
    assert need["bytes"] == pytest.approx(10.91e9, rel=0.01)
    # beyond the window a window layer's cache costs no more
    longer = family.decode_step(w, 96.0, 11600.0, weight_bytes=2, kv_bytes=2)
    assert longer["kv_bytes"] - need["kv_bytes"] == 96 * token * 2 * 5800
    short = family.decode_step(w, 96.0, 300.0, weight_bytes=2, kv_bytes=2)
    assert short["kv_bytes"] == 96 * token * 5 * 300
    # the kernel alone: whole pages in reach, K and V, q in and o out
    alone = need["kernel"]
    assert alone == family.paged_attention_step(w, 96.0, 5800.0, kv_bytes=2)
    assert alone["pages_a_slot"] == {"full_attention": 363,
                                     "sliding_attention": 33}
    page = 16 * token
    assert alone["bytes"] == 96 * ((2 * 363 + 3 * 33) * page
                                   + 2 * 4 * 128 * (2 * 48 + 3 * 72))
    assert alone["bytes"] <= 1.02 * need["kv_bytes"]


def _ctx(cell, plain_trace=True):
    family = cell["family"]
    return {"trace": {"devices": 1} if plain_trace else None, "operands": {},
            "counters": {"mean_batch": 95.0, "mean_cached": 5800.0},
            "family": family, "widths": family.widths(cell["config"]),
            "config": cell["config"], "peaks": spec.load_peaks("TPU v5 lite")}


def _read(name, cell, plain, monkeypatch, **kw):
    monkeypatch.setattr(_gqa_ops, "run_profile", lambda: plain)
    m = {"name": name, **spec.load_layer_metric(name, REPO)}
    ctx = _ctx(cell, **kw)
    return readers.read(m, ctx), ctx


def test_attn_decode_roofline_on_the_recorded_cut(cell, monkeypatch):
    value, ctx = _read("attn_decode_roofline", cell, PLAIN, monkeypatch)
    seconds = _scoped_ops.scope_seconds_a_step(PLAIN, "attn")
    need = cell["family"].decode_step(
        ctx["widths"], 95.0, 5800.0, weight_bytes=2, kv_bytes=2)["parts"]["attn"]
    assert value == pytest.approx(100.0 * need["bytes"] / 819e9 / seconds, rel=1e-3)
    assert 0 < value < 100
    assert ctx["operands"]["attn_decode_roofline"]["bound"] == "memory"


def test_paged_gqa_roofline_on_the_recorded_cut(cell, monkeypatch):
    value, ctx = _read("paged_gqa_roofline", cell, PLAIN, monkeypatch)
    seconds = _scoped_ops.scope_seconds_a_step(PLAIN, "kernel")
    need = cell["family"].decode_step(
        ctx["widths"], 95.0, 5800.0, weight_bytes=2, kv_bytes=2)["kernel"]
    assert value == pytest.approx(100.0 * need["bytes"] / 819e9 / seconds, rel=1e-3)
    assert 0 < value < 100
    ops = ctx["operands"]["paged_gqa_roofline"]
    assert ops["bound"] == "memory"
    assert ops["kernel_calls"] == 5 * ops["decode_steps_traced"]
    # the kernel's calls lie inside the attention scopes' time
    assert seconds <= _scoped_ops.scope_seconds_a_step(PLAIN, "attn")


def test_window_pages_in_use_pct_on_the_recorded_cut(cell, monkeypatch):
    value, _ = _read("window_pages_in_use_pct", cell, PLAIN, monkeypatch)
    rows = PLAIN["iterations"]
    assert rows and all(r["window_pages_total"] == 96 * 33 for r in rows)
    by_hand = sum(100.0 * r["window_pages_in_use"] / r["window_pages_total"]
                  for r in rows) / len(rows)
    assert value == pytest.approx(by_hand) and 50.0 < value <= 100.0


@pytest.mark.parametrize("name", ["attn_decode_roofline", "paged_gqa_roofline",
                                  "window_pages_in_use_pct"])
def test_a_program_without_the_scopes_gives_none(cell, monkeypatch, name):
    """The parent of the PR that brought these (no ``attn/`` scope, no such
    kernel call inside a step, no window pool), an untraced run, a run that
    wrote no profile: nothing to read, nothing raised."""
    empty = {"programs": {"decode_step": [[0.0, 1e6]], "prefill_chunk": []},
             "ops": [], "iterations": []}
    assert _read(name, cell, empty, monkeypatch)[0] is None
    assert _read(name, cell, None, monkeypatch)[0] is None
    assert _read(name, cell, PLAIN, monkeypatch, plain_trace=False)[0] is None


def test_the_patterns_name_the_program_s_scopes_and_kernel():
    assert _gqa_ops.ATTN.search("jit(step)/attn/full/dot_general")
    assert _gqa_ops.ATTN.search("jit(step)/attn/window/paged_decode_attention")
    assert not _gqa_ops.ATTN.search("jit(step)/mla/dot_general")
    assert not _gqa_ops.ATTN.search("jit(step)/moe/experts/ragged_dot")
    assert _gqa_ops.KERNEL.search(
        '%paged_decode_attention.3 = f32[96,128,128] custom-call(...)')
    assert not _gqa_ops.KERNEL.search("%paged_latent_attention.1 = f32[..]")


# ----------------------------------------- the benchmark's cell, tiny

sys.path.insert(0, os.path.join(REPO, "tests"))


def _tiny_cell(tmp_path):
    import test_laguna as tiny

    serve = {
        "kind": "serve", "loop": "closed", "clients": 8, "shape_seed": 1,
        "pool": 32, "block": 8,
        "prompt_len": {"median": 20, "sigma": 0.6, "min": 2, "max": 90},
        "output_len": {"median": 8, "sigma": 0.5, "min": 2, "max": 20},
        "max_total": 128, "max_requests": 2000, "lead_s": 0.3,
        "stall_s": 5.0, "check": {"requests": 4},
        "trace": {"lead_s": 0.1, "seconds": 0.2},
    }
    fam = spec.load_family("laguna", REPO)
    return {"root": str(tmp_path), "config": tiny.CONFIG, "traffic": serve,
            "family": fam, "cell": {"chips": 1}}


def _drive(cell):
    from benchmark import drive_serve, harness

    args = types.SimpleNamespace(seed=2**31 + 321, seconds=0.6, trace=0)
    out = drive_serve.run(cell, args, time.perf_counter(),
                          harness.CompileWatch())
    assert out["compiled_in_window"] == 0
    return out


@pytest.mark.e2e
def test_a_tiny_copy_of_the_cell_is_correct_through_the_driver(tmp_path):
    """``drive_serve.run`` as the benchmark runs it: the family's weights,
    ``quantize_model(bits=16)``, the bundle, the paged engine with its two
    page budgets behind ``ServingServer``, requests longer than the window,
    the reference's check."""
    out = _drive(_tiny_cell(tmp_path))
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert out["e2e"]["serve_tokens_per_s"] > 0
    c = out["counters"]
    assert 0 < c["occupancy_sum_window"] <= c["slot_steps_window"]


@pytest.mark.e2e
@pytest.mark.parametrize("broken", ["ring", "head"])
def test_the_tiny_cell_with_the_timed_path_broken_is_not_correct(
        tmp_path, monkeypatch, broken):
    """The engine serves with the prefill chunks' keys and values never
    reaching the window layers' rings (the first decode steps read what the
    ring held before), or from a head the reference never saw: the
    reference's check sees either."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.serving import engine

    cell = _tiny_cell(tmp_path)
    if broken == "ring":
        real = engine.DecodeStepper._chunk_where

        def nothing_real(self, slot, pbt, n):
            row, ring, _ = real(self, slot, pbt, n)
            return row, ring, np.int32(0)  # no token of the chunk is real

        monkeypatch.setattr(engine.DecodeStepper, "_chunk_where",
                            nothing_real)
    else:
        fam = cell["family"]
        real = fam.build_program_model

        def altered(w, weights, traffic):
            head = str(w["layers"] + 2)
            kernel = weights[head]["kernel"]
            noise = 0.05 * jax.random.normal(jax.random.PRNGKey(1),
                                             kernel.shape)
            weights = {**weights, head: {"kernel": (
                kernel.astype(jnp.float32) + noise).astype(kernel.dtype)}}
            return real(w, weights, traffic)

        monkeypatch.setattr(fam, "build_program_model", altered)
    out = _drive(cell)
    assert out["correct"] is False
    gap = {n: v for n, v, _ in out["compared"]}["widest_logit_gap"]
    assert gap > cell["config"]["serving"]["check"]["gap_limit"]
