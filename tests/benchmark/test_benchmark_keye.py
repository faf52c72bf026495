"""The ``keye_vl2`` family and the four metrics of a block that selects its
keys: the contract's names, the configuration against the catalog's row, the
cut's parameter count, the cell's mix, the decode step's count by part (the
selector keys at the cached length, keys and values at ``min(cached, topk)``),
the readers on a small recorded cut of a traced run of ``serve_backlog_keye``
(``fixtures/select_ops_small.json``, the plain form of
``benchmark/layer_metrics/_select_ops.py``), and a tiny copy of the cell
through its own driver, sound and with the selection broken underneath."""

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
from benchmark.layer_metrics import _scoped_ops, _select_ops  # noqa: E402

CELL = "serve_backlog_keye"
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "select_ops_small.json")
with open(FIXTURE) as _f:
    RECORDED = json.load(_f)
PLAIN = RECORDED["plain"]
CONTRACT = {"widths", "param_count", "make_weights", "build_program_model",
            "train_readings", "token_gaps", "decode_step"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL, REPO)


def test_the_family_keeps_the_contract_s_names_and_no_other_count(cell):
    """Every name of README.md's "A model family", ``decode_step`` as its one
    count, and nothing of the program imported but the zoo entry."""
    family = cell["family"]
    for name in CONTRACT:
        assert callable(getattr(family, name, None)), name
    assert not hasattr(family, "train_flops_per_token")
    assert not hasattr(family, "flash_attention_train")
    with open(os.path.join(REPO, "benchmark", "families", "keye_vl2.py")) as f:
        src = f.read()
    # the zoo entry, and the block that the selector's control rounds
    assert re.findall(r"^\s*(?:from|import) distkeras_tpu\S*.*$", src, re.M) == [
        "    from distkeras_tpu.models import zoo",
        "    from distkeras_tpu.models.gqa_moe import GroupedQueryMoEBlock as block"]
    w = family.widths(cell["config"])
    assert (w["vocab"], w["seq"], w["layers"], w["top_k"]) == (18992, 49152, 6, 8)
    assert (w["experts"], w["experts_held"]) == (128, 16)
    assert (w["n_heads"], w["kv_heads"], w["head_dim"]) == (32, 4, 128)
    assert (w["index_heads"], w["index_dim"], w["topk"]) == (16, 64, 2048)
    assert w["theta"] == 1e7 and w["expert_width"] == 768 and w["d"] == 2048


def test_the_configuration_holds_the_catalog_s_row(cell):
    """Every key of the catalog row's ``config`` under its own name: equal,
    or listed in ``reduced`` with the published value beside it; no width
    among the cuts; every assumption with a reason."""
    cfg = cell["config"]
    pub = cfg["published"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [json.loads(ln) for ln in f if "Keye-VL-2.0-30B-A3B" in ln][0]
        assert pub == row["config"] and cfg["source"].startswith(row["source_url"])
    assert pub["model_type"] == "KeyeVL2" and pub["num_hidden_layers"] == 48
    for key, value in pub.items():
        if key in cfg["reduced"]:
            assert cfg["reduced_from"][key] == [value, cfg[key]]
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts", "vocab_size",
        "max_position_embeddings"]
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    for width in ("hidden_size", "head_dim", "num_attention_heads",
                  "num_key_value_heads", "moe_intermediate_size",
                  "num_experts_per_tok", "sa_config", "rope_theta",
                  "intermediate_size"):
        assert width not in cfg["reduced"] and cfg[width] == pub[width]
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert "64 chips" in cfg["deployment"] and "8 chips" in cfg["deployment"]
    for name in ("language_model_only", "qk_norm", "indexer", "chunk_sizes",
                 "rope", "router_score", "activation", "weights"):
        assert len(cfg["assumed"][name]) > 40, name
    s = cfg["serving"]
    assert (s["num_slots"], s["page_size"], s["kv_dtype"]) == (32, 16, "bfloat16")
    assert "INDEX SCORES" in s["precision"] and s["prefill_chunk"] == 2048


def test_the_cut_holds_the_issue_s_parameter_count(cell):
    """Attention 18,874,368, the indexer 2,261,120, the router 262,144, the
    norms 4,352: 21,401,984 a layer outside its experts; an expert
    4,718,592; a held layer 96,899,456; embedding and head 38,895,616 each;
    659.2e6 in all: 1.318e9 bytes."""
    family = cell["family"]
    n = family.param_count(family.widths(cell["config"]))
    assert n["attention"] == 18_874_368 and n["indexer"] == 2_261_120
    assert n["router"] == 262_144 and n["norms"] == 4_352
    assert n["layer_outside_experts"] == 21_401_984
    assert n["expert"] == 4_718_592 and n["layer_held"] == 96_899_456
    assert n["layer_whole"] == 625_381_760
    assert n["embedding"] == n["head"] == 18992 * 2048 == 38_895_616
    assert n["total"] == 6 * 96_899_456 + 2 * 38_895_616 + 2048 == 659_190_016


def test_the_cell_is_the_issue_s(cell):
    t, s = cell["traffic"], cell["config"]["serving"]
    assert (t["loop"], t["clients"], s["num_slots"]) == ("closed", 64, 32)
    assert t["prompt_len"] == {"median": 16384, "sigma": 0.5, "min": 4096,
                               "max": 40960}
    assert t["output_len"] == {"median": 1024, "sigma": 0.6, "min": 128,
                               "max": 4096}
    assert t["max_total"] == 45056 and cell["cell"]["chips"] == 1
    assert t["check"]["requests"] == 6 and t["lead_s"] >= 60
    assert cell["cell"]["traffic"] == "backlog_16k"
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"index_decode_roofline", "sparse_attn_decode_roofline",
            "index_chunk_ms", "keys_selected_pct", "decode_step_ms",
            "decode_step_roofline", "moe_decode_roofline", "experts_hit_pct",
            "prefill_chunk_ms", "kv_pages_in_use_pct"} <= reported
    assert not {"mla_decode_roofline", "attn_decode_roofline",
                "paged_gqa_roofline", "window_pages_in_use_pct"} & reported
    # every request is 2 to 22 times topk: every step selects
    from benchmark import loadgen

    reqs = loadgen.make_requests(t, 5, 18992, 64)
    lens = [len(r["prompt"]) for r in reqs]
    assert min(lens) >= 4096 and max(
        len(r["prompt"]) + r["max_new_tokens"] for r in reqs) <= 45056
    assert max(int(r["prompt"].max()) for r in reqs) < 18992


# --------------------------------------------------- how a request is judged

with open(os.path.join(os.path.dirname(__file__), "fixtures",
                       "keye_check_small.json")) as _f:
    CHECKED = json.load(_f)


def _judged(cell, gaps, margins, prompt_len=16384, **changed):
    family = cell["family"]
    w = {**family.widths(cell["config"]), **changed}
    out = family.judged(np.asarray(gaps), w, np.asarray(margins), prompt_len)
    return w, out


@pytest.mark.parametrize("side, correct", [("sound", True), ("bits8", False)])
def test_the_cell_s_limits_tell_the_recorded_request_from_its_8_bit_control(
        cell, side, correct):
    """A recorded request of the chip's runs (a prompt of 11,873 tokens) as
    the program served it and as the same program served it from weights
    rounded to 8 bits: under the limits the configuration states the first
    is correct and the second is not, by the share of flips where the
    reference's margin is 0.03 to 0.16 and not by the widest gap, and the
    harness's count of tokens stays the request's."""
    row = CHECKED[side]
    w, out = _judged(cell, row["gaps"], row["margins"], row["prompt_len"])
    assert len(out) == len(row["gaps"]) == 1021
    assert bool(out.max() <= w["gap_limit"]) is correct
    share = out[0] * w["flip_share_limit"] / w["gap_limit"]
    widest = out[1] * w["swap_gap_limit"] / w["gap_limit"]
    assert widest == pytest.approx(max(row["gaps"])) and widest < 0.25
    m, g = np.asarray(row["margins"]), np.asarray(row["gaps"])
    near = (m >= 0.03) & (m < 0.16)
    assert near.sum() >= 600
    # a prompt under ``flip_length`` is held as it is, and the count at
    # three of its standard deviations under what was counted
    flips = np.count_nonzero(g[near])
    assert w["flip_sigmas"] == 3
    assert share == pytest.approx(
        max(0.0, flips - 3 * flips ** 0.5) / near.sum())
    assert (share == 0) if correct else (share > 0.06)
    # the share of ALL its tokens that differ tells the two apart far less
    assert (np.count_nonzero(g) / len(g) > 0.06) and (
        np.count_nonzero(g) / len(g) < 0.19)


@pytest.mark.parametrize("case", [
    "too_few_counted", "a_longer_prompt", "a_shorter_prompt",
    "the_refused_run_s_request", "one_random_token", "no_limits_stated",
    "no_margins"])
def test_what_judged_holds_beside_the_share(cell, case):
    gaps = np.zeros(400)
    margins = np.full(400, 0.1)
    gaps[:40] = 0.11  # a tenth of the positions in the band differ
    w, out = _judged(cell, gaps, margins)
    proven = (40 - w["flip_sigmas"] * 40 ** 0.5) / 400
    assert 0.052 < proven < 0.053
    assert out[0] == pytest.approx(
        proven * w["gap_limit"] / w["flip_share_limit"])
    assert out.max() > w["gap_limit"]
    if case == "too_few_counted":
        # under ``flip_floor`` positions in the band: the request says nothing
        few = margins.copy()
        few[w["flip_floor"] - 1:] = 0.5
        w, out = _judged(cell, gaps, few)
        assert out[0] == 0 and out.max() <= w["gap_limit"]
    elif case == "a_longer_prompt":
        # the stated precision's own share grows with the length's square
        w, long = _judged(cell, gaps, margins, prompt_len=2 * 16384)
        assert long[0] == pytest.approx(out[0] / 4) and long[1] == out[1]
    elif case == "a_shorter_prompt":
        # ... beyond ``flip_length`` only: a short prompt's share is not
        # made larger
        w, short = _judged(cell, gaps, margins, prompt_len=16384 // 4)
        assert short[0] == out[0] and short[1] == out[1]
    elif case == "the_refused_run_s_request":
        # seed 1731659281's: 6 flips of 469 under a 7,899-token prompt read
        # 0.055 of 0.045 while the share was divided by 0.23
        gaps, margins = np.zeros(902), np.full(902, 0.5)
        margins[:469], gaps[:6] = 0.05, 0.06
        w, out = _judged(cell, gaps, margins, prompt_len=7899)
        assert out[0] == 0  # six positions prove no share
        assert out.max() == out[1] < w["gap_limit"] / 5
    elif case == "one_random_token":
        one = np.zeros(400)
        one[7] = 3.0
        w, out = _judged(cell, one, margins)
        assert out[0] == 0  # one position proves no share
        assert out[1] > w["gap_limit"] == pytest.approx(
            out[1] * w["swap_gap_limit"] / 3.0)
    elif case == "no_limits_stated":
        family = cell["family"]
        w = {k: v for k, v in family.widths(cell["config"]).items()
             if not k.startswith(("flip_", "swap_"))}
        assert family.judged(gaps, w, margins) is gaps
    else:
        with pytest.raises(ValueError, match="controls_select"):
            cell["family"].judged(gaps, w)


def test_a_dump_is_judged_again_as_the_harness_would(cell, tmp_path, capsys):
    """``controls_select.py --replay``: the recorded pair written as two
    dumps, the sound one correct and the control not, under the limits the
    configuration states."""
    from benchmark import controls_select

    for side in ("sound", "bits8"):
        with open(tmp_path / f"{side}_seed1.json", "w") as f:
            json.dump({"control": side, "requests": [CHECKED[side]]}, f)
    assert controls_select.replay(cell, [str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "sound_seed1.json: 1 requests" in out and "NOT correct" in out
    with open(tmp_path / "sound_seed2.json", "w") as f:
        json.dump({"control": "sound", "requests": [CHECKED["bits8"]]}, f)
    assert controls_select.replay(cell, [str(tmp_path)]) == 1


THREAD_METRICS = ("step_call_cpu_ms", "sched_offcpu_ms",
                  "stream_send_in_call_pct", "loop_busy_pct")
SERVING = ["serve_backlog", "serve_backlog_kanana", "serve_backlog_longcat",
           "serve_backlog_laguna", CELL]


def test_the_thread_metrics_keep_what_their_own_test_held_but_its_two_pins():
    """``test_benchmark_thread_spans.py`` pins the list's last four entries
    to PR 37's and their ``workloads`` to the four serving cells of its day;
    this PR's cell and metrics break those two asserts and ``conftest.py``
    marks that test ``xfail`` (strictly). Everything else it held is held
    here: the four are still there in their order, now on the five serving
    cells, with their sources, layers and readers, and not on the training
    cell."""
    bench = spec.load_benchmark(REPO)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(THREAD_METRICS[0])
    assert tuple(names[at:at + 4]) == THREAD_METRICS
    # this PR's four come after them, at the list's end
    assert names[at + 4:] == [
        "index_decode_roofline", "sparse_attn_decode_roofline",
        "index_chunk_ms", "keys_selected_pct"]
    for m in bench["per_layer"][at:at + 4]:
        assert m["workloads"] == SERVING and m["source"] == "program_span"
        assert m["moves"] == "serve_tokens_per_s" and m["unit"] in ("ms", "%")
        got = spec.load_layer_metric(m["name"], REPO)
        assert got["reader"] == "python" and callable(got["read"])
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["sched_offcpu_ms"] == layers["loop_busy_pct"] \
        == layers["sched_iter_ms"]
    assert layers["step_call_cpu_ms"] == layers["step_call_ms"]
    assert layers["stream_send_in_call_pct"] \
        == "server streams (serving/server.py)"
    train = {m["name"]
             for m in spec.load_cell("train_seq2048", REPO)["per_layer"]}
    assert not set(THREAD_METRICS) & train


def test_decode_step_s_parts_sum_to_its_whole_and_topk_bounds_the_rows(cell):
    family = cell["family"]
    w = family.widths(cell["config"])
    need = family.decode_step(w, 32.0, 20000.0, weight_bytes=2, kv_bytes=2)
    parts = need["parts"]
    assert set(parts) == {"index", "attn", "moe", "head"}
    assert sum(p["bytes"] for p in parts.values()) == need["bytes"]
    assert sum(p["flops"] for p in parts.values()) == pytest.approx(need["flops"])
    # the issue's count, a layer: selector keys 82e6 bytes, selected K/V
    # 134e6, attention's and the indexer's matrices 43e6, experts 132e6
    assert parts["index"]["bytes"] == 6 * (2_261_120 * 2 + 32 * 20000 * 128)
    assert parts["attn"]["bytes"] == 6 * (18_874_368 * 2 + 32 * 2048 * 2048)
    assert need["rows_read_a_slot"] == 2048
    assert need["experts_reached_a_layer"] == pytest.approx(16 * 0.873, rel=1e-2)
    assert parts["moe"]["bytes"] / 6 == pytest.approx(132e6, rel=0.02)
    # 2 x 1,024 operations a cached position for its score
    assert parts["index"]["flops"] == 6 * (
        2 * 32 * 2_261_120 + 32 * 20000 * 2 * 1024)
    # beyond topk the K/V part costs no more; the selector keys do
    longer = family.decode_step(w, 32.0, 40000.0, weight_bytes=2, kv_bytes=2)
    assert longer["parts"]["attn"] == parts["attn"]
    assert longer["parts"]["index"]["bytes"] - parts["index"]["bytes"] == \
        6 * 32 * 20000 * 128
    short = family.decode_step(w, 32.0, 300.0, weight_bytes=2, kv_bytes=2)
    assert short["rows_read_a_slot"] == 300
    assert short["kv_bytes"] == 6 * 32 * 300 * (2048 + 128)


def _ctx(cell, plain_trace=True):
    family = cell["family"]
    return {"trace": {"devices": 1} if plain_trace else None, "operands": {},
            "counters": {"mean_batch": RECORDED["mean_batch"],
                         "mean_cached": RECORDED["mean_cached"]},
            "family": family, "widths": family.widths(cell["config"]),
            "config": cell["config"], "peaks": spec.load_peaks("TPU v5 lite")}


def _read(name, cell, plain, monkeypatch, **kw):
    monkeypatch.setattr(_select_ops, "run_profile", lambda: plain)
    m = {"name": name, **spec.load_layer_metric(name, REPO)}
    ctx = _ctx(cell, **kw)
    return readers.read(m, ctx), ctx


@pytest.mark.parametrize("name, kind, part", [
    ("index_decode_roofline", "index", "index"),
    ("sparse_attn_decode_roofline", "sparse", "attn")])
def test_the_two_roofline_shares_on_the_recorded_cut(
        cell, monkeypatch, name, kind, part):
    value, ctx = _read(name, cell, PLAIN, monkeypatch)
    seconds = _scoped_ops.scope_seconds_a_step(PLAIN, kind)
    need = cell["family"].decode_step(
        ctx["widths"], RECORDED["mean_batch"], RECORDED["mean_cached"],
        weight_bytes=2, kv_bytes=2)["parts"][part]
    by_hand = 100.0 * max(need["bytes"] / 819e9, need["flops"] / 197e12) / seconds
    assert value == pytest.approx(by_hand, rel=1e-3)
    assert 0 < value < 100
    assert ctx["operands"][name]["bound"] == "memory"
    assert value == pytest.approx(RECORDED["by_hand"][name], rel=1e-3)


def test_index_chunk_ms_on_the_recorded_cut(cell, monkeypatch):
    value, _ = _read("index_chunk_ms", cell, PLAIN, monkeypatch)
    # by hand, in other code than the helper's: a sweep over interval ends
    ev = []
    for kind, s, d in PLAIN["chunk_ops"]:
        if kind == "index":
            ev += [(s, 1), (s + d, -1)]
    ev.sort()
    depth, last, busy = 0, None, 0.0
    for t, step in ev:
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    assert value == pytest.approx(
        busy / 1e6 / len(PLAIN["programs"]["prefill_chunk"]), rel=1e-6)
    assert value == pytest.approx(RECORDED["by_hand"]["index_chunk_ms"], rel=1e-3)
    whole = sorted(d for _s, d in PLAIN["programs"]["prefill_chunk"])
    assert 0 < value < whole[-1] / 1e6


def test_keys_selected_pct_on_the_recorded_cut(cell, monkeypatch):
    value, _ = _read("keys_selected_pct", cell, PLAIN, monkeypatch)
    rows = PLAIN["collect"]
    assert rows and all(r["keys_selected"] <= r["keys_cached"] for r in rows)
    assert value == pytest.approx(
        100.0 * sum(r["keys_selected"] for r in rows)
        / sum(r["keys_cached"] for r in rows))
    assert 2.0 < value < 50.0  # every request is 2 to 22 times topk


@pytest.mark.parametrize("name", [
    "index_decode_roofline", "sparse_attn_decode_roofline", "index_chunk_ms",
    "keys_selected_pct"])
def test_a_program_without_the_scopes_gives_none(cell, monkeypatch, name):
    """The parent of the PR that brought these (no ``attn/index`` or
    ``attn/sparse`` scope, no selection's counters), an untraced run, a run
    that wrote no profile: nothing to read, nothing raised."""
    empty = {"programs": {"decode_step": [[0.0, 1e6]],
                          "prefill_chunk": [[2e6, 1e6]]},
             "ops": [], "chunk_ops": [], "collect": []}
    assert _read(name, cell, empty, monkeypatch)[0] is None
    assert _read(name, cell, None, monkeypatch)[0] is None
    assert _read(name, cell, PLAIN, monkeypatch, plain_trace=False)[0] is None


def test_the_patterns_name_the_program_s_scopes():
    kind = _select_ops.kind_of
    assert kind("jit(step)/jit(main)/attn/index/dot_general") == "index"
    assert kind("jit(chunk)/attn/sparse/while/body/attn/index/top_k") == "index"
    assert kind("jit(step)/attn/sparse/gather") == "sparse"
    assert kind("jit(step)/attn/full/dot_general") is None
    assert kind("jit(step)/moe/experts/ragged_dot") is None
    assert kind("jit(step)/attn/indexes") is None


# ----------------------------------------- the benchmark's cell, tiny

sys.path.insert(0, os.path.join(REPO, "tests"))


def _tiny_cell(tmp_path):
    import test_keye as tiny

    serve = {
        "kind": "serve", "loop": "closed", "clients": 8, "shape_seed": 1,
        "pool": 32, "block": 8,
        "prompt_len": {"median": 40, "sigma": 0.5, "min": 10, "max": 100},
        "output_len": {"median": 8, "sigma": 0.5, "min": 2, "max": 20},
        "max_total": 128, "max_requests": 2000, "lead_s": 0.3,
        "stall_s": 5.0, "check": {"requests": 4},
        "trace": {"lead_s": 0.1, "seconds": 0.2},
    }
    config = {**tiny.CONFIG, "serving": {
        "weight_bits": 16, "weight_bytes": 2, "kv_dtype": "bfloat16",
        "kv_bytes": 2, "num_slots": 4, "page_size": 8, "num_pages": 80,
        "queue_capacity": 64,
        # bfloat16 operands and caches against the float32 reference: most
        # sound requests of this tiny cell read 0, and one in five a pick
        # that the rounding swapped (one key of 8 is another: 0.003 to
        # 0.02); a selection of the wrong keys reads 0.07 to 0.22 in every
        # request, zeroed selector keys likewise
        "check": {"gap_limit": 0.04}}}
    fam = spec.load_family("keye_vl2", REPO)
    return {"root": str(tmp_path), "config": config, "traffic": serve,
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
    ``quantize_model(bits=16)``, the bundle, the paged engine with its
    selector pool behind ``ServingServer``, requests several ``topk`` long,
    the reference's check."""
    out = _drive(_tiny_cell(tmp_path))
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert out["e2e"]["serve_tokens_per_s"] > 0
    c = out["counters"]
    assert 0 < c["occupancy_sum_window"] <= c["slot_steps_window"]
    assert c["mean_cached"] > 2 * 8


@pytest.mark.e2e
@pytest.mark.parametrize("broken", ["selection", "selector_cache"])
def test_the_tiny_cell_with_the_selection_broken_is_not_correct(
        tmp_path, monkeypatch, broken):
    """The engine serves with the step reading the LOWEST-scored keys in the
    place of the highest, or with the prefill chunks' selector keys never
    reaching their pages (the steps then score what the pool held before):
    the reference's check sees either."""
    import jax.numpy as jnp

    from distkeras_tpu.models import gqa_moe

    cell = _tiny_cell(tmp_path)
    if broken == "selection":
        real = gqa_moe.select_rows
        monkeypatch.setattr(
            gqa_moe, "select_rows",
            lambda scores, visible, k: real(-scores, visible, k))
    else:
        real = gqa_moe.GroupedQueryMoEBlock.index_inputs

        def no_key(self, pi, h, pos):
            qi, ki, w = real(self, pi, h, pos)
            # the chunk (h has a sequence axis of its own) writes zeros
            return qi, (jnp.zeros_like(ki) if h.ndim == 3 and h.shape[0] == 1
                        and h.shape[1] > 1 else ki), w

        monkeypatch.setattr(gqa_moe.GroupedQueryMoEBlock, "index_inputs",
                            no_key)
    out = _drive(cell)
    assert out["correct"] is False
    gap = {n: v for n, v, _ in out["compared"]}["widest_logit_gap"]
    assert gap > cell["config"]["serving"]["check"]["gap_limit"]
