"""The ``granite_hybrid`` family and the four metrics of the state-space
layers: the contract's names, the configuration against the catalog's row, the
whole model's parameter count, the reference's recurrence against one written
out in NumPy, the decode step's count by part, the readers on a small plain
form of ``benchmark/layer_metrics/_ssm_ops.py``, and a tiny copy of the cell
through its own driver with the timed path broken underneath (a state that no
admission resets, a state that a chunk's padding advances)."""

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
from benchmark.layer_metrics import (  # noqa: E402
    _gqa_ops, _scoped_ops, _ssm_ops)

CELL = "serve_backlog_granite"
CONTRACT = {"widths", "param_count", "make_weights", "build_program_model",
            "train_readings", "token_gaps", "decode_step"}
# a plain form as ``_ssm_ops.load`` gives it, by hand: two decode steps of
# 20 ms with 12 ms under ssm/* (two operations, one of them inside a loop
# that is an event of its own: the union counts the time once), two chunks
# of 50 ms with 30 ms under ssm/*, and the real tokens of three chunk spans
PLAIN = {
    "programs": {"decode_step": [[0.0, 20e6], [30e6, 20e6]],
                 "prefill_chunk": [[60e6, 50e6], [120e6, 50e6]]},
    "ops": [["ssm", 1e6, 8e6], ["ssm", 5e6, 4e6], ["ssm", 10e6, 4e6],
            ["ssm", 31e6, 12e6]],
    "chunk_ops": [["ssm", 61e6, 30e6], ["ssm", 125e6, 30e6]],
    "chunk_tokens": [1024.0, 300.0, 512.0],
    "step_state_bytes": [9.0e9, 9.6e9, 9.6e9],
}
with open(os.path.join(os.path.dirname(__file__), "fixtures",
                       "ssm_ops_small.json")) as _f:
    FIXTURE = json.load(_f)["plain"]


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL, REPO)


def test_the_family_keeps_the_contract_s_names_and_no_other_count(cell):
    """Every name of README.md's "A model family", ``decode_step`` as its one
    count (the chunk's count is its ``scan`` entry), and nothing of the
    program imported but the zoo entry."""
    family = cell["family"]
    for name in CONTRACT:
        assert callable(getattr(family, name, None)), name
    assert not hasattr(family, "train_flops_per_token")
    assert not hasattr(family, "flash_attention_train")
    path = os.path.join(REPO, "benchmark", "families", "granite_hybrid.py")
    with open(path) as f:
        src = f.read()
    assert re.findall(r"^\s*(?:from|import) distkeras_tpu\S*.*$", src, re.M) == [
        "    from distkeras_tpu.models import zoo"]
    import inspect

    mixer = inspect.getsource(family.mamba_mixer)
    # the recurrence a position at a time; the block size is never read
    assert "jax.lax.scan(one" in mixer and "ssm_chunk" not in mixer
    w = family.widths(cell["config"])
    assert (w["vocab"], w["seq"], w["layers"], w["d"]) == (100352, 8192, 40, 2048)
    assert w["layer_types"].count("mamba") == 36
    assert [i for i, k in enumerate(w["layer_types"]) if k == "attention"] == [
        5, 15, 25, 35]
    assert (w["q_heads"], w["kv_heads"], w["head_dim"]) == (32, 8, 64)
    assert (w["ssm_heads"], w["ssm_head_dim"], w["ssm_state"]) == (64, 64, 128)
    assert (w["embed_scale"], w["residual_scale"], w["attn_scale"],
            w["logits_scaling"]) == (12.0, 0.22, 0.015625, 8.0)
    # the state's precision is no width and no option: the program holds it
    # in float32, and nothing here can ask it for less
    assert "state_dtype" not in w and not hasattr(family, "program_control")


def test_the_configuration_holds_the_catalog_s_row(cell):
    """Every key of the catalog row's ``config`` under its own name: equal,
    but ``max_position_embeddings``; nothing that shapes a weight is cut;
    every assumption with a reason."""
    cfg = cell["config"]
    pub = cfg["published"]
    assert pub["model_type"] == "granitemoehybrid"
    assert len(pub["layer_types"]) == 40 and pub["vocab_size"] == 100352
    for key, value in pub.items():
        if key in cfg["reduced"]:
            assert cfg["reduced_from"][key] == [value, cfg[key]]
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["max_position_embeddings"]
    assert cfg["reduced_from"] == {"max_position_embeddings": [131072, 8192]}
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    entry = spec._by_name(cell["bench"]["configs"], cfg["name"], "config")
    assert entry["reduced"] == cfg["reduced"]
    for name in ("weights", "embedding", "output_projections", "mamba_init",
                 "split_order", "gate", "state_precision", "conv", "attention",
                 "n_groups"):
        assert len(cfg["assumed"][name]) > 40, name
    assert "one chip" in cfg["deployment"]


def test_the_whole_model_holds_the_issue_s_parameter_count(cell):
    """The mixer 25,847,232, the attention 10,485,760, the MLP 50,331,648,
    the embedding (tied: once) 205.5e6: 3.19e9 parameters, 6.38e9 bytes."""
    family = cell["family"]
    n = family.param_count(family.widths(cell["config"]))
    assert n["mixer"] == 25_847_232 and n["attention"] == 10_485_760
    assert n["mlp"] == 50_331_648 and n["head"] == 0
    assert n["embedding"] == 100352 * 2048
    assert n["mamba_layer"] == 25_847_232 + 50_331_648 + 4096
    assert n["total"] == 36 * n["mamba_layer"] + 4 * n["attention_layer"] \
        + n["embedding"] + 2048
    assert n["total"] == pytest.approx(3.19e9, rel=1e-3)


def test_the_cell_is_the_issue_s(cell):
    t, s = cell["traffic"], cell["config"]["serving"]
    assert (t["loop"], t["clients"], s["num_slots"]) == ("closed", 128, 64)
    assert t["prompt_len"] == {"median": 384, "sigma": 0.9, "min": 32, "max": 4096}
    assert t["output_len"] == {"median": 256, "sigma": 0.7, "min": 16, "max": 2048}
    assert t["max_total"] == 6144 and cell["cell"]["chips"] == 1
    assert t["lead_s"] == 30.0 and t["check"]["requests"] == 6
    assert s["page_size"] == 16 and s["kv_dtype"] == "bfloat16"
    assert s["weight_bits"] == 16 and "gap_limit" in s["check"]
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"decode_step_ms", "decode_step_roofline", "prefill_chunk_ms",
            "kv_pages_in_use_pct", "attn_decode_roofline",
            "paged_gqa_roofline",
            "device_idle_pct.serve", "loop_busy_pct", "ssm_decode_roofline",
            "ssm_scan_roofline", "ssm_step_share_pct",
            "state_gb_per_step"} <= reported
    # ``dense_ffn_decode_roofline`` is NOT reported here though the 40 MLPs
    # open its scope: on the chip XLA streams their matrices into fast
    # memory by asynchronous slices that carry no scope, the scope's own
    # operations then take 2.50 ms a step for 4.03e9 bytes, and the share
    # read 196% (PERF.md sections 5 and 7)
    assert not {"moe_decode_roofline", "mla_decode_roofline",
                "dense_ffn_decode_roofline"} & reported
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    new = [m for m in cell["bench"]["per_layer"]
           if m["name"].startswith(("ssm_", "state_gb"))]
    assert [m["workloads"] for m in new] == [[CELL]] * 4
    assert len({m["layer"] for m in new}) == 1


def test_the_reference_s_recurrence_is_the_one_written_out_in_numpy(cell):
    """``mamba_mixer`` against the layer equations in NumPy, a position at a
    time with Python loops, on 7 positions: the split orders, the causal
    convolution's zeros before the start, softplus, the decay, the outer
    product, the read-out, ``D``, the gate before the norm."""
    import jax
    import jax.numpy as jnp

    family = cell["family"]
    w = {"d": 12, "ssm_heads": 3, "ssm_head_dim": 4, "ssm_state": 5,
         "ssm_groups": 1, "ssm_conv": 4, "eps": 1e-5}
    inner, conv, cols = family._ssm_sizes(w)
    assert (inner, conv, cols) == (12, 22, 37)
    rng = np.random.default_rng(0)
    p = {"w_in": rng.normal(size=(12, cols)) * 0.5,
         "conv_w": rng.uniform(-0.5, 0.5, (4, conv)),
         "conv_b": rng.uniform(-0.5, 0.5, conv),
         "dt_bias": rng.normal(size=3), "a_log": np.log(rng.uniform(1, 16, 3)),
         "d_skip": rng.normal(size=3), "norm": {"gamma": rng.normal(size=12)},
         "w_out": rng.normal(size=(12, 12)) * 0.5}
    u = rng.normal(size=(7, 12))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(family.mamba_mixer(
            jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p),
            jnp.asarray(u, jnp.float32), w, family.dot_highest))

    silu = lambda v: v / (1 + np.exp(-v))  # noqa: E731
    zxd = u @ p["w_in"]
    z, raw, dt = zxd[:, :12], zxd[:, 12:34], zxd[:, 34:]
    state = np.zeros((3, 4, 5))
    want = np.zeros((7, 12))
    for t in range(7):
        xbc = p["conv_b"].copy()
        for j in range(4):
            if t - 3 + j >= 0:
                xbc += p["conv_w"][j] * raw[t - 3 + j]
        xbc = silu(xbc)
        x, b, c = xbc[:12].reshape(3, 4), xbc[12:17], xbc[17:]
        step = np.log1p(np.exp(dt[t] + p["dt_bias"]))
        y = np.zeros((3, 4))
        for h in range(3):
            a = np.exp(step[h] * -np.exp(p["a_log"][h]))
            state[h] = a * state[h] + step[h] * np.outer(x[h], b)
            y[h] = state[h] @ c + p["d_skip"][h] * x[h]
        g = y.reshape(12) * silu(z[t])
        g = g / np.sqrt(np.mean(g * g) + 1e-5) * p["norm"]["gamma"]
        want[t] = g @ p["w_out"]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)  # values of 3


def test_decode_step_s_parts_sum_to_its_whole_and_the_state_is_counted_twice(
        cell):
    """The parts sum to the whole; a Mamba layer's state is read once and
    written once a slot and step (36 x 64 x 2 x 2,097,152 bytes at 64 slots),
    whatever the cached length; the attention layers' cache grows with it;
    the head reads the tied table once."""
    family = cell["family"]
    w = family.widths(cell["config"])
    n = family.decode_step(w, 64.0, 900.0, weight_bytes=2, kv_bytes=2)
    for key in ("flops", "bytes"):
        assert n[key] == pytest.approx(sum(p[key] for p in n["parts"].values()))
    assert set(n["parts"]) == {"ssm", "attn", "dense", "head"}
    assert n["state_bytes"] == 36 * 64 * 2 * 2_097_152
    assert n["kv_bytes"] == 4 * 2 * 8 * 64 * 900 * 64 * 2
    assert n["weight_bytes"] == pytest.approx(6.38e9, rel=2e-3)
    ssm = n["parts"]["ssm"]
    tails = 36 * 64 * 2 * 3 * 4352 * 4
    assert ssm["bytes"] == pytest.approx(
        n["state_bytes"] + tails + 36 * (25_821_184 * 2 + 26_048 * 4))
    # two thirds of a step's bytes are the Mamba layers' own
    assert 0.6 < ssm["bytes"] / n["bytes"] < 0.75
    longer = family.decode_step(w, 64.0, 1800.0, weight_bytes=2, kv_bytes=2)
    assert longer["parts"]["ssm"] == ssm
    assert longer["kv_bytes"] == 2 * n["kv_bytes"]
    half = family.decode_step(w, 32.0, 900.0, weight_bytes=2, kv_bytes=2)
    assert half["state_bytes"] == n["state_bytes"] / 2
    assert n["parts"]["head"]["bytes"] == 100352 * 2048 * 2
    # the paged kernel alone: 57 pages of 16 tokens x 8 heads x 64 x (K and
    # V) x 2 bytes a slot and attention layer, 32 query heads of 64 in and
    # out in float32; 4 x 32 x 64 operations a cached position
    kernel = n["kernel"]
    assert kernel["pages_a_slot"] == 57
    assert kernel["bytes"] == 4 * 64 * (57 * 32_768 + 2 * 32 * 64 * 4)
    assert kernel["flops"] == 4 * 64 * 900 * 32 * 64 * 4
    assert kernel["bytes"] > n["kv_bytes"]  # whole pages, and the queries
    scan = n["scan"]
    assert scan["flops_a_token"] > 36 * 2 * 25_821_184
    assert scan["bytes_a_chunk"] == pytest.approx(
        36 * (25_821_184 * 2 + 26_048 * 4 + 2 * (2_097_152 + 3 * 4352 * 4)))


def test_the_thread_metrics_keep_what_pr_39_s_test_held_but_its_two_pins():
    """``test_benchmark_keye.py`` pins what follows the four thread metrics
    in the per-layer list to PR 39's four, and the thread metrics'
    ``workloads`` to the five serving cells of its day; this PR's cell and
    metrics break those two asserts and ``tests/conftest.py`` marks that test
    ``xfail`` (strictly). Everything else it held is held here, by ORDER and
    MEMBERSHIP and by no list's end or length, so that the next cell or
    metric breaks nothing here: the four are there in their order, on every
    serving cell of PR 39's day and on this PR's, with their sources, layers
    and readers, and not on the training cell; PR 39's four follow them in
    their order, and this PR's four come after those in theirs."""
    bench = spec.load_benchmark(REPO)
    names = [m["name"] for m in bench["per_layer"]]
    thread = ("step_call_cpu_ms", "sched_offcpu_ms",
              "stream_send_in_call_pct", "loop_busy_pct")
    at = names.index(thread[0])
    assert tuple(names[at:at + 4]) == thread
    in_order = [
        *thread, "index_decode_roofline", "sparse_attn_decode_roofline",
        "index_chunk_ms", "keys_selected_pct", "ssm_decode_roofline",
        "ssm_scan_roofline", "ssm_step_share_pct", "state_gb_per_step"]
    assert [n for n in names if n in in_order] == in_order
    serving = ["serve_backlog", "serve_backlog_kanana",
               "serve_backlog_longcat", "serve_backlog_laguna",
               "serve_backlog_keye"]
    for m in bench["per_layer"][at:at + 4]:
        assert [c for c in m["workloads"] if c in serving] == serving
        assert CELL in m["workloads"] and m["source"] == "program_span"
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
    assert not set(thread) & train


def _ctx(cell, plain_trace=True):
    family = cell["family"]
    return {"trace": {"devices": 1} if plain_trace else None, "operands": {},
            "counters": {"mean_batch": 60.0, "mean_cached": 800.0},
            "family": family, "widths": family.widths(cell["config"]),
            "config": cell["config"], "peaks": spec.load_peaks("TPU v5 lite")}


def _read(name, cell, plain, monkeypatch, **kw):
    monkeypatch.setattr(_ssm_ops, "run_profile", lambda: plain)
    m = {"name": name, **spec.load_layer_metric(name, REPO)}
    ctx = _ctx(cell, **kw)
    return readers.read(m, ctx), ctx


def test_ssm_step_share_pct_on_the_plain_form(cell, monkeypatch):
    value, _ = _read("ssm_step_share_pct", cell, PLAIN, monkeypatch)
    # the union of [1, 9] [5, 9] [10, 14] is 12 ms, and 12 ms: 24 of 40
    assert value == pytest.approx(60.0)
    assert _scoped_ops.scope_seconds_a_step(PLAIN, "ssm") == pytest.approx(0.012)


def test_ssm_decode_roofline_on_the_plain_form(cell, monkeypatch):
    value, ctx = _read("ssm_decode_roofline", cell, PLAIN, monkeypatch)
    need = cell["family"].decode_step(
        ctx["widths"], 60.0, 800.0, weight_bytes=2, kv_bytes=2)["parts"]["ssm"]
    assert value == pytest.approx(100.0 * need["bytes"] / 819e9 / 0.012, rel=1e-3)
    assert ctx["operands"]["ssm_decode_roofline"]["bound"] == "memory"


def test_ssm_scan_roofline_on_the_plain_form(cell, monkeypatch):
    value, ctx = _read("ssm_scan_roofline", cell, PLAIN, monkeypatch)
    scan = cell["family"].decode_step(
        ctx["widths"], 60.0, 800.0, weight_bytes=2, kv_bytes=2)["scan"]
    ops = ctx["operands"]["ssm_scan_roofline"]
    assert ops["mean_chunk_tokens"] == pytest.approx(612.0)
    assert ops["flops"] == pytest.approx(scan["flops_a_token"] * 612.0)
    least = max(ops["flops"] / 197e12, scan["bytes_a_chunk"] / 819e9)
    assert value == pytest.approx(100.0 * least / 0.030, rel=1e-3)
    assert 0 < value < 100


@pytest.mark.parametrize("name, module, ops_name, part", [
    ("paged_gqa_roofline", _gqa_ops, "kernel", lambda n: n["kernel"]),
    ("attn_decode_roofline", _gqa_ops, "attn", lambda n: n["parts"]["attn"]),
])
def test_the_accepted_shares_find_this_family_s_counts(
        cell, monkeypatch, name, module, ops_name, part):
    """The accepted metrics the cell is listed on read this family's counts:
    the paged kernel's own calls against ``kernel`` (the four attention
    layers go through ``paged_decode_attention``, two heads of 64 as one of
    128 lanes), the ``attn/full`` scope against the ``attn`` part."""
    plain = {"programs": PLAIN["programs"],
             "ops": [[ops_name, 1e6, 1e6], [ops_name, 31e6, 1e6]],
             "iterations": []}
    monkeypatch.setattr(module, "run_profile", lambda: plain)
    m = {"name": name, **spec.load_layer_metric(name, REPO)}
    ctx = _ctx(cell)
    value = readers.read(m, ctx)
    need = part(cell["family"].decode_step(
        ctx["widths"], 60.0, 800.0, weight_bytes=2, kv_bytes=2))
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert value == pytest.approx(100.0 * least / 0.001, rel=1e-3)
    assert name in {x["name"] for x in cell["per_layer"]}


@pytest.mark.parametrize("name", ["ssm_decode_roofline", "ssm_scan_roofline",
                                  "ssm_step_share_pct"])
def test_a_program_without_the_scopes_gives_none(cell, monkeypatch, name):
    """The parent of the PR that brought these (no ``ssm/`` scope, no
    ``tokens`` on a chunk's span), an untraced run, a run that wrote no
    profile: nothing to read, nothing raised."""
    empty = {"programs": {"decode_step": [[0.0, 1e6]],
                          "prefill_chunk": [[2e6, 1e6]]},
             "ops": [], "chunk_ops": [], "chunk_tokens": []}
    assert _read(name, cell, empty, monkeypatch)[0] is None
    assert _read(name, cell, None, monkeypatch)[0] is None
    assert _read(name, cell, PLAIN, monkeypatch, plain_trace=False)[0] is None


def test_state_gb_per_step_reads_the_span_and_gives_none_without_it(
        cell, monkeypatch):
    assert _read("state_gb_per_step", cell, PLAIN, monkeypatch)[0] == \
        pytest.approx(9.6)
    bare = {**PLAIN, "step_state_bytes": []}
    assert _read("state_gb_per_step", cell, bare, monkeypatch)[0] is None
    assert _read("state_gb_per_step", cell, None, monkeypatch)[0] is None


def test_the_fixture_of_the_synthetic_run_is_a_plain_form(cell, monkeypatch):
    """``tests/conftest.py`` hands ``test_benchmark_spec``'s synthetic run
    this plain form: every key the loader gives, and a value from each of the
    four readers."""
    assert set(FIXTURE) == set(PLAIN)
    for name in ("ssm_decode_roofline", "ssm_scan_roofline",
                 "ssm_step_share_pct", "state_gb_per_step"):
        assert _read(name, cell, FIXTURE, monkeypatch)[0] > 0, name


def test_the_pattern_names_the_program_s_scopes():
    for part in ("proj", "update", "scan"):
        assert _ssm_ops.SSM.search(f"jit(step)/ssm/{part}/dot_general")
    assert _ssm_ops.SSM.search("jit(chunk)/ssm/scan/while/body/mul")
    assert not _ssm_ops.SSM.search("jit(step)/attn/full/dot_general")
    assert not _ssm_ops.SSM.search("jit(step)/ffn/dense/dot_general")


# ----------------------------------------- the benchmark's cell, tiny

sys.path.insert(0, os.path.join(REPO, "tests"))


def _tiny_cell(tmp_path):
    import test_granite_hybrid as tiny

    serve = {
        "kind": "serve", "loop": "closed", "clients": 8, "shape_seed": 1,
        "pool": 32, "block": 8,
        "prompt_len": {"median": 20, "sigma": 0.6, "min": 1, "max": 90},
        "output_len": {"median": 8, "sigma": 0.5, "min": 2, "max": 20},
        "max_total": 128, "max_requests": 2000, "lead_s": 0.3,
        "stall_s": 5.0, "check": {"requests": 12},
        "trace": {"lead_s": 0.1, "seconds": 0.2},
    }
    fam = spec.load_family("granite_hybrid", REPO)
    return {"root": str(tmp_path), "config": tiny.CONFIG, "traffic": serve,
            "family": fam, "cell": {"chips": 1}}


def _drive(cell, seed=5):
    from benchmark import drive_serve, harness

    args = types.SimpleNamespace(seed=seed, seconds=0.6, trace=0)
    out = drive_serve.run(cell, args, time.perf_counter(),
                          harness.CompileWatch())
    assert out["compiled_in_window"] == 0
    return out


@pytest.mark.e2e
def test_a_tiny_copy_of_the_cell_is_correct_through_the_driver(tmp_path):
    """``drive_serve.run`` as the benchmark runs it: the family's weights,
    ``quantize_model(bits=16)``, the bundle, the paged engine with a state a
    slot behind ``ServingServer``, twice as many clients as slots so that
    every slot is reused, prompts of one token among them, the reference's
    check. ``release`` frees the states with the pools."""
    out = _drive(_tiny_cell(tmp_path), seed=2**31 + 321)
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert out["e2e"]["serve_tokens_per_s"] > 0
    c = out["counters"]
    assert 0 < c["occupancy_sum_window"] <= c["slot_steps_window"]


def _remembers_long(fam, monkeypatch):
    """The family's seeded weights with every Mamba head slow (a decay of
    0.98 a position at a step of about 1), for the program and the reference
    alike: what a state holds then outlives a request of this tiny mix,
    which the seeded spread of decays (0.2 to 0.999, most of it gone in ten
    positions) lets show only now and then."""
    import math

    import jax.numpy as jnp

    real = fam.make_weights

    def long_memory(w, seed):
        p = real(w, seed)
        for i, kind in enumerate(w["layer_types"]):
            if kind == "mamba":
                m = p[str(i + 1)]["mixer"]
                m["a_log"] = jnp.full_like(m["a_log"], math.log(0.02))
                m["dt_bias"] = jnp.full_like(m["dt_bias"], 0.5)
        return p

    monkeypatch.setattr(fam, "make_weights", long_memory)


@pytest.mark.e2e
@pytest.mark.parametrize("broken", [None, "reset", "padding"],
                         ids=["sound", "reset", "padding"])
def test_the_tiny_cell_with_the_timed_path_broken_is_not_correct(
        tmp_path, monkeypatch, broken):
    """The engine serves with a state that no admission resets (a slot's
    next request inherits its last one's), or with a chunk's padding
    advancing the state and taking the convolution's tail from behind the
    real tokens: the reference's check sees either (0.001 to 0.007 against a
    limit of 2e-5, by seed), and passes the same cell left whole (0)."""
    from distkeras_tpu.models import mamba2
    from distkeras_tpu.serving import engine

    cell = _tiny_cell(tmp_path)
    _remembers_long(cell["family"], monkeypatch)
    if broken == "reset":
        monkeypatch.setattr(
            engine.DecodeStepper, "_zero_where",
            staticmethod(lambda fresh, arrays: tuple(arrays)))
    elif broken == "padding":
        real = mamba2.Mamba2Block.forward

        def all_real(self, p, x, carry, n_valid=None, keep=None, step=False):
            return real(self, p, x, carry, None, keep, step)

        monkeypatch.setattr(mamba2.Mamba2Block, "forward", all_real)
    out = _drive(cell)
    gap = {n: v for n, v, _ in out["compared"]}["widest_logit_gap"]
    limit = cell["config"]["serving"]["check"]["gap_limit"]
    if broken is None:
        assert out["correct"] is True and gap <= limit
    else:
        assert out["correct"] is False and gap > 10 * limit
