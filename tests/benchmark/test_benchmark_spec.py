"""BENCHMARK.json against the contract's names and units, every cell's files
found by name, and a throw-away cell and a throw-away model family added by
files alone."""

import glob
import json
import os
import re
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import benchmark_tiny_cells as tiny  # noqa: E402

from benchmark import harness, readers, spec  # noqa: E402

REPO = tiny.REPO

BENCH = spec.load_benchmark(REPO)
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            yield group, entry["name"]
    for wl in BENCH["workloads"]:
        yield "workloads.config", wl["config"]
        yield "workloads.traffic", wl["traffic"]
    for cfg in BENCH["configs"]:
        for key in cfg["reduced"]:
            yield "configs.reduced", key


@pytest.mark.parametrize("group,name", sorted(set(_names())))
def test_every_name_is_of_the_allowed_characters(group, name):
    assert spec.NAME.match(name), (group, name)


@pytest.mark.parametrize(
    "metric", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_metric_entry_is_well_formed(metric):
    assert spec.UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if "bound" in metric:
        allowed |= {"bound"}
        assert 0 < metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        allowed |= {"layer", "moves"}
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    assert set(metric) <= allowed
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", [])) <= cells


def test_the_file_keeps_to_the_contract_s_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_and_reports_enough(workload):
    cell = spec.load_cell(workload, REPO)
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"], "a cell reports at least one per-layer metric"
    for m in cell["per_layer"]:
        assert m["moves"] in e2e, (m["name"], "moves a metric the cell lacks")
        reader = spec.load_layer_metric(m["name"], REPO)
        assert reader["reader"] == "python" or reader["reader"] in readers.KINDS
    assert cell["traffic"]["kind"] in ("train", "serve")


class _Recorded(dict):
    """A configuration file that notes which of its keys are read."""

    def __init__(self, *a):
        super().__init__(*a)
        self.read = set()

    def __getitem__(self, key):
        self.read.add(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        self.read.add(key)
        return super().get(key, default)


def _copy_with_a_throwaway_family(root):
    """A copy of the benchmark with what a later PR would add beside it."""
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark")
    return tiny.add_throwaway_family(root)


@pytest.mark.parametrize(
    "name", [c["name"] for c in BENCH["configs"]] + ["throwaway"])
def test_configurations_carry_the_published_widths(name, tmp_path):
    """Every configuration, whatever its family's keys are: the source's own
    value of each size beside the value held, every cut listed. The last
    case is a configuration with other keys than GPT-2's, in a copy."""
    root, bench = REPO, BENCH
    if name == "throwaway":
        root, bench = str(tmp_path), _copy_with_a_throwaway_family(tmp_path)
    config = spec._by_name(bench["configs"], name, "config")
    with open(os.path.join(root, config["file"])) as f:
        cfg = _Recorded(json.load(f))
    assert cfg["published"], "the source's own sizes"
    for key, value in cfg["published"].items():
        if key in config["reduced"]:
            assert cfg[key] != value
            assert cfg["reduced_from"][key] == [value, cfg[key]]
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == config["reduced"]
    assert "assumed" in cfg and "source" in cfg
    assert "precision" in cfg.get("serving", cfg.get("training"))
    cfg.read.clear()
    w = spec.family_of(cfg, root, bench).widths(cfg)
    assert all(w[k] > 0 for k in ("vocab", "seq", "layers"))
    sizes = {k for k in cfg.read if isinstance(cfg[k], int)}
    assert sizes and sizes <= set(cfg["published"]), (
        "a size that widths reads has no published value beside it")


@pytest.mark.parametrize("name", ["cerebras-gpt-1.3b", "cerebras-gpt-1.3b-cut"])
def test_the_cerebras_configurations_keep_their_six_numbers(name):
    entry = spec._by_name(BENCH["configs"], name, "config")
    with open(os.path.join(REPO, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["published"] == {
        "n_embd": 2048, "n_head": 16, "n_inner": 8192, "vocab_size": 50257,
        "n_positions": 2048, "n_layer": 24}
    assert cfg["family"] == "gpt2"
    w = spec.family_of(cfg, REPO, BENCH).widths(cfg)
    assert (w["d"], w["heads"], w["inner"], w["vocab"], w["seq"]) == (
        2048, 16, 8192, 50257, 2048)
    assert w["layers"] == (6 if name.endswith("-cut") else 24)


def _synthetic_run(cell):
    """What a traced run of ``cell`` hands the readers, with round numbers."""
    return {
        "samples": {"itl_gaps_s": [0.25, 0.30, 0.35]},
        "counters": {"compile_seconds_setup": 1.5, "occupancy_sum_window": 90.0,
                     "slot_steps_window": 100, "mean_batch": 30.0,
                     "mean_cached": 400.0, "traced_steps": 10, "batch": 4,
                     "steps": 200},
        "trace": {"idle_pct": 5.0,
                  "programs": {"decode_step": {"count": 10.0, "total_s": 1.0,
                                               "median_s": 0.1},
                               "train_window": {"count": 10.0, "total_s": 2.0,
                                                "median_s": 0.2}},
                  "kernels": {"flash": {"count": 180.0, "total_s": 0.25}}},
        "e2e": {"train_tokens_per_s_per_chip": 40000.0,
                "serve_tokens_per_s": 100.0, "setup_s": 20.0},
        "family": cell["family"], "widths": cell["family"].widths(cell["config"]),
        "config": cell["config"],
        "traffic": cell["traffic"], "peaks": spec.load_peaks("TPU v5 lite"),
        "chips": cell["cell"]["chips"], "operands": {},
    }


@pytest.mark.parametrize("workload,metric", [
    (w["name"], m["name"]) for w in BENCH["workloads"] for m in BENCH["per_layer"]
    if w["name"] in m.get("workloads", [w["name"]])])
def test_every_per_layer_metric_finds_its_number_in_its_cells(workload, metric):
    cell = spec.load_cell(workload, REPO)
    ctx = _synthetic_run(cell)
    m = {"name": metric, **spec.load_layer_metric(metric, REPO)}
    value = readers.read(m, ctx)
    assert value is not None and value > 0
    by_hand = {"itl_p95_ms": 345.0, "slot_occupancy_pct": 90.0, "compile_s": 1.5,
               "decode_step_ms": 100.0, "train_step_ms": 200.0,
               "device_idle_pct.train": 5.0, "device_idle_pct.serve": 5.0}
    if metric in by_hand:
        assert value == pytest.approx(by_hand[metric])
    if metric == "train_mfu_pct":  # 2.58 GFLOP a token at 40k tokens/s
        assert value == pytest.approx(100 * 40000 * 2.58e9 / 197e12, rel=0.01)
        assert ctx["operands"][metric]["peak_flops_per_s"] == 197e12
    if "roofline" in metric:
        assert 0 < value < 100 and ctx["operands"][metric]["bound"] in (
            "compute", "memory")


def test_a_reader_that_finds_nothing_returns_nothing():
    cell = spec.load_cell("serve_backlog", REPO)
    ctx = {**_synthetic_run(cell), "trace": {}, "samples": {}, "counters": {}}
    for entry in cell["per_layer"]:
        m = {"name": entry["name"], **spec.load_layer_metric(entry["name"], REPO)}
        assert readers.read(m, ctx) is None, entry["name"]


def test_unknown_device_kind_is_an_error():
    assert spec.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v9000")


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A later PR's way in: a configuration, a traffic mix, a cell and a
    per-layer metric arrive as new files and new entries; no file that is
    there is edited."""
    root = tmp_path
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark")
    extra = root / "extra_bench"
    for sub in ("configs", "traffic", "layer_metrics"):
        (extra / sub).mkdir(parents=True)
    cfg = json.load(open(os.path.join(
        REPO, "benchmark/configs/cerebras-gpt-1.3b-cut.json")))
    cfg["name"] = "throwaway"
    (extra / "configs/throwaway.json").write_text(json.dumps(cfg))
    mix = json.load(open(os.path.join(REPO, "benchmark/traffic/backlog.json")))
    mix["prompt_len"] = {"median": 1400, "sigma": 0.2, "min": 1024, "max": 1900}
    mix["output_len"] = {"median": 32, "sigma": 0.4, "min": 16, "max": 64}
    (extra / "traffic/long_prompt.json").write_text(json.dumps(mix))
    (extra / "layer_metrics/itl_p50_ms.json").write_text(json.dumps(
        {"reader": "sample_percentile", "sample": "itl_gaps_s",
         "percentile": 50, "scale": 1000.0}))
    (extra / "layer_metrics/twice_setup.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['e2e']['setup_s']\n")
    bench = json.loads(json.dumps(BENCH))
    bench["paths"].append("extra_bench")
    bench["configs"].append({
        "name": "throwaway", "source": "https://example.org/x",
        "file": "extra_bench/configs/throwaway.json", "reduced": ["n_layer"],
        "why": "test"})
    bench["workloads"].append({
        "name": "throwaway.long_prompt", "config": "throwaway",
        "traffic": "long_prompt", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("throwaway.long_prompt")
    for name, moves in (("itl_p50_ms", "serve_tokens_per_s"), ("twice_setup", "setup_s")):
        bench["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "host_clock", "layer": "test", "moves": moves,
            "workloads": ["throwaway.long_prompt"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("throwaway.long_prompt", str(root))
    assert cell["config"]["name"] == "throwaway"
    assert cell["traffic"]["prompt_len"]["min"] == 1024
    assert {m["name"] for m in cell["per_layer"]} == {"itl_p50_ms", "twice_setup"}
    ctx = {"samples": {"itl_gaps_s": [0.4, 0.5, 0.6]}, "e2e": {"setup_s": 21.0}}
    by_name = {m["name"]: spec.load_layer_metric(m["name"], str(root), bench)
               for m in cell["per_layer"]}
    assert readers.read({"name": "itl_p50_ms", **by_name["itl_p50_ms"]}, ctx) == 500.0
    assert readers.read(by_name["twice_setup"], ctx) == 42.0
    # and the generator reads the new mix with no new code
    from benchmark import loadgen

    reqs = loadgen.make_requests(cell["traffic"], 5, 50257, 40)
    assert len(reqs) == 40 and min(len(r["prompt"]) for r in reqs) >= 1024


# ------------------------------------------------------- model families

REQUIRED = {"widths", "param_count", "make_weights", "build_program_model",
            "train_readings", "token_gaps"}
COUNTS = {"train_flops_per_token", "decode_step", "flash_attention_train"}


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_s_family_keeps_the_contract(config):
    """README.md, "A model family": the names every family brings, and the
    counts it may bring."""
    with open(os.path.join(REPO, config["file"])) as f:
        family = spec.family_of(json.load(f), REPO, BENCH)
    for name in REQUIRED:
        assert callable(getattr(family, name, None)), name
    for name in COUNTS & set(dir(family)):
        assert callable(getattr(family, name)), name


def test_the_harness_takes_from_a_family_the_contract_s_names_and_no_others():
    """The drivers, the controls, ``run.py`` and the metrics reach a model
    through the cell's family alone, by the names the contract lists; and
    nothing of the harness outside ``families/`` names a width of one model
    or imports the program's models."""
    taken = set()
    sources = glob.glob(os.path.join(REPO, "benchmark", "*.py")) + glob.glob(
        os.path.join(REPO, "benchmark", "layer_metrics", "*.py"))
    for path in sources:
        with open(path) as f:
            src = f.read()
        taken |= set(re.findall(r"\bfamily\.([a-z_]+)\(", src))
        taken |= set(re.findall(r'getattr\(ctx\["family"\], "([a-z_]+)"', src))
        for word in ("n_embd", "n_inner", "n_head", "zoo.", "distkeras_tpu.models",
                     '"heads"', '"inner"'):
            assert word not in src, (path, word)
    assert taken <= REQUIRED | COUNTS, taken - REQUIRED - COUNTS
    assert COUNTS <= taken and {"widths", "make_weights", "build_program_model",
                                "train_readings", "token_gaps"} <= taken
    assert not os.path.exists(os.path.join(REPO, "benchmark", "model_build.py"))


def test_a_family_is_added_by_files_alone(tmp_path):
    """A later PR's way in for a new architecture: a directory of its own in
    ``paths`` with ``families/<name>.py``, a configuration under that
    family's own keys (none of them GPT-2's), a cell, and an entry each; no
    file that is there is edited."""
    before = {p: open(p, "rb").read() for p in glob.glob(
        os.path.join(REPO, "benchmark", "**", "*.*"), recursive=True)
        if os.path.isfile(p) and "__pycache__" not in p}
    _copy_with_a_throwaway_family(tmp_path)
    for path, content in before.items():
        copy = os.path.join(str(tmp_path), os.path.relpath(path, REPO))
        assert open(copy, "rb").read() == content, path
    cell = spec.load_cell("throwaway.pretrain_2k", str(tmp_path))
    assert cell["family"].__file__ == str(
        tmp_path / "extra_bench/families/throwaway.py")
    assert not set(tiny.THROWAWAY_KEYS) & set(cell["config"])
    w = cell["family"].widths(cell["config"])
    assert (w["vocab"], w["seq"], w["layers"], w["d"]) == (50257, 2048, 6, 2048)
    assert cell["family"].param_count(w)["total"] == pytest.approx(512e6, rel=0.01)
    # the committed cells' family is still found, and is another module
    own = spec.load_cell("train_seq2048", str(tmp_path))["family"]
    assert own.__file__ == str(tmp_path / "benchmark/families/gpt2.py")
    assert {m["name"] for m in cell["per_layer"]} == {
        m["name"] for m in spec.load_cell("train_seq2048", REPO)["per_layer"]}


@pytest.mark.parametrize("edit,says", [
    (lambda cfg: cfg.pop("family"), "names no family"),
    (lambda cfg: cfg.update(family="no_such_family"), "no families/no_such_family.py"),
    (lambda cfg: cfg.update(family="../configs/x"), "not a name"),
], ids=["no_family", "unknown_family", "not_a_name"])
def test_a_configuration_whose_family_cannot_be_found_is_an_error(
        edit, says, tmp_path):
    _copy_with_a_throwaway_family(tmp_path)
    path = tmp_path / "extra_bench/configs/throwaway.json"
    cfg = json.loads(path.read_text())
    edit(cfg)
    path.write_text(json.dumps(cfg))
    with pytest.raises(spec.SpecError, match=says):
        spec.load_cell("throwaway.pretrain_2k", str(tmp_path))


def test_a_family_without_a_count_leaves_that_metric_out_of_the_line(tmp_path):
    """The throw-away family brings no ``flash_attention_train``: its cell's
    traced run reports every other per-layer metric of the cell, leaves
    ``flash_attn_roofline`` out, and ends as any run does."""
    from benchmark import run

    _copy_with_a_throwaway_family(tmp_path)
    cell = spec.load_cell("throwaway.pretrain_2k", str(tmp_path))
    assert "flash_attn_roofline" in {m["name"] for m in cell["per_layer"]}
    out = _synthetic_run(cell)
    metrics, operands = run.per_layer_metrics(
        cell, out, {"kind": "TPU v5 lite"}, out["trace"])
    harness.check_shares(metrics, operands)
    assert set(metrics) == {m["name"] for m in cell["per_layer"]} - {
        "flash_attn_roofline"}
    assert metrics["train_mfu_pct"]["value"] == pytest.approx(
        100 * 40000 * 2.58e9 / 197e12, rel=0.01)
