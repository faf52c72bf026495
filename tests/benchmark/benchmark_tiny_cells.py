"""Tiny cells for the benchmark's CPU tests: the drivers' own code paths at
sizes a test run can hold. Not a benchmark configuration. And a throw-away
model family, as a later PR would add one: by files and entries alone."""

import functools
import json
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import spec  # noqa: E402

CONFIG = {
    "family": "gpt2",
    "vocab_size": 211, "n_positions": 64, "n_embd": 32, "n_head": 2,
    "n_inner": 128, "n_layer": 2, "assumed": {"gelu": "tanh"},
    "serving": {"weight_bits": 8, "weight_bytes": 1, "kv_dtype": "bfloat16",
                "kv_bytes": 2, "num_slots": 4, "page_size": 4,
                "num_pages": 128, "queue_capacity": 64,
                "check": {"gap_limit": 0.005}},
}
TRAIN = {
    "kind": "train", "shape_seed": 1, "alphabet": 16, "batch_per_chip": 2,
    "window": 1, "optimizer": "adam", "learning_rate": 3e-3,
    "compute_dtype": "bfloat16", "attention": "dense", "warm_calls": 1,
    "check": {"steps": 3, "limits": {"loss_abs_diff": 0.01,
                                     "moment_norm_gap": 0.03,
                                     "change_norm_gap": 0.03}},
    "trace": {"lead_s": 0.1, "seconds": 0.2},
}
SERVE = {
    "kind": "serve", "loop": "closed", "clients": 8, "shape_seed": 1,
    "pool": 32, "block": 8,
    "prompt_len": {"median": 12, "sigma": 0.6, "min": 2, "max": 40},
    "output_len": {"median": 8, "sigma": 0.5, "min": 2, "max": 20},
    "max_total": 64, "max_requests": 2000, "lead_s": 0.3,
    "stall_s": 5.0, "check": {"requests": 4}, "trace": {"lead_s": 0.1, "seconds": 0.2},
}


def family(name: str, bench_root: str = REPO):
    """A family's module, found as ``spec.load_cell`` finds it among the
    paths of ``bench_root``'s BENCHMARK.json; loaded once a test process,
    so that what it has jitted stays compiled from test to test."""
    return _family(name, bench_root)


@functools.lru_cache(maxsize=None)
def _family(name, bench_root):
    return spec.family_of({"family": name}, bench_root)


def cell(traffic: dict, chips: int = 1, root: str = "/tmp",
         config: dict = CONFIG, bench_root: str = REPO) -> dict:
    """A cell as ``spec.load_cell`` hands it to a driver, built here from
    dicts."""
    return {"root": root, "config": config, "traffic": traffic,
            "family": family(config["family"], bench_root),
            "cell": {"chips": chips}}


def args(seed: int = 2**31 + 77, seconds: float = 0.6, trace: int = 0):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)


def train_windowed(window: int) -> dict:
    """The training mix with ``window`` steps a call, checked after one
    window (or three calls at window 1)."""
    steps = 3 if window == 1 else window
    return {**TRAIN, "window": window,
            "check": {**TRAIN["check"], "steps": steps}}


# ------------------------------------------------- a family added by files
#
# The GPT-2 block again, under a configuration file whose keys are another
# source's: nothing of the harness may read a GPT-2 key. It brings no
# ``flash_attention_train``, as a family that runs no such kernel.

THROWAWAY_KEYS = {
    "n_embd": "hidden_size", "n_layer": "num_hidden_layers",
    "n_head": "num_attention_heads", "n_inner": "intermediate_size",
    "n_positions": "max_position_embeddings"}

THROWAWAY_FAMILY = '''\
"""The GPT-2 block under another source's keys (a test's throw-away)."""

from benchmark.families import gpt2
from benchmark.families.gpt2 import (  # noqa: F401
    build_program_model, decode_step, make_weights, param_count, token_gaps,
    train_flops_per_token, train_readings)


def widths(config):
    return gpt2.widths({
        "vocab_size": config["vocab_size"],
        "n_positions": config["max_position_embeddings"],
        "n_embd": config["hidden_size"],
        "n_head": config["num_attention_heads"],
        "n_inner": config["intermediate_size"],
        "n_layer": config["num_hidden_layers"],
        "assumed": config["assumed"]})
'''


def as_throwaway(config: dict) -> dict:
    """``config`` with GPT-2's keys renamed to the throw-away family's, at
    the top level and wherever the file lists keys."""

    def rename(keys):
        if isinstance(keys, dict):
            return {THROWAWAY_KEYS.get(k, k): v for k, v in keys.items()}
        return [THROWAWAY_KEYS.get(k, k) for k in keys]

    out = {**rename(config), "family": "throwaway", "name": "throwaway"}
    for group in ("published", "reduced", "reduced_from"):
        if group in out:
            out[group] = rename(out[group])
    return out


THROWAWAY_CONFIG = as_throwaway(CONFIG)


def add_throwaway_family(root) -> dict:
    """What a later PR adds, written under ``root``: a directory of its
    own in ``paths`` with a family, a configuration of it (the committed
    training configuration under the other keys), and in BENCHMARK.json an
    entry each and a cell that reports what ``train_seq2048`` reports.
    Returns the benchmark it wrote."""
    extra = os.path.join(str(root), "extra_bench")
    for sub in ("families", "configs"):
        os.makedirs(os.path.join(extra, sub), exist_ok=True)
    with open(os.path.join(extra, "families", "throwaway.py"), "w") as f:
        f.write(THROWAWAY_FAMILY)
    with open(os.path.join(
            REPO, "benchmark/configs/cerebras-gpt-1.3b-cut.json")) as f:
        config = as_throwaway(json.load(f))
    with open(os.path.join(extra, "configs", "throwaway.json"), "w") as f:
        json.dump(config, f)
    bench = spec.load_benchmark(REPO)
    bench["paths"].append("extra_bench")
    bench["configs"].append({
        "name": "throwaway", "source": "https://example.org/x",
        "file": "extra_bench/configs/throwaway.json",
        "reduced": config["reduced"], "why": "test"})
    bench["workloads"].append({
        "name": "throwaway.pretrain_2k", "config": "throwaway",
        "traffic": "pretrain_2k", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "train_seq2048" in m.get("workloads", []):
            m["workloads"].append("throwaway.pretrain_2k")
    with open(os.path.join(str(root), "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return bench
