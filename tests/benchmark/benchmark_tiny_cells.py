"""Tiny cells for the benchmark's CPU tests: the drivers' own code paths at
sizes a test run can hold. Not a benchmark configuration."""

import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONFIG = {
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


def cell(traffic: dict, chips: int = 1, root: str = "/tmp") -> dict:
    return {"root": root, "config": CONFIG, "traffic": traffic,
            "cell": {"chips": chips}}


def args(seed: int = 2**31 + 77, seconds: float = 0.6, trace: int = 0):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)


def train_windowed(window: int) -> dict:
    """The training mix with ``window`` steps a call, checked after one
    window (or three calls at window 1)."""
    steps = 3 if window == 1 else window
    return {**TRAIN, "window": window,
            "check": {**TRAIN["check"], "steps": steps}}
