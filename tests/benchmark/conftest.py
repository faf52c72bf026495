"""``test_benchmark_spec``'s synthetic traced run hands the readers a reduced
trace with round numbers and has written no profile; the metrics that read
the program's own spans (PR 25) take theirs from the recorded fixture there."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

PROGRAM_SPANS = os.path.join(HERE, "fixtures", "program_spans_small.json")


@pytest.fixture(autouse=True)
def _synthetic_run_has_program_spans(request, monkeypatch):
    if request.module.__name__ == "test_benchmark_spec":
        from benchmark.layer_metrics import _program_spans

        with open(PROGRAM_SPANS) as f:
            plain = json.load(f)
        monkeypatch.setattr(_program_spans, "run_profile", lambda: plain)
