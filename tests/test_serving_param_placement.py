"""Where the served weights live (PR 26): the stepper binds one
device-resident copy of the parameter tree when it is built, on the
unsharded path as on the sharded one, and every program call takes that
copy. A bundle's NumPy tree is placed once; a tree that is on the device
already is bound as it is; dropping ``stepper._params`` frees the copy.
"""

from __future__ import annotations

import gc

import numpy as np
import pytest
from test_faults import _wait
from test_serving_spans import _traced

PROMPTS = [(np.arange(3 + 2 * i, dtype=np.int32) * 7 + i) % 53 for i in range(4)]


def _lm(d_model=40):
    """Widths no other test file uses, so that a live array of a leaf's
    shape and dtype is one of this file's."""
    from distkeras_tpu.models import zoo

    return zoo.transformer_lm(vocab_size=53, seq_len=32, d_model=d_model,
                              num_heads=2, depth=2)


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def _matrices(tree):
    return [leaf for leaf in _leaves(tree) if getattr(leaf, "ndim", 0) >= 2]


def _signature(tree):
    return {(tuple(leaf.shape), np.dtype(leaf.dtype)) for leaf in _matrices(tree)}


def _live_like(signature, known=()):
    """Live device arrays with a matrix leaf's shape and dtype, those in
    ``known`` (by identity) left out."""
    import jax

    seen = {id(a) for a in known}
    return [a for a in jax.live_arrays()
            if id(a) not in seen and (tuple(a.shape), np.dtype(a.dtype)) in signature]


def _bundle(tmp_path, lm=None):
    from distkeras_tpu.ops.quantization import quantize_model
    from distkeras_tpu.utils.serialization import save_serving_bundle

    lm = lm or _lm()
    quantize_model(lm, bits=8)
    path = str(tmp_path / "bundle.dkt")
    save_serving_bundle(path, lm)
    return path


def _generate(engine, steps=6, **kw):
    reqs = [engine.submit(p, steps, **kw) for p in PROMPTS]
    return [list(r.result(120)) for r in reqs]


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_a_bundle_s_tree_is_placed_once_and_no_call_uploads_it(tmp_path, paged):
    import jax

    from distkeras_tpu.serving import ServingEngine

    kw = dict(paged=True, page_size=4) if paged else dict(paged=False)
    engine = ServingEngine.from_bundle(
        _bundle(tmp_path), num_slots=2, prefill_chunk=4, **kw)
    engine.start()
    try:
        stepper = engine._stepper
        host = _leaves(engine.model.params)
        assert host and all(isinstance(leaf, np.ndarray) for leaf in host)
        assert any(leaf.dtype == np.int8 for leaf in host)
        tree_bytes = sum(leaf.nbytes for leaf in host)
        placed = _leaves(stepper._params)
        assert len(placed) == len(host)
        assert all(isinstance(leaf, jax.Array) for leaf in placed)
        assert [(p.shape, p.dtype) for p in placed] == [(h.shape, h.dtype) for h in host]
        assert stepper._params_host_bytes == 0
        _generate(engine)  # compiles, off the traced drive
        _, plain = _traced(tmp_path / "trace", lambda: _generate(engine))
        spans = [s for s in plain["spans"]
                 if s[0] in ("serving/step", "serving/prefill_chunk")]
        assert {s[0] for s in spans} == {"serving/step", "serving/prefill_chunk"}
        # lens, mask, a page table and five sampler arrays for two slots; a
        # chunk's tokens, its table row and position
        assert all(0 < s[4]["host_arg_bytes"] <= 256 < tree_bytes for s in spans)
        if paged:
            assert engine.stats()["paged"]["host_arg_bytes_step"] <= 256
    finally:
        engine.stop()


@pytest.mark.parametrize("sampling", [None, {"temperature": 0.8, "seed": 9}],
                         ids=["greedy", "seeded"])
def test_host_leaves_and_device_leaves_serve_the_same_tokens(sampling):
    import jax

    from distkeras_tpu.serving import ServingEngine

    out = {}
    for where in ("host", "device"):
        lm = _lm()
        if where == "host":
            lm.params = jax.tree_util.tree_map(np.asarray, lm.params)
        engine = ServingEngine(lm, num_slots=2, paged=True, page_size=4,
                               prefill_chunk=4)
        engine.start()
        try:
            assert engine._stepper._params_host_bytes == 0
            out[where] = _generate(
                engine, 8, **({"sampling": sampling} if sampling else {}))
        finally:
            engine.stop()
    assert out["host"] == out["device"]
    assert all(len(seq) == len(p) + 8 for seq, p in zip(out["host"], PROMPTS))


def test_leaves_on_the_device_already_are_bound_as_they_are():
    import jax

    from distkeras_tpu.serving.engine import DecodeStepper, ModelDrafter

    lm, draft = _lm(), _lm(d_model=24)
    assert all(isinstance(leaf, jax.Array) for leaf in _leaves(lm.params))
    stepper = DecodeStepper(lm, num_slots=2, speculative=ModelDrafter(draft))
    for own, model in ((stepper, lm), (stepper.drafter._st, draft)):
        bound, handed = _leaves(own._params), _leaves(model.params)
        assert len(bound) == len(handed) > 0
        assert all(b is h for b, h in zip(bound, handed))
        assert own._params_host_bytes == 0


def test_dropping_the_stepper_s_tree_frees_the_device_copy(tmp_path):
    """What ``benchmark/drive_serve.release`` does to a stopped engine
    before it builds the float32 reference: nothing else may hold the
    placed tree."""
    import jax

    from distkeras_tpu.serving import ServingEngine

    known = list(jax.live_arrays())
    engine = ServingEngine.from_bundle(
        _bundle(tmp_path), num_slots=2, paged=True, page_size=4, prefill_chunk=4)
    engine.start()
    _generate(engine)
    engine.stop()
    stepper = engine._stepper
    signature = _signature(stepper._params)
    assert len(_live_like(signature, known)) == len(_matrices(stepper._params)) > 0
    for name in ("_pools", "_caches", "_params", "_ctx"):
        if hasattr(stepper, name):
            setattr(stepper, name, None)
    engine.model.params = None
    assert stepper._params is None and stepper._params_host_bytes == 0
    gc.collect()
    assert _live_like(signature, known) == []


def test_a_rebuilt_stepper_places_the_one_copy_there_is(tmp_path):
    import jax

    from distkeras_tpu.faults import FaultPlan
    from distkeras_tpu.serving import InternalError, ServingEngine

    known = list(jax.live_arrays())
    engine = ServingEngine.from_bundle(
        _bundle(tmp_path), num_slots=2, prefix_cache=False,
        watchdog_interval=0.3, watchdog_grace=30.0, max_restarts=3,
        restart_backoff=0.01)
    engine.start()
    first = engine._stepper
    signature = _signature(first._params)
    n_matrices = len(_matrices(first._params))
    plan = (
        FaultPlan()
        .arm("stepper.step", action="delay", delay=0.02, times=None)
        .arm("scheduler.loop", times=1, after=5, when=lambda ctx: ctx["busy"])
    )
    try:
        before = _generate(engine)
        assert len(_live_like(signature, known)) == n_matrices
        with plan:
            inflight = engine.submit(PROMPTS[0], 20)
            with pytest.raises(InternalError):
                inflight.result(timeout=30)
            _wait(lambda: engine.health()["restarts"] == 1
                  and engine.health()["status"] == "serving",
                  timeout=60.0, msg="supervisor restart")
        rebuilt = engine._stepper
        assert rebuilt is not first and first._params is None
        assert rebuilt._params_host_bytes == 0
        assert all(isinstance(leaf, jax.Array) for leaf in _leaves(rebuilt._params))
        assert all(isinstance(leaf, np.ndarray) for leaf in _leaves(engine.model.params))
        del inflight
        gc.collect()
        assert len(_live_like(signature, known)) == n_matrices
        assert _generate(engine) == before
    finally:
        engine.stop()


def test_the_mesh_branch_binds_what_shard_decode_params_returns(monkeypatch):
    from distkeras_tpu.parallel import tensor_parallel
    from distkeras_tpu.serving.engine import DecodeStepper

    returned = []
    real = tensor_parallel.shard_decode_params

    def recording(params, mesh):
        returned.append(real(params, mesh))
        return returned[-1]

    monkeypatch.setattr(tensor_parallel, "shard_decode_params", recording)
    lm = _lm()
    handed = _leaves(lm.params)
    stepper = DecodeStepper(lm, num_slots=2, mesh="tp:2")
    assert len(returned) == 1 and stepper._params is returned[0]
    assert stepper._params_host_bytes == 0
    assert all(len(leaf.sharding.device_set) == 2 for leaf in _leaves(stepper._params))
    assert all(a is b for a, b in zip(_leaves(lm.params), handed))
