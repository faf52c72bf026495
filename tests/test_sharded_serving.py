"""Tensor-parallel serving pins: sharded decode == solo decode.

THE correctness bar, inherited from the paged PR's harness
(``test_paged_serving.py``): a ``DecodeStepper(mesh="tp:N")`` slot's
stream equals its solo single-device decode token for token, on EVERY
admission path — fresh, chunked prefill, device-prefix hit, host-ladder
restore, CoW fork (n-parallel sampling), speculative verify, and a QoS
preempt/swap-out/swap-in round trip — greedy AND sampled, on the
8-virtual-device CPU mesh the training tests use. Plus the geometry
surfaces: loud head-divisibility validation at bundle load, mesh shape
on ``health``/``stats``/the fleet replica books, and the
``serving_mesh_devices`` / ``serving_kv_shard_bytes`` gauges.
"""

import numpy as np
import pytest

from distkeras_tpu.serving import PrefixStore, ServingEngine
from distkeras_tpu.serving.engine import DecodeStepper, NgramDrafter
from distkeras_tpu.serving.sampling import SamplingParams


@pytest.fixture(scope="module")
def lm():
    from distkeras_tpu.models import zoo

    return zoo.transformer_lm(
        vocab_size=61, seq_len=32, d_model=32, num_heads=2, depth=2,
        seed=0,
    )


@pytest.fixture(scope="module")
def lm4h():
    """A 4-head model: the widest mesh tp:2's heads allow is 2, and
    the tp:4 pins need a head count 4 divides."""
    from distkeras_tpu.models import zoo

    return zoo.transformer_lm(
        vocab_size=61, seq_len=32, d_model=32, num_heads=4, depth=2,
        seed=1,
    )


@pytest.fixture(scope="module")
def lm_ref(lm):
    from distkeras_tpu.predictors import CachedSequenceGenerator

    return CachedSequenceGenerator(lm)


def _solo(lm_ref, p, s):
    return lm_ref.generate(p[None], steps=s)[0][len(p):].tolist()


def _decode_slot(st, slot, steps):
    out = []
    for _ in range(steps):
        active = np.zeros(st.num_slots, bool)
        active[slot] = True
        out.append(int(st.step(active)[slot]))
    return out


# --------------------------------------------- mesh construction helper


def test_serving_mesh_helper(tp_mesh, cpu_devices):
    from jax.sharding import Mesh

    from distkeras_tpu.parallel.mesh import serving_mesh

    m = serving_mesh("tp:4")
    assert isinstance(m, Mesh) and m.shape == {"model": 4}
    assert serving_mesh(2).shape == {"model": 2}
    assert serving_mesh(m) is m  # passthrough
    assert tp_mesh(2).shape == {"model": 2}  # the shared fixture
    with pytest.raises(ValueError, match="needs 16 devices"):
        serving_mesh("tp:16")
    with pytest.raises(ValueError, match="unrecognized"):
        serving_mesh("dp:2")
    with pytest.raises(ValueError, match="unrecognized"):
        serving_mesh("tp:")
    with pytest.raises(ValueError, match=">= 1"):
        serving_mesh(0)
    with pytest.raises(ValueError, match="'model' axis"):
        from distkeras_tpu.parallel.mesh import make_mesh

        serving_mesh(make_mesh(2, axis_names=("data",)))
    # explicit device list caps the pool
    with pytest.raises(ValueError, match="only 2"):
        serving_mesh("tp:4", devices=cpu_devices[:2])


def test_decode_param_specs_megatron_pairing(lm, tp_mesh):
    from jax.sharding import PartitionSpec as P

    from distkeras_tpu.parallel.tensor_parallel import (
        describe_decode_shardings,
    )

    d = describe_decode_shardings(lm.params, tp_mesh(2))
    assert d["1/mhsa/wq"] == P(None, "model")  # head- (column-) sharded
    assert d["1/mhsa/wk"] == P(None, "model")
    assert d["1/mhsa/wv"] == P(None, "model")
    assert d["1/mhsa/wo"] == P("model", None)  # row: one psum per pair
    assert d["1/mhsa/bo"] == P()
    assert d["1/fc1/kernel"] == P(None, "model")
    assert d["1/fc1/bias"] == P("model")
    assert d["1/fc2/kernel"] == P("model", None)
    assert d["1/fc2/bias"] == P()
    assert d["0/tokens"] == P()  # embeddings / LN / head replicated
    assert d["3/gamma"] == P()
    assert d["4/kernel"] == P()


def test_decode_param_specs_quantized(lm, tp_mesh):
    from jax.sharding import PartitionSpec as P

    from distkeras_tpu.ops.quantization import quantize_model
    from distkeras_tpu.parallel.tensor_parallel import (
        describe_decode_shardings,
    )

    d = describe_decode_shardings(
        quantize_model(lm.copy()).params, tp_mesh(2)
    )
    # int8 groups shard q like the f32 matrix; per-output-column
    # scales follow a column shard, replicate under a row shard
    assert d["1/mhsa/wq/q"] == P(None, "model")
    assert d["1/mhsa/wq/s"] == P("model")
    assert d["1/mhsa/wo/q"] == P("model", None)
    assert d["1/mhsa/wo/s"] == P()
    # packed int4 replicates (stated in _pair_specs)
    d4 = describe_decode_shardings(
        quantize_model(lm.copy(), bits=4).params, tp_mesh(2)
    )
    assert d4["1/mhsa/wq"] == P()


def test_heads_divisibility_is_loud_at_load(lm):
    with pytest.raises(ValueError, match="cannot shard 2 K/V heads"):
        DecodeStepper(lm, num_slots=2, mesh="tp:4")
    # the ENGINE must fail the boot too, never demote to predict-only
    with pytest.raises(ValueError, match="cannot shard 2 K/V heads"):
        ServingEngine(lm, num_slots=2, mesh="tp:4")
    with pytest.raises(ValueError, match="needs 16 devices"):
        ServingEngine(lm, num_slots=2, mesh="tp:16")


def test_mesh_none_is_bit_for_bit_unchanged(lm):
    import jax

    st = DecodeStepper(lm, num_slots=2)
    assert st.mesh is None and st.mesh_spec is None
    assert st.mesh_devices == 1
    # leaves on the device already are bound as they are: no copy ran
    bound = jax.tree_util.tree_leaves(st._params)
    assert bound and all(
        a is b for a, b in zip(bound, jax.tree_util.tree_leaves(lm.params))
    )


# --------------------------------------------- identity: every path


def test_sharded_fresh_and_chunked_matches_solo(lm, lm_ref):
    """Fresh one-shot admission AND chunked prefill, dense and paged,
    tp:2 — greedy streams pinned to solo."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 61, 19).astype(np.int32)
    short = rng.integers(0, 61, 5).astype(np.int32)
    ref = _solo(lm_ref, prompt, 6)
    ref_short = _solo(lm_ref, short, 6)
    for paged in (False, True):
        st = DecodeStepper(
            lm, num_slots=2, mesh="tp:2", prefix_cache=None,
            **(dict(paged=True, page_size=4) if paged else {}),
        )
        assert st.mesh_spec == "tp:2"
        st.admit(0, short, max_new=6)  # fresh, one-shot
        left = st.begin_admit(1, prompt, max_new=6)  # chunked
        while left:
            left = st.prefill_chunk(1, 5)
        active = np.ones(2, bool)
        g0, g1 = [], []
        for _ in range(6):
            t = st.step(active)
            g0.append(int(t[0]))
            g1.append(int(t[1]))
        assert g0 == ref_short, f"paged={paged}"
        assert g1 == ref, f"paged={paged}"


def test_sharded_sampled_matches_solo_sampled(lm):
    """The sampled identity reference (PR 10): same (prompt, params,
    seed) on a solo stepper and a tp:2 stepper emit the same stream —
    dense and paged."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 61, 8).astype(np.int32)
    sp = SamplingParams(temperature=0.8, top_k=9, seed=13)
    for paged in (False, True):
        kw = dict(paged=True, page_size=4) if paged else {}
        want = None
        for mesh in (None, "tp:2"):
            st = DecodeStepper(
                lm, num_slots=2, mesh=mesh, prefix_cache=None, **kw
            )
            st.admit(0, prompt, max_new=8, sampling=sp)
            got = _decode_slot(st, 0, 8)
            if want is None:
                want = got
            else:
                assert got == want, f"paged={paged}"


def test_sharded_device_prefix_hit_matches_solo(lm, lm_ref):
    """Two prompts sharing a long header on a tp:2 paged stepper: the
    second admission SHARES the header's pages (host-side refcount,
    geometry-oblivious) and decodes token-identical to solo."""
    st = DecodeStepper(lm, num_slots=3, mesh="tp:2", paged=True,
                       page_size=4, prefix_cache=None)
    rng = np.random.default_rng(8)
    header = rng.integers(0, 61, 17).astype(np.int32)
    st.admit(0, header, max_new=6)
    assert _decode_slot(st, 0, 6) == _solo(lm_ref, header, 6)
    ext = np.concatenate(
        [header, rng.integers(0, 61, 5).astype(np.int32)]
    )
    left = st.begin_admit(1, ext, max_new=6)
    assert st.prefix_index.stats()["hits"] == 1
    assert left == (ext.size - 1) - 16  # 4 full pages skipped
    assert st._kv_alloc.shared_pages >= 4
    while left:
        left = st.prefill_chunk(1, 4)
    assert _decode_slot(st, 1, 6) == _solo(lm_ref, ext, 6)


def test_host_ladder_restore_crosses_geometries(lm, lm_ref):
    """The ``PrefixStore`` row format is the gathered full-head layout:
    an entry WRITTEN by a solo stepper restores bit-exactly into a
    tp:2 stepper (and the restored stream matches solo decode) — the
    fleet serialization path is mesh-oblivious."""
    store = PrefixStore(max_bytes=8 << 20)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 61, 17).astype(np.int32)
    ref = _solo(lm_ref, prompt, 6)
    solo = DecodeStepper(lm, num_slots=1, paged=True, page_size=4,
                         prefix_cache=store)
    solo.admit(0, prompt, max_new=6)  # miss 1 (ghost rung)
    solo.release(0)
    solo.prefix_index.clear()
    solo.admit(0, prompt, max_new=6)  # miss 2: ladder stored
    solo.release(0)
    assert store.stats()["entries"] >= 1
    st = DecodeStepper(lm, num_slots=2, mesh="tp:2", paged=True,
                       page_size=4, prefix_cache=store)
    st.prefix_index.clear()  # force the HOST ladder path
    left = st.begin_admit(1, prompt, max_new=6)
    assert store.stats()["hits"] >= 1
    assert left < prompt.size - 1  # the rung skipped real prefill
    while left:
        left = st.prefill_chunk(1, 4)
    assert _decode_slot(st, 1, 6) == ref


def test_sharded_fork_n_parallel_sampled(lm):
    """CoW fork on a tp:2 paged stepper: each forked completion's
    sampled stream equals an INDEPENDENT solo admission under the
    derived completion seed (the PR 10 n-parallel contract), and the
    fork shared pages instead of copying the cache."""
    from distkeras_tpu.serving.sampling import seed_for_completion

    rng = np.random.default_rng(10)
    prompt = rng.integers(0, 61, 9).astype(np.int32)
    sp = SamplingParams(temperature=0.9, seed=31)
    # solo references: completion c == a fresh solo admission with the
    # derived seed
    want = []
    for c in range(3):
        solo = DecodeStepper(lm, num_slots=1, prefix_cache=None)
        solo.admit(
            0, prompt, max_new=8,
            sampling=SamplingParams(
                temperature=0.9, seed=seed_for_completion(31, c)
            ),
        )
        want.append(_decode_slot(solo, 0, 8))
    st = DecodeStepper(lm, num_slots=3, mesh="tp:2", paged=True,
                       page_size=4, prefix_cache=None)
    st.admit(0, prompt, max_new=9, sampling=sp)
    st.fork_slot(0, 1, max_new=8, completion=1)
    st.fork_slot(0, 2, max_new=8, completion=2)
    assert st._kv_alloc.shared_pages >= 2
    active = np.ones(3, bool)
    got = [[], [], []]
    for _ in range(8):
        t = st.step(active)
        for i in range(3):
            got[i].append(int(t[i]))
    assert got == want


def test_sharded_speculative_verify_matches_solo(lm, lm_ref):
    """The paged verify program over a tp:2 mesh: repetitive traffic
    (proposals fire) and incompressible traffic both stay pinned to
    solo greedy decode; a SAMPLED spec stream matches the solo spec
    stepper's (rejection sampling is deterministic per seed)."""
    def spec_drive(st, prompts, params, steps):
        for slot, p in enumerate(prompts):
            st.admit(slot, p, max_new=steps,
                     sampling=params[slot])
        outs = [[] for _ in prompts]
        live = set(range(len(prompts)))
        while live:
            active = np.zeros(st.num_slots, bool)
            active[list(live)] = True
            seqs = [
                (prompts[i], outs[i]) if i in live else None
                for i in range(st.num_slots)
            ]
            toks, counts, _ = st.spec_step(active, seqs)
            for i in list(live):
                for t in np.atleast_1d(toks[i])[: int(counts[i])]:
                    outs[i].append(int(t))
                    if len(outs[i]) == steps:
                        live.discard(i)
                        st.release(i)
                        break
        return outs

    rng = np.random.default_rng(12)
    prompts = [
        # period 5 over 14 tokens: the closing bigram has recurred, so
        # the n-gram drafter proposes at once (a period of 13 recurs
        # only in its last token, and no proposal ever fires)
        ((7 + np.arange(14)) % 5).astype(np.int32),  # repetitive
        rng.integers(0, 61, 9).astype(np.int32),  # incompressible
    ]
    params = [None, SamplingParams(temperature=0.8, seed=5)]
    solo = DecodeStepper(lm, num_slots=2, paged=True, page_size=4,
                         speculative=NgramDrafter(), draft_k=3,
                         prefix_cache=None)
    want = spec_drive(solo, prompts, params, 8)
    assert want[0] == _solo(lm_ref, prompts[0], 8)  # greedy pin
    st = DecodeStepper(lm, num_slots=2, mesh="tp:2", paged=True,
                       page_size=4, speculative=NgramDrafter(),
                       draft_k=3, prefix_cache=None)
    got = spec_drive(st, prompts, params, 8)
    assert got == want
    assert solo.spec_verify_steps > 0  # the traffic does propose
    assert st.spec_verify_steps > 0  # the sharded verify actually ran
    assert st.spec_draft_failures == 0


def test_sharded_swap_roundtrip_matches_solo(lm, lm_ref):
    """The QoS preemption seam on a tp:2 paged stepper: decode, swap
    OUT (host serialization gathers the shards), release, swap IN to a
    different slot — the resumed stream continues exactly where an
    uninterrupted solo decode would be, greedy AND sampled."""
    rng = np.random.default_rng(14)
    prompt = rng.integers(0, 61, 9).astype(np.int32)
    cases = [
        (None, _solo(lm_ref, prompt, 8)),
    ]
    sp = SamplingParams(temperature=0.8, seed=23)
    solo = DecodeStepper(lm, num_slots=1, prefix_cache=None)
    solo.admit(0, prompt, max_new=8, sampling=sp)
    cases.append((sp, _decode_slot(solo, 0, 8)))
    for sampling, want in cases:
        st = DecodeStepper(lm, num_slots=2, mesh="tp:2", paged=True,
                           page_size=4, prefix_cache=None)
        st.admit(0, prompt, max_new=8, sampling=sampling)
        head = _decode_slot(st, 0, 3)
        state = st.swap_out(0)
        st.release(0)
        st.swap_in(1, state, max_new=5)
        tail = _decode_slot(st, 1, 5)
        assert head + tail == want, f"sampling={sampling}"


def test_engine_qos_preemption_under_mesh(lm, lm_ref):
    """Engine-level preempt-by-swap on a sharded engine: a tight pool
    plus a high-priority arrival preempts the low-priority stream; both
    complete token-identical to solo."""
    from distkeras_tpu.serving import QosPolicy

    rng = np.random.default_rng(15)
    lo_p = rng.integers(0, 61, 9).astype(np.int32)
    hi_p = rng.integers(0, 61, 7).astype(np.int32)
    eng = ServingEngine(
        lm, num_slots=2, mesh="tp:2", paged=True, page_size=4,
        num_pages=8, prefix_cache=False, queue_capacity=8,
        qos=QosPolicy(preempt=True, max_preemptions=2),
        watchdog_interval=30.0,
    ).start()
    try:
        lo = eng.submit(lo_p, 8, tenant="lo", priority=0)
        # let lo admit and start decoding before the preemptor arrives
        import time

        for _ in range(200):
            if eng.batcher.stats()["active_slots"]:
                break
            time.sleep(0.01)
        hi = eng.submit(hi_p, 4, tenant="hi", priority=2)
        out_lo = eng.wait(lo, 120)
        out_hi = eng.wait(hi, 120)
        np.testing.assert_array_equal(
            out_lo, lm_ref.generate(lo_p[None], steps=8)[0]
        )
        np.testing.assert_array_equal(
            out_hi, lm_ref.generate(hi_p[None], steps=4)[0]
        )
        s = eng.stats()
        assert s["preemptions"] >= 0  # tight-pool path exercised
    finally:
        eng.stop()


# --------------------------------------------- tp:4 + observability


def test_tp4_engine_every_admission_path(lm4h):
    """The acceptance row: ``ServingEngine(mesh="tp:4")`` on the 4-head
    model serves greedy, sampled, and an n=2 fork group — all
    token-identical to the solo engine's outputs — and the geometry
    rides health/stats/metrics."""
    rng = np.random.default_rng(16)
    reqs = [
        (rng.integers(0, 61, 7).astype(np.int32), 6, None),
        (rng.integers(0, 61, 11).astype(np.int32), 5,
         SamplingParams(temperature=0.8, seed=41)),
        (rng.integers(0, 61, 6).astype(np.int32), 5,
         SamplingParams(temperature=0.9, seed=42, n=2)),
    ]

    def run(mesh):
        eng = ServingEngine(
            lm4h, num_slots=4, mesh=mesh, paged=True, page_size=4,
            prefix_cache=False, watchdog_interval=30.0,
        ).start()
        try:
            outs = [
                eng.generate(p, s, sampling=sp) for p, s, sp in reqs
            ]
            return outs, eng.health(), eng.stats(), {
                s["name"]: s["value"]
                for s in eng.metrics_snapshot()
                if s["kind"] == "gauge"
            }
        finally:
            eng.stop()

    want, h0, st0, _ = run(None)
    got, h4, st4, gauges = run("tp:4")
    for w, g, (p, s, sp) in zip(want, got, reqs):
        if isinstance(w, list):
            assert len(w) == len(g)
            for a, b in zip(w, g):
                np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_array_equal(w, g)
    # geometry surfaces
    assert h0["mesh"] is None and h4["mesh"] == "tp:4"
    assert h4["kv_shard_bytes"] * 4 == st4["paged"]["kv_bytes_total"]
    assert st4["paged"]["mesh"] == "tp:4"
    assert st4["mesh"] == "tp:4" and st0["mesh"] is None
    # equal total KV bytes across geometries at the same config
    assert st4["paged"]["kv_bytes_total"] == st0["paged"]["kv_bytes_total"]
    assert gauges["serving_mesh_devices"] == 4
    assert gauges["serving_kv_shard_bytes"] == h4["kv_shard_bytes"]


def test_fleet_replica_books_carry_mesh():
    from distkeras_tpu.serving.fleet import _Replica

    r = _Replica(("127.0.0.1", 9001))
    assert r.snapshot()["mesh"] is None  # no health seen yet
    r.last_health = {"status": "serving", "mesh": "tp:2",
                     "num_slots": 4, "queue_capacity": 8}
    assert r.snapshot()["mesh"] == "tp:2"


def test_dkt_top_renders_mesh_column():
    import sys

    sys.path.insert(0, "tools")
    from dkt_top import format_table

    samples = [
        {"name": "serving_mesh_devices", "kind": "gauge", "value": 4,
         "labels": {"replica": "127.0.0.1:9001"}},
        {"name": "serving_mesh_devices", "kind": "gauge", "value": 1,
         "labels": {"replica": "127.0.0.1:9002"}},
    ]
    out = format_table(samples)
    assert "== 127.0.0.1:9001  mesh=tp:4 " in out
    assert "== 127.0.0.1:9002  mesh=solo " in out


@pytest.mark.parametrize("mesh", [None, "tp:2"], ids=["solo", "tp2"])
def test_swallowed_draft_failures_are_counted_in_health(lm, lm_ref, mesh):
    """A drafter that raises never fails the request — decode falls to
    the plain step and the tokens still match — so the only trace is
    the counter: ``health()`` and ``stats()`` must show it."""
    from distkeras_tpu.serving import ServingEngine

    class RaisingDrafter(NgramDrafter):
        def propose(self, active, k, seqs):
            raise RuntimeError("draft source down")

    eng = ServingEngine(
        lm, num_slots=2, paged=True, page_size=4, prefix_cache=None,
        speculative=RaisingDrafter(), draft_k=3, mesh=mesh,
    ).start()
    try:
        p = ((7 + np.arange(14)) % 5).astype(np.int32)
        out = eng.generate(p, 6)
        assert out[len(p):].tolist() == _solo(lm_ref, p, 6)
        health, spec = eng.health(), eng.stats()["speculative"]
    finally:
        eng.stop()
    assert health["speculative_draft_failures"] >= 1
    assert spec["draft_failures"] == health["speculative_draft_failures"]
    assert spec["verify_steps"] == 0 and spec["fallback_steps"] >= 1


# ------------------------------------- a full bank on every mesh size


@pytest.fixture(scope="module")
def grid():
    """A model wide enough to shard four ways (128 wide, 4 heads, 2
    layers), a bank of two slots and a 16-token prompt for each."""
    from distkeras_tpu.models import zoo
    from distkeras_tpu.predictors import CachedSequenceGenerator

    model = zoo.transformer_lm(
        vocab_size=512, seq_len=64, d_model=128, num_heads=4, depth=2,
        seed=0,
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, 16).astype(np.int32) for _ in range(2)]
    gen = CachedSequenceGenerator(model)
    refs = [gen.generate(p[None], steps=8)[0][16:].tolist() for p in prompts]
    return model, prompts, refs


def _grid_stepper(model, ways):
    return DecodeStepper(
        model, num_slots=2, paged=True, page_size=16, prefix_cache=None,
        mesh=None if ways == 1 else f"tp:{ways}",
    )


@pytest.mark.parametrize("ways", [2, 4])
def test_a_full_bank_decodes_the_solo_generator_s_tokens(grid, ways):
    """Every slot admitted and all decoding at once, the pool sharded by
    heads over the mesh: each slot's tokens are the solo generator's."""
    model, prompts, refs = grid
    st = _grid_stepper(model, ways)
    for slot, p in enumerate(prompts):
        st.admit(slot, p, max_new=9)
    outs = [[] for _ in prompts]
    for _ in range(8):
        toks = st.step(np.ones(len(prompts), bool))
        for slot, out in enumerate(outs):
            out.append(int(toks[slot]))
    assert outs == refs


def test_the_pool_s_bytes_are_the_same_on_every_mesh_size(grid):
    """The mesh changes how many bytes land on a shard and never how many
    there are: what makes one mesh size comparable with another."""
    model = grid[0]
    steppers = {ways: _grid_stepper(model, ways) for ways in (1, 2, 4)}
    total = steppers[1].kv_bytes_total()
    for ways, st in steppers.items():
        assert st.kv_bytes_total() == total, ways
        assert st.kv_shard_bytes() * ways == total, ways
