"""Test bootstrap: force an 8-device CPU platform (SURVEY §7.4).

Multi-device code paths (mesh, sync allreduce, per-device async workers) are
exercised on CPU via ``--xla_force_host_platform_device_count=8``. Must run
before any JAX backend initialization.
"""

from distkeras_tpu.parallel.mesh import force_cpu_mesh

force_cpu_mesh(8)

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Unit tier first, harness tier last — deterministically.

    ``chaos`` (subprocess fleets, seeded fault storms) and ``e2e``
    (full bench-harness runs) tests each cost tens of seconds to
    minutes on this 1-core sandbox; alphabetical collection buries
    them mid-suite where they starve hundreds of sub-second unit
    tests behind them. A stable two-bucket sort keeps every test
    selected and every relative order intact, but a time-boxed or
    interrupted run now drains the whole unit tier before the first
    multi-minute smoke starts — fast, broad signal first."""
    items.sort(key=lambda it: int(
        it.get_closest_marker("chaos") is not None
        or it.get_closest_marker("e2e") is not None
    ))


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    assert len(jax.devices()) == 8, "tests expect 8 virtual CPU devices"


@pytest.fixture(scope="session")
def cpu_devices():
    """The 8-virtual-device topology, as a fixture: serving/parallel
    tests that need devices take this instead of re-rolling
    ``jax.devices()`` behind their own ad-hoc setup — the dependency
    makes the required topology explicit in each test's signature."""
    return jax.devices()


@pytest.fixture(scope="session")
def tp_mesh(cpu_devices):
    """Factory for serving tensor-parallel meshes on the shared CPU
    topology: ``tp_mesh(2)`` -> the 2-way ``serving_mesh`` every
    sharded-serving test (and the decode bench) uses."""
    from distkeras_tpu.parallel.mesh import serving_mesh

    def make(n: int):
        return serving_mesh(f"tp:{n}", devices=cpu_devices)

    return make
