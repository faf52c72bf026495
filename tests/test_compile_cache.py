"""Where the compile cache goes (``utils.compile_cache``) and what the
backend helper does when the chip was asked for and is not there."""

import os

import jax
import pytest

from distkeras_tpu.parallel.backend import setup_backend
from distkeras_tpu.utils import compile_cache


@pytest.fixture
def cache_config():
    """Restore the JAX cache settings the helper touches."""
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs",
    )
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_variable_set_means_no_directory_set_in_code(
    monkeypatch, tmp_path, cache_config
):
    outside = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache(platform="tpu") == outside
    # JAX reads the variable itself; the helper left the setting alone
    # and made no directory of its own
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(outside)


def test_variable_unset_means_checkout_dot_jax_cache(
    monkeypatch, tmp_path, cache_config
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.DEFAULT_DIR == os.path.join(root, ".jax_cache")
    # the real default is fixed; the write goes to a stand-in here
    stand_in = str(tmp_path / ".jax_cache")
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", stand_in)
    assert compile_cache.enable_compile_cache(platform="tpu") == stand_in
    assert jax.config.jax_compilation_cache_dir == stand_in
    assert os.path.isdir(stand_in)


@pytest.mark.parametrize("platform", ["cpu", None])
def test_cache_stays_off_for_an_asked_for_cpu_run(
    monkeypatch, tmp_path, cache_config, platform
):
    # platform=None asks JAX, which is the CPU mesh under the tests
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache(platform=platform) is None
    assert jax.config.jax_compilation_cache_dir == before


def test_backend_raises_when_the_chip_was_asked_for_and_is_not_there():
    # the tests' backend is the CPU mesh: asking for the chip names what
    # was found instead, and never returns "cpu"
    with pytest.raises(RuntimeError, match="cpu"):
        setup_backend()


def test_backend_returns_cpu_only_when_cpu_was_asked_for():
    assert setup_backend(cpu=True, cpu_devices=8) == "cpu"
    assert len(jax.devices()) == 8
