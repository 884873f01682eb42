"""Entry-point runtime configuration: the compile-cache placement and the
per-device peaks table the roofline reads."""
from pathlib import Path

import jax
import pytest

from benchmarks import roofline
from repro import compile_cache
from repro.launch import mesh


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_dir_from_environment_is_left_alone(monkeypatch,
                                                  restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    assert first == compile_cache.use_compile_cache()  # never moves
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.CACHE_DIR == Path(__file__).resolve().parents[1] \
        / ".jax_cache"


def test_v5e_peaks_are_the_published_ones():
    p = mesh.device_peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bw"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        mesh.device_peaks(kind)


def test_roofline_uses_the_table(monkeypatch):
    rows = roofline.gather_mix_rows(ms=(1024,))
    assert rows[0]["dense_s"] > 0
    monkeypatch.setattr(roofline, "TARGET_KIND", "cpu")
    with pytest.raises(KeyError):
        roofline.gather_mix_rows(ms=(1024,))
