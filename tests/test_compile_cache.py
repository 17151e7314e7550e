"""Compile plane unit tests (common/compile_cache.py): enablement
resolution, idempotence, and the timed_compile hit/miss telemetry."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.common import compile_cache
from analytics_zoo_tpu.metrics import (
    MetricsRegistry,
    set_registry,
    snapshot,
)


@pytest.fixture
def fresh_registry():
    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    try:
        yield reg
    finally:
        set_registry(prev)


@pytest.fixture(autouse=True)
def cache_teardown(monkeypatch):
    # these tests place the cache themselves: a directory given from
    # outside would win over every path they name
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        yield
    finally:
        compile_cache.disable_persistent_cache()


def _samples(reg, name):
    return [s for s in snapshot(reg)["samples"] if s["name"] == name]


def test_disabled_without_env_or_path(monkeypatch):
    monkeypatch.delenv("ZOO_COMPILE_CACHE", raising=False)
    assert compile_cache.maybe_enable_persistent_cache(None) is None
    assert compile_cache.cache_dir() is None


def test_enable_from_env_and_idempotence(tmp_path, monkeypatch):
    d = str(tmp_path / "cc")
    monkeypatch.setenv("ZOO_COMPILE_CACHE", d)
    got = compile_cache.maybe_enable_persistent_cache()
    assert got == os.path.abspath(d)
    assert os.path.isdir(d)
    # idempotent: re-enable with no path keeps the enabled dir
    monkeypatch.delenv("ZOO_COMPILE_CACHE")
    assert compile_cache.maybe_enable_persistent_cache() == got
    assert compile_cache.cache_dir() == got


def test_explicit_path_beats_env(tmp_path, monkeypatch):
    monkeypatch.setenv("ZOO_COMPILE_CACHE", str(tmp_path / "env"))
    explicit = str(tmp_path / "explicit")
    assert compile_cache.maybe_enable_persistent_cache(explicit) \
        == os.path.abspath(explicit)


def test_timed_compile_records_miss_then_hit(tmp_path, fresh_registry):
    """First compile of a program = miss (writes the cache entry); an
    identical re-lower+compile = hit (served from disk, no new entry).
    Both land in zoo_compile_seconds."""
    compile_cache.maybe_enable_persistent_cache(str(tmp_path / "cc"))

    def f(a, b):
        return jnp.tanh(a @ b).sum()

    args = (jnp.ones((8, 8)), jnp.ones((8, 8)))
    compile_cache.timed_compile(jax.jit(f).lower(*args), "probe")
    compile_cache.timed_compile(jax.jit(f).lower(*args), "probe")

    (hist,) = _samples(fresh_registry, "zoo_compile_seconds")
    assert hist["labels"] == {"label": "probe"}
    assert hist["count"] == 2
    hits = _samples(fresh_registry, "zoo_compile_cache_hits_total")
    misses = _samples(fresh_registry, "zoo_compile_cache_misses_total")
    assert sum(s["value"] for s in misses) == 1
    assert sum(s["value"] for s in hits) == 1


def test_timed_compile_without_cache_counts_misses(fresh_registry):
    """No persistent cache enabled: every AOT compile is a miss (and the
    executable still comes back usable)."""
    def g(a):
        return (a * 2.0).sum()

    exe = compile_cache.timed_compile(
        jax.jit(g).lower(jnp.ones((4,))), "nocache")
    assert float(exe(jnp.ones((4,)))) == 8.0
    hits = _samples(fresh_registry, "zoo_compile_cache_hits_total")
    misses = _samples(fresh_registry, "zoo_compile_cache_misses_total")
    assert sum(s["value"] for s in misses) == 1
    assert sum(s["value"] for s in hits) == 0


def test_zoo_config_resolves_dispatch_and_cache_knobs(monkeypatch):
    from analytics_zoo_tpu.common.engine import ZooConfig

    monkeypatch.setenv("ZOO_STEPS_PER_DISPATCH", "8")
    monkeypatch.setenv("ZOO_COMPILE_CACHE", "/tmp/zoo-cc-env")
    cfg = ZooConfig()
    assert cfg.steps_per_dispatch == 8
    assert cfg.compile_cache == "/tmp/zoo-cc-env"
    # explicit beats env (the documented precedence)
    cfg2 = ZooConfig(steps_per_dispatch=2, compile_cache="/tmp/other")
    assert cfg2.steps_per_dispatch == 2
    assert cfg2.compile_cache == "/tmp/other"
    monkeypatch.setenv("ZOO_STEPS_PER_DISPATCH", "0")
    with pytest.raises(ValueError):
        ZooConfig()
