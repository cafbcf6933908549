"""Encoder "auto" resolution: decided once from what jax reports.

On a JAX-installed box WITHOUT a TPU (this test environment — conftest
pins JAX to the CPU platform), "auto" must resolve to the native C++
SIMD backend, not the slower XLA bit-plane path, and Client's default
must follow it instead of hardcoding the numpy golden path. Where jax
reports an accelerator the device backend is built and any failure
building it propagates: nothing degrades to a CPU backend.
"""

import os

import numpy as np
import pytest

from lizardfs_tpu.core import encoder as enc_mod
from lizardfs_tpu.core import native
from lizardfs_tpu.core.encoder import TpuChunkEncoder, get_encoder
from lizardfs_tpu.runtime import jaxcache


def _jax_is_cpu_only() -> bool:
    import jax

    return all(d.platform == "cpu" for d in jax.devices())


def test_tpu_encoder_refuses_cpu_platform(monkeypatch):
    monkeypatch.delenv("LZ_TPU_ALLOW_CPU", raising=False)
    assert _jax_is_cpu_only(), "test box must be a JAX-without-TPU box"
    with pytest.raises(RuntimeError, match="CPU-platform"):
        TpuChunkEncoder()
    # explicit forcing still works (numerics tests, operators who mean it)
    enc = TpuChunkEncoder(force_cpu=True)
    rng = np.random.default_rng(0)
    data = [rng.integers(0, 256, 256, dtype=np.uint8) for _ in range(3)]
    assert len(enc.encode(3, 2, data)) == 2
    # env escape hatch
    monkeypatch.setenv("LZ_TPU_ALLOW_CPU", "1")
    TpuChunkEncoder()


def test_auto_ladder_degrades_to_cpp(monkeypatch):
    """JAX-without-TPU box => auto = cpp (the pin the satellite asks
    for). With the native .so absent it would degrade to cpu."""
    monkeypatch.delenv("LZ_TPU_ALLOW_CPU", raising=False)
    monkeypatch.delenv("LIZARDFS_TPU_ENCODER", raising=False)
    assert _jax_is_cpu_only()
    e = get_encoder("auto")
    if native.available():
        assert e.name == "cpp", (
            "auto selected the XLA-on-CPU path on a box without silicon"
        )
    else:
        assert e.name == "cpu"


def test_client_defaults_to_auto_ladder(monkeypatch):
    monkeypatch.delenv("LIZARDFS_TPU_ENCODER", raising=False)
    from lizardfs_tpu.client.client import Client

    c = Client("127.0.0.1", 1)  # never connected; just the constructor
    assert c.encoder.name == get_encoder("auto").name
    if native.available():
        assert c.encoder.name == "cpp"  # not the numpy golden default


def test_env_override_still_wins(monkeypatch):
    monkeypatch.setenv("LIZARDFS_TPU_ENCODER", "cpu")
    assert get_encoder(None).name == "cpu"


class _FakeTpu:
    platform = "tpu"
    device_kind = "fake v5e"


def test_auto_raises_when_visible_accelerator_fails_to_build(monkeypatch):
    """jax reports an accelerator but the device encoder cannot be
    built: the error reaches the caller; auto does not become cpp."""
    import jax

    monkeypatch.delenv("LZ_TPU_ALLOW_CPU", raising=False)
    monkeypatch.setattr(enc_mod, "_ENCODERS", {})
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])

    def broken(self, *a, **kw):
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(TpuChunkEncoder, "__init__", broken)
    with pytest.raises(RuntimeError, match="mosaic says no"):
        get_encoder("auto")
    assert "auto" not in enc_mod._ENCODERS
    # two or more devices: the mesh backend is the one asked for
    monkeypatch.setattr(
        jax, "devices", lambda *a: [_FakeTpu(), _FakeTpu()]
    )
    assert enc_mod._resolve_auto() == "sharded"


def test_auto_names_a_backend_that_would_not_start(monkeypatch):
    """A platform list whose backend fails to start (a chip another
    process holds) is an error that says so, not a CPU backend."""
    import jax

    monkeypatch.setattr(enc_mod, "_ENCODERS", {})

    def held(*a):
        raise RuntimeError("Unable to initialize backend 'tpu': ABORTED")

    monkeypatch.setattr(jax, "devices", held)
    with pytest.raises(RuntimeError, match="One process owns a chip"):
        get_encoder("auto")


def test_auto_decides_once_and_exports_the_name(monkeypatch):
    from lizardfs_tpu.runtime.metrics import Metrics

    monkeypatch.setattr(enc_mod, "_ENCODERS", {})
    calls = []
    real = enc_mod._resolve_auto
    monkeypatch.setattr(
        enc_mod, "_resolve_auto", lambda: calls.append(1) or real()
    )
    first = get_encoder("auto")
    assert get_encoder("auto") is first and len(calls) == 1
    metrics = Metrics()
    enc_mod.export_backend(metrics, first)
    assert (
        f'lizardfs_encoder_backend_total{{name="{first.name}"}} 1'
        in metrics.to_prometheus()
    )


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code.
    Unset: one fixed path inside the checkout. Either way the
    thresholds keep the small per-segment programs."""
    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.__setitem__(k, v)
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert jaxcache.configure_compile_cache() == "/some/dir"
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1
    updates.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(repo, ".jax_cache")
    assert jaxcache.configure_compile_cache() == want
    assert updates["jax_compilation_cache_dir"] == want
