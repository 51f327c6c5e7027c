"""chip_smoke.py's phases at tiny sizes on the CPU, kernels interpreted.

The chip run exercises the same functions at deployment size; here every
PR re-checks their wiring and references, plus the guards that keep a run
from passing without the device: the answered-key counters, the refusal of
platforms other than ``cpu``/``tpu``, and the exit without a result when no
TPU is present.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.core.cdc import ContentDefinedChunker  # noqa: E402
from repro.core.fp_index import FingerprintIndex  # noqa: E402


@pytest.fixture(scope="module")
def clock():
    return chip_smoke._CompileClock()


@pytest.fixture
def device_indexes(monkeypatch):
    """Engines' indexes on the Pallas backend (interpreted here), with a
    host-set cutoff small enough for tiny front-end batches to reach it."""
    monkeypatch.setitem(FingerprintIndex.__init__.__kwdefaults__, "backend", "pallas")
    monkeypatch.setitem(FingerprintIndex.__init__.__kwdefaults__, "small_batch", 64)


def test_phase_state_tiny(clock, device_indexes):
    r = chip_smoke.phase_state(clock, distinct=20_000, served=8192, max_batch=4096)
    assert r["distinct_resident_fps"] >= 20_000
    assert sum(r["resident_after_serve_by_shard"]) >= r["distinct_resident_fps"]
    assert r["records"] == 8192 and r["consistent"]
    assert r["device_table_bytes"] > 0
    chip_smoke._check_on_device(r)


def test_phase_exact_tiny(clock, device_indexes):
    r = chip_smoke.phase_exact(clock, writes=20_000, max_batch=4096)
    assert r["report_equal"] and r["records"] == 20_000
    chip_smoke._check_on_device(r)


def test_phase_bytes_tiny(clock, device_indexes):
    r = chip_smoke.phase_bytes(clock, fleet_bytes=2 << 20, sample_bytes=1 << 20,
                               backend="pallas")
    assert r["bytes"] >= 2 << 20 and r["report_equal"]
    assert r["fingerprints_checked_bytes"] >= 1 << 20
    chip_smoke._check_on_device(r)


def test_check_on_device_rejects_host_runs():
    ok = {"phase": "x", "backends": ["pallas"], "device_answered_keys": 1}
    chip_smoke._check_on_device(ok)
    for bad in ({**ok, "backends": ["numpy"]}, {**ok, "backends": ["numpy", "pallas"]},
                {**ok, "device_answered_keys": 0}, {**ok, "cdc_backend": "numpy"}):
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke._check_on_device(bad)


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_answered_key_counters_add_up(backend):
    rng = np.random.default_rng(3)
    idx = FingerprintIndex(backend=backend, small_batch=100)
    idx.add_many(rng.integers(1, 1 << 63, size=3000, dtype=np.uint64))
    sizes = [0, 1, 100, 101, 2000, 37]
    for n in sizes:
        idx.contains_many(rng.integers(1, 1 << 63, size=n, dtype=np.uint64))
    idx.probe_and_add(np.unique(rng.integers(1, 1 << 63, size=500, dtype=np.uint64)))
    st = idx.table_stats()
    assert st["probed_device"] + st["probed_host"] == sum(sizes) + 500
    table_keys = 101 + 2000 + 500  # batches above small_batch
    assert st["probed_device"] == (table_keys if backend == "pallas" else 0)


@pytest.mark.parametrize("resolver", ["fp_index", "cdc", "kernel"])
def test_backend_resolution_raises_off_cpu_and_tpu(monkeypatch, resolver):
    import jax

    from repro.kernels.ops import fingerprint_blocks

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    run = {
        "fp_index": lambda: FingerprintIndex(small_batch=0).contains_many(
            np.arange(1, 9, dtype=np.uint64)),
        "cdc": lambda: ContentDefinedChunker().chunk(np.zeros(4096, dtype=np.uint8)),
        "kernel": lambda: fingerprint_blocks(np.zeros((1, 128), dtype=np.uint32)),
    }[resolver]
    with pytest.raises(RuntimeError, match="gpu"):
        run()


def _run_script(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, path], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_script_refuses_cpu_without_a_result():
    out = _run_script(os.path.join(ROOT, "chip_smoke.py"), ROOT)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_script_alone_fails_without_a_result(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), lone)
    out = _run_script(str(lone), str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_placement(monkeypatch, env_dir):
    import jax

    from repro import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(compile_cache.CHECKOUT, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert calls == []  # JAX reads the variable itself
    assert os.path.isfile(os.path.join(compile_cache.CHECKOUT, "chip_smoke.py"))

