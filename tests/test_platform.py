"""Backend set-up (utils/platform.py), bench.py's headline row and the
no-chip exits: a run meant for the chip never carries on elsewhere, and the
compile cache lives where JAX_COMPILATION_CACHE_DIR or the fixed
in-repo default says."""

import os

import jax
import numpy as np
import pytest

from p2p_gossip_tpu.utils import platform as plat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert plat.compile_cache_dir() == str(tmp_path)
    # Set: JAX reads the variable itself, so no other directory is set in
    # code (and the test process's cache stays off).
    monkeypatch.setenv("JAX_PLATFORMS", "")
    before = jax.config.jax_compilation_cache_dir
    assert plat.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_repo_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert plat.compile_cache_dir() == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_stays_off_on_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    before = jax.config.jax_compilation_cache_dir
    assert plat.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_check_backend_exits_without_chip(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(SystemExit) as e:
        plat.check_backend()
    assert "no TPU found" in str(e.value.code)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert plat.check_backend() == "cpu"


def test_use_host_devices_only_on_cpu(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/dev/null")
    monkeypatch.setenv("JAX_PLATFORMS", "")
    plat.use_host_devices(4)
    assert os.environ["XLA_FLAGS"] == "--xla_dump_to=/dev/null"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    plat.use_host_devices(4)
    assert os.environ["XLA_FLAGS"].endswith(
        "--xla_force_host_platform_device_count=4"
    )
    plat.use_host_devices(2)  # an existing count wins
    assert "count=2" not in os.environ["XLA_FLAGS"]


def test_bench_exits_nonzero_without_chip(monkeypatch, capsys):
    """No chip and CPU not asked for: bench.py exits non-zero before any
    work and prints no JSON row."""
    import bench

    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_bench_prints_one_row_naming_its_device(monkeypatch, capsys):
    """Asked for the host CPU, bench.py floods its reduced config to full
    coverage and prints exactly one JSON line: the headline row, under
    the device it ran on and a CPU label."""
    import json

    import bench

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    bench.main()
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 1
    row = json.loads(lines[0])
    assert row["device"] == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    assert "CPU - JAX_PLATFORMS=cpu" in row["metric"]
    assert row["unit"] == "node-updates/s"
    assert row["value"] > 0 and row["vs_baseline"] > 0 and row["ticks"] > 0


def test_native_engine_raises_when_unbuilt(monkeypatch):
    """A missing C++ library is an error, never a silent switch to
    another engine."""
    import p2p_gossip_tpu as pg
    from p2p_gossip_tpu.runtime import native

    monkeypatch.setattr(native, "load_library", lambda: None)
    g = pg.erdos_renyi(8, 0.5, seed=0)
    sched = pg.Schedule(g.n, np.array([0], np.int32), np.array([0], np.int32))
    with pytest.raises(RuntimeError, match="make -C native"):
        native.run_native_sim(g, sched, 4)
    with pytest.raises(RuntimeError, match="make -C native"):
        native.run_native_partnered_sim(g, sched, 4)
