"""chip_smoke.py's phases at a tiny size on the CPU, and the guards that
keep the chip path honest: no TPU means a non-zero exit, never a CPU
result; the compile cache sits where the environment or the repo says; a
warm standby is refused on a TPU host."""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from sentinel_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def fresh_api():
    from sentinel_tpu.core import api

    yield
    api.reset()


@pytest.mark.parametrize("seed", [0, 1])
def test_single_chip_phases_match_oracle(fresh_api, seed):
    scale = chip_smoke.TINY
    r = chip_smoke.run_single(scale, seed)
    assert chip_smoke._single_ok(r, scale) == []
    assert r["demo"] == [chip_smoke.DEMO_COUNT] * 3
    assert r["checked"] > 0 and r["mismatches"] == 0
    assert r["armed"] and r["trips"] == 0
    assert r["policy_served"] == 0 and r["health"] == "HEALTHY"
    assert r["flushes"] == {0: scale.flushes, 2: scale.flushes}
    assert r["entries"] == 2 * scale.flushes * scale.batch
    assert 0 < r["admitted"] < r["entries"]  # the rules bite


def test_mesh_phase_matches_one_device():
    scale = chip_smoke.Scale(
        rules=256, resources=32, degrade=4, param_keys=200, batch=512,
        param_share=64, exits=64, flushes=2, sample=0,
    )
    r = chip_smoke.run_mesh(scale, 0, 4)
    assert r["mesh_devices"] == [0, 1, 2, 3]
    assert r["diff"] == 0
    assert r["policy_served"] == 0
    # Tables and state live on all four devices from the first flush on:
    # nothing is copied out from device 0 by every flush.
    for placed in r["shardings"]:
        assert all(ids == [0, 1, 2, 3] for ids in placed.values()), placed


def test_main_refuses_a_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_bench_without_platform_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench.py", "--budget-s", "120"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert "error" in last and last["value"] == 0.0


def test_require_platform_exits_on_the_wrong_backend():
    with pytest.raises(SystemExit):
        backend.require_platform("tpu")
    # JAX_PLATFORMS=cpu (the tests' pin) expects nothing else.
    assert not backend.expects_tpu()
    assert backend.require_platform() == "cpu"


def test_tpu_chips_counted_from_pci_sysfs(tmp_path):
    for name, vendor, device in [
        ("0000:00:04.0", "0x1ae0", "0x0063"),  # v5e
        ("0000:00:05.0", "0x1ae0", "0x0063"),
        ("0000:00:06.0", "0x1ae0", "0x0001"),  # Google, not a TPU
        ("0000:00:07.0", "0x10de", "0x0063"),  # another vendor
    ]:
        d = tmp_path / name
        d.mkdir()
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
    (tmp_path / "0000:00:08.0").mkdir()  # unreadable entries are skipped
    assert backend.tpu_chips_on_host(str(tmp_path)) == 2
    assert backend.tpu_chips_on_host(str(tmp_path / "absent")) == 0


@pytest.fixture()
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_honours_env(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert backend.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing else set


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert backend.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # The tests' own switch still keeps the cache off.
    assert not jax.config.jax_enable_compilation_cache


def test_standby_refused_on_a_tpu_host(monkeypatch):
    from sentinel_tpu.ipc import supervise
    from sentinel_tpu.utils.config import config

    monkeypatch.setattr(backend, "expects_tpu", lambda: True)
    prev = config.get(config.SUPERVISE_STANDBY)
    config.set(config.SUPERVISE_STANDBY, "true")
    try:
        with pytest.raises(RuntimeError, match="one process at a time"):
            supervise.EngineSupervisor()
    finally:
        config.set(config.SUPERVISE_STANDBY, prev)
