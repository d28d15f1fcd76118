"""chip_smoke.py's phases at tiny sizes on the CPU mesh, its refusal of
a CPU backend, and where the compile cache goes (compile_cache.py).

The phases are the same functions the chip runs at full size; here
they must decide every verdict equal to its reference and trip none of
the fallback rules.  Pallas runs only on the chip, so the "Pallas on"
rule is not exercised here."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def telemetry_on():
    from jepsen_tpu import telemetry

    was = telemetry.enabled()
    telemetry.enable(True)
    yield
    telemetry.enable(was)
    telemetry.reset()


@pytest.mark.parametrize("phase", [
    lambda: chip_smoke.phase_north_star(0, n_ops=2000),
    lambda: chip_smoke.phase_independent(0, n_keys=24),
    lambda: chip_smoke.phase_elle(0, n_txns=60),
    lambda: chip_smoke.phase_mesh(0, 4, n_keys=24),
], ids=["north-star", "independent", "elle", "mesh"])
def test_phase_passes_tiny_on_cpu(phase, telemetry_on):
    rec = phase()
    json.dumps(rec, default=str)  # printable as one line
    assert rec["ok"], rec["problems"]
    assert rec["fallbacks"] == {} or not any(rec["fallbacks"].values())


def test_phase_judge_fails_on_fallback_counter(telemetry_on):
    rec = chip_smoke._judge({}, {"wgl.degrade.witness.retry-halved": 1},
                            ["wgl-tpu"], witness_ran=False)
    assert not rec["ok"]
    rec = chip_smoke._judge({}, {}, ["event-degraded"], witness_ran=False)
    assert not rec["ok"]


def test_entry_point_refuses_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--seed",
         "0"], env=env, capture_output=True, timeout=300, cwd=tmp_path,
    )
    assert proc.returncode != 0
    lines = proc.stdout.decode().splitlines()
    assert not any(ln.startswith('{"ok"') for ln in lines), lines


def test_compile_cache_respects_env(monkeypatch, tmp_path):
    import jax

    from jepsen_tpu import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.place() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_default(monkeypatch):
    import jax

    from jepsen_tpu import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        assert compile_cache.place() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            compile_cache.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_held_chip_fails_at_once_with_a_clear_error(monkeypatch):
    import jax

    from jepsen_tpu.ops import degrade

    def held():
        raise RuntimeError(
            "Unable to initialize backend 'tpu': ABORTED: Internal error "
            "when accessing libtpu multi-process lockfile.")

    monkeypatch.setattr(degrade, "_chip_state", "unprobed")
    monkeypatch.setattr(jax, "devices", held)
    with pytest.raises(degrade.ChipBusy, match="one process per chip"):
        degrade.note_backend()
    assert degrade.chip_state() == "unprobed"
