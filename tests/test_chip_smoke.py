"""``chip_smoke.py`` off the chip: its body at a tiny lattice in interpret
mode must pass its own checks on both meshes, the script itself must
refuse a machine without a TPU before it builds anything, and the
compile cache it (and every entry point) wires must follow the
environment."""

import os
import subprocess
import sys

import pytest

import common  # noqa: F401  (side effect: enables x64)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("proc_shape", [(1, 1, 1), (2, 2, 1)])
def test_smoke_body_passes_its_own_checks(tmp_path, proc_shape,
                                          make_decomp, isolated_cache):
    """Both driver invocations, the checkpoint read-back and the parity
    comparison at 16x16x128 — every check of the chip run except the
    ones only a chip can answer (``peak_bytes_in_use``)."""
    make_decomp(proc_shape)  # skips when the host has too few devices
    leg = chip_smoke.run_leg((16, 16, 128), proc_shape, str(tmp_path))
    assert leg["steps"] == 16 and leg["checkpoints"] >= 1
    assert set(chip_smoke.MAIN_KERNELS) <= set(leg["blocks"])
    assert leg["parity_maxrel"] <= chip_smoke.PARITY_BOUND
    assert leg["in_place_differing"] == 0
    assert len(leg["digest"]) == 16
    assert (leg["halo_bytes"] > 0) == (proc_shape != (1, 1, 1))
    # the CPU keeps no allocator statistics: the chip-only check must
    # say so rather than pass
    assert leg["peak_bytes_in_use"] == [None] * len(
        leg["peak_bytes_in_use"])
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_peaks(leg)


def _run(code_or_script, env_extra, *args):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_extra)
    return subprocess.run(
        [sys.executable, *code_or_script, *args], capture_output=True,
        text=True, timeout=240, env=env, cwd=REPO)


def test_script_refuses_a_machine_without_a_tpu():
    res = _run([os.path.join(REPO, "chip_smoke.py")], {})
    assert res.returncode != 0
    assert "not a TPU" in res.stderr
    # nothing built, no result printed
    assert res.stdout == ""


_CACHE_PROBE = """
import jax
from pystella_tpu import obs
print(obs.ensure_compilation_cache())
print(jax.config.jax_compilation_cache_dir)
"""


def test_cache_dir_is_placed_from_outside(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR in a child's environment the
    program sets no other directory (unset, the in-checkout default:
    tests/test_warmstart.py)."""
    placed = str(tmp_path / "placed_cache")
    res = _run(["-c", _CACHE_PROBE], {"JAX_COMPILATION_CACHE_DIR": placed})
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.split() == [placed, placed]
