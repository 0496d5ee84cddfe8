"""Test configuration: virtual multi-device CPU mesh.

Mirrors the reference's strategy of running the same test bodies at several
process-grid shapes (/root/reference/test/conftest.py:1-22 +
.github/workflows/ci.yml:96-97, which reruns the suite under
``mpirun -np 4 --proc_shape 2,2,1``). Here a single process fakes 8 CPU
devices via ``--xla_force_host_platform_device_count`` and tests
parametrize over mesh shapes, exercising the identical ``shard_map`` /
``ppermute`` / ``psum`` code paths that run over ICI on a real TPU slice.

The suite is CPU-only: ``JAX_PLATFORMS`` defaults to ``cpu`` here, before
jax is first imported. The chip is reached through ``chip_smoke.py`` and
the benchmark (``benchmark/run.py``), one process each; the
compiled-kernel evidence lives there, in tests/test_tpu_compile.py (the
main path compiled for a described v5e) and in tests/test_tpu_lowering.py
(Pallas TPU lowering checks, on CPU).
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Pin the suite-wide default to the PADDED halo path: with the
# production default (overlap auto-on for sharded meshes) every
# sharded-mesh test compiles the extra interior+shell graphs, which
# costs ~2 minutes of tier-1 wall time against a hard 870 s budget.
# The overlapped path's correctness — including that it IS the default
# resolution — is covered explicitly in tests/test_overlap.py via
# per-constructor overrides, which beat this env. setdefault, so
# PYSTELLA_HALO_OVERLAP=1 pytest ... runs the whole suite overlapped
# (the bit-exactness contract means results must be identical).
os.environ.setdefault("PYSTELLA_HALO_OVERLAP", "0")

import common  # noqa: F401, E402  (side effect: enables x64)
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption("--grid_shape", action="store", default=None,
                     help="comma-separated lattice shape, e.g. 32,32,32")
    parser.addoption("--proc_shape", action="store", default=None,
                     help="comma-separated mesh shape, e.g. 2,2,1")


def _parse(opt, default):
    if opt is None:
        return default
    return tuple(int(i) for i in opt.split(","))


@pytest.fixture
def grid_shape(request):
    if hasattr(request, "param"):  # indirect parametrization wins
        return tuple(request.param)
    return _parse(request.config.getoption("--grid_shape"), (16, 16, 16))


@pytest.fixture
def proc_shape(request):
    if hasattr(request, "param"):  # indirect parametrization wins
        return tuple(request.param)
    return _parse(request.config.getoption("--proc_shape"), (2, 2, 1))


@pytest.fixture
def isolated_cache(tmp_path, monkeypatch):
    """A compilation cache placed from OUTSIDE, the one way the program
    allows: the environment variable names it (jax read it at import,
    so an in-process test also hands jax the same value)."""
    import jax
    cache = str(tmp_path / "xla_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", cache)
    yield cache
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.fixture
def make_decomp():
    """Build a DomainDecomposition for ``proc_shape``, skipping when the
    host exposes fewer devices than the mesh needs (the suite assumes
    ``--xla_force_host_platform_device_count=8`` but should degrade
    gracefully, like the reference's mpirun-parametrized CI)."""
    def _make(proc_shape):
        import jax
        from pystella_tpu import DomainDecomposition
        n = int(np.prod(proc_shape))
        if n > len(jax.devices()):
            pytest.skip(f"mesh {proc_shape} needs {n} devices, "
                        f"have {len(jax.devices())}")
        return DomainDecomposition(proc_shape, devices=jax.devices()[:n])
    return _make


@pytest.fixture
def decomp(proc_shape, make_decomp):
    return make_decomp(proc_shape)
