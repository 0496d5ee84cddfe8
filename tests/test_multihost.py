"""Real two-process distributed execution test.

The reference proves its distributed backend by running the suite under
``mpirun -np 4`` / ``-np 3`` (/root/reference/.github/workflows/ci.yml:96-97).
The TPU-native analog: two OS processes form a ``jax.distributed``
multi-controller cluster over a localhost coordinator (each with two virtual
CPU devices), build one global 4-device mesh, and check the multihost verbs
(``host_local_to_global``/``global_to_host_local``), a cross-process
halo-exchange stencil, the pencil DFT, and ``sync_hosts`` — see
``multihost_worker.py`` for the worker body.
"""

import os
import socket
import subprocess
import sys

import pytest

import common  # noqa: F401  (side effect: enables x64)

WORKER = os.path.join(os.path.dirname(__file__), "multihost_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


@pytest.mark.parametrize("nproc", [2, 3])
def test_process_cluster(tmp_path, nproc):
    """2- and 3-process clusters (each contributing 2 devices) — the
    analog of the reference CI's even/odd process-count matrix
    (``mpirun -np 4`` and ``-np 3``, ci.yml:96-97): the odd count
    catches layout bugs that even divisibility hides."""
    coordinator = f"localhost:{_free_port()}"

    env = dict(os.environ)
    # the worker configures its own platform/devices; scrub the suite's
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")

    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, coordinator, str(i),
             str(tmp_path / "snaps"), str(nproc)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        for i in range(nproc)]

    outputs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=540)
            outputs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multihost workers timed out:\n"
                    + "\n".join(o or "" for o in outputs))

    for i, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, \
            f"worker {i} failed (rc={p.returncode}):\n{out}"
        assert f"worker {i}: OK" in out
