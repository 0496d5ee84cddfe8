"""Accumulation-exactness tests for the chunked histogram path.

TPUs have no native f64, so on real hardware (``jax_enable_x64`` off) a
naive f32 scatter-add loses integer exactness once a bin passes 2**24
counts — a 512**3 lattice has 1.3e8 sites. The chunked design must stay
exact regardless of x64 (the analog of the reference's f64 device
accumulation, /root/reference/pystella/histogram.py:199-206). These tests
run in a subprocess with x64 explicitly DISABLED and more than 2**24
samples landing in one bin.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import numpy as np
import jax
import pystella_tpu as ps
from pystella_tpu import field as f

assert not jax.config.jax_enable_x64

decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
shape = (256, 256, 257)              # 16,842,752 sites > 2**24
total = int(np.prod(shape))
fx = decomp.shard(np.full(shape, 2.3, np.float32))

# exact integer counts (unit weight -> int path)
h = ps.Histogrammer(decomp, {"h": (f.Field("f"), 1)}, 4, dtype=np.int64)
out = h(f=fx)["h"]
assert out[2] == total, (out, total)
assert out.sum() == total

# weighted path: every chunk partial is exact for uniform weights, and the
# host finalizes in f64, so the total is exact too
hw = ps.Histogrammer(decomp, {"h": (f.Field("f"), 2.0)}, 4)
outw = hw(f=fx)["h"]
assert outw[2] == 2.0 * total, (outw, 2.0 * total)

print("EXACT-OK")
"""


def test_exact_counts_without_x64():
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "0"
    env["PYTHONPATH"] = REPO
    result = subprocess.run([sys.executable, "-c", _SCRIPT],
                            capture_output=True, text=True, timeout=600,
                            env=env)
    assert result.returncode == 0, result.stderr[-2000:]
    assert "EXACT-OK" in result.stdout
