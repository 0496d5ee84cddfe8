"""Grid-transfer operators (restriction and interpolation) for multigrid.

TPU-native counterpart of /root/reference/pystella/multigrid/transfer.py:40-264.
The reference generates loopy stencil kernels indexed by ``(2i, 2j, 2k)``
(restriction) or by ``((i+a)//2, i%2)`` parity selection (interpolation).
Here both are tensor-product per-axis array ops on local blocks: restriction
is a strided slice of a halo-padded block, interpolation is an interleave
(``stack`` + ``reshape``) of even/odd parts — shapes are static, so XLA
fuses the three axes into one pass.

Each operator works on *local blocks*: inside a ``shard_map`` (halos arrive
via ``lax.ppermute`` through the supplied pad function) or on whole
replicated arrays (periodic wrap pad). The multigrid driver chooses per
level; the operators themselves are mesh-agnostic.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

__all__ = ["RestrictionBase", "FullWeighting", "Injection",
           "InterpolationBase", "LinearInterpolation", "CubicInterpolation",
           "periodic_pad"]


def periodic_pad(x, halo, lattice_axes=None):
    """Pad the lattice axes of ``x`` with periodic wraps of width
    ``halo[d]`` — the local (no-communication) analog of
    ``DomainDecomposition.pad_with_halos`` for replicated arrays."""
    if np.isscalar(halo):
        halo = (halo,) * 3
    if lattice_axes is None:
        lattice_axes = tuple(range(x.ndim - 3, x.ndim))
    for d, ax in enumerate(lattice_axes):
        h = halo[d]
        if h == 0:
            continue
        lo = lax.slice_in_dim(x, x.shape[ax] - h, x.shape[ax], axis=ax)
        hi = lax.slice_in_dim(x, 0, h, axis=ax)
        x = lax.concatenate([lo, x, hi], dimension=ax)
    return x


class RestrictionBase:
    """Tensor-product restriction: coarse point ``i`` receives
    ``sum_o c_o * fine[2 i + o]`` along each axis (reference
    transfer.py:40-102; coefficient convention matches
    ``pystella.derivs.centered_diff``).

    :arg coefs: dict mapping fine-grid offset ``o`` (relative to the
        coinciding fine point ``2 i``) to its weight.
    :arg halo_shape: accepted for API parity with the reference (padding is
        handled by the pad function, not baked into array shapes).
    :arg correct: if True, :meth:`__call__` computes ``f2 - R(f1)`` — the
        kernel the reference calls ``restrict_and_correct``.
    """

    coefs = {0: 1}

    def __init__(self, halo_shape=0, correct=False, **kwargs):
        self.halo_shape = halo_shape
        self.correct = correct
        self.pad = max(abs(int(o)) for o in self.coefs)

    def apply_local(self, x, pad_fn=periodic_pad):
        """Restrict the trailing 3 (lattice) axes of a local block ``x``
        (even extents) to half resolution."""
        hp = self.pad
        la = x.ndim - 3
        if hp:
            x = pad_fn(x, (hp,) * 3)
        for d in range(3):
            ax = la + d
            n = x.shape[ax] - 2 * hp
            m = n // 2
            acc = None
            for o, c in sorted(self.coefs.items()):
                start = hp + o
                sl = lax.slice_in_dim(x, start, start + 2 * (m - 1) + 1,
                                      stride=2, axis=ax)
                acc = c * sl if acc is None else acc + c * sl
            # the strided slice consumed this axis's halos; later axes keep
            # theirs until their own pass
            x = acc
        return x

    def __call__(self, f1, f2=None, decomp=None):
        """Restrict global array ``f1``; with ``correct=True`` returns
        ``f2 - R(f1)``. ``decomp`` (if given and sharded) runs the operator
        under ``shard_map`` with ppermute halos."""
        out = _run_local(self, f1, decomp)
        if self.correct:
            if f2 is None:
                raise ValueError("correct=True requires f2")
            return f2 - out
        return out


class FullWeighting(RestrictionBase):
    """1/4, 1/2, 1/4 full-weighting restriction per axis (reference
    transfer.py:105-125)."""

    coefs = {-1: 1 / 4, 0: 1 / 2, 1: 1 / 4}


class Injection(RestrictionBase):
    """Direct injection ``f2[i] = f1[2i]`` (reference transfer.py:128-143)."""

    coefs = {0: 1}


class InterpolationBase:
    """Tensor-product interpolation, coarse to fine (reference
    transfer.py:146-205). Per axis: ``fine[2i] = sum_e e_c * coarse[i+e]``
    and ``fine[2i+1] = sum_o o_c * coarse[i+o]``, with coefficients given in
    *coarse-grid* offsets; the two parts interleave via stack+reshape (the
    analog of the reference's 8-parity kernel).

    :arg correct: if True, :meth:`__call__` computes ``f1 + I(f2)`` — the
        reference's ``interpolate_and_correct``.
    """

    even_coefs = {0: 1}
    odd_coefs = {0: 1 / 2, 1: 1 / 2}

    def __init__(self, halo_shape=0, correct=False, **kwargs):
        self.halo_shape = halo_shape
        self.correct = correct
        offs = list(self.even_coefs) + list(self.odd_coefs)
        self.pad = max(abs(int(o)) for o in offs)

    def apply_local(self, x, pad_fn=periodic_pad):
        """Interpolate the trailing 3 (lattice) axes of a local coarse block
        to double resolution."""
        hp = self.pad
        la = x.ndim - 3
        if hp:
            x = pad_fn(x, (hp,) * 3)

        for d in range(3):
            ax = la + d
            m = x.shape[ax] - 2 * hp

            def part(coefs):
                acc = None
                for o, c in sorted(coefs.items()):
                    sl = lax.slice_in_dim(x, hp + o, hp + o + m, axis=ax)
                    acc = c * sl if acc is None else acc + c * sl
                return acc

            even, odd = part(self.even_coefs), part(self.odd_coefs)
            y = jnp.stack([even, odd], axis=ax + 1)
            shape = list(even.shape)
            shape[ax] *= 2
            x = y.reshape(shape)
        return x

    def __call__(self, f2, f1=None, decomp=None):
        """Interpolate global coarse array ``f2``; with ``correct=True``
        returns ``f1 + I(f2)``."""
        out = _run_local(self, f2, decomp)
        if self.correct:
            if f1 is None:
                raise ValueError("correct=True requires f1")
            return f1 + out
        return out


class LinearInterpolation(InterpolationBase):
    """Linear interpolation (reference transfer.py:208-231)."""

    even_coefs = {0: 1}
    odd_coefs = {0: 1 / 2, 1: 1 / 2}


class CubicInterpolation(InterpolationBase):
    """Cubic interpolation; odd fine points take a 4-point coarse stencil
    (reference transfer.py:234-264)."""

    even_coefs = {0: 1}
    odd_coefs = {-1: -1 / 16, 0: 9 / 16, 1: 9 / 16, 2: -1 / 16}


def _run_local(op, x, decomp):
    """Apply ``op.apply_local`` on a global array — under ``shard_map`` when
    a sharded decomp is supplied, else locally with periodic-wrap pads.
    Compiled wrappers are cached on ``op`` so repeated calls reuse the
    executable. The replicated branch is jitted too: eagerly it issues
    ~a dozen sliced ops per transfer, each a separate device dispatch
    (what a dispatch costs on the chip is not measured)."""
    cache = getattr(op, "_jit_cache", None)
    if cache is None:
        cache = op._jit_cache = {}
    if decomp is not None and any(p > 1 for p in decomp.proc_shape):
        key = (decomp, x.ndim)
        fn = cache.get(key)
        if fn is None:
            spec = decomp.spec(x.ndim - 3)

            def body(blk):
                return op.apply_local(blk, pad_fn=decomp.pad_with_halos)

            from pystella_tpu.obs import memory as _obs_memory
            fn = cache[key] = _obs_memory.instrument_jit(
                decomp.shard_map(body, spec, spec),
                label=f"mg.transfer.{type(op).__name__}.sharded")
        return fn(x)
    fn = cache.get("local")
    if fn is None:
        from pystella_tpu.obs import memory as _obs_memory
        fn = cache["local"] = _obs_memory.instrument_jit(
            op.apply_local,
            label=f"mg.transfer.{type(op).__name__}.local")
    return fn(x)
