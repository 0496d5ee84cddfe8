"""Grid-transfer operators (restriction and interpolation) for multigrid.

TPU-native counterpart of /root/reference/pystella/multigrid/transfer.py:40-264.
The reference generates loopy stencil kernels indexed by ``(2i, 2j, 2k)``
(restriction) or by ``((i+a)//2, i%2)`` parity selection (interpolation).
Here both are tensor-product per-axis array ops on local blocks with static
shapes. Restriction splits the major lattice axis into its even and odd
planes (a free reshape) and contracts the two minor axes with the operator's
``(n/2, n)`` weight matrix on the MXU, the periodic wrap in the matrix's
corners: a stride of 2 along a tiled minor axis is a relayout on the TPU,
and a padded copy of the block is a pass over HBM, so it takes neither.
Interpolation is an interleave (``stack`` + ``reshape``) of even/odd parts
of a halo-padded block.

Each operator works on *local blocks*: inside a ``shard_map`` (halos arrive
via ``lax.ppermute`` through the supplied pad function) or on whole
replicated arrays (periodic wrap). The multigrid driver chooses per
level; the operators themselves are mesh-agnostic.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

from pystella_tpu.obs import events as _events
from pystella_tpu.obs import memory as _obs_memory

__all__ = ["RestrictionBase", "FullWeighting", "Injection",
           "InterpolationBase", "LinearInterpolation", "CubicInterpolation",
           "periodic_pad"]


#: a restriction's contractions: six bfloat16 passes on the TPU, which keep
#: float32 products with weights that are exact in bfloat16 exact
_PRECISION = lax.Precision.HIGHEST


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

    def weights(self, n, dtype, wrap=True):
        """The ``(n // 2, n)`` matrix of one axis's restriction, row ``i``
        holding ``coefs`` about column ``2 i`` with the periodic wrap in its
        corners; with ``wrap=False`` the ``(n // 2, n + 2 pad)`` one that
        takes a block padded by :attr:`pad` halo rows instead."""
        m = n // 2
        w = np.zeros((m, n if wrap else n + 2 * self.pad), dtype)
        for o, c in self.coefs.items():
            j = 2 * np.arange(m) + o
            w[np.arange(m), j % n if wrap else j + self.pad] += c
        return w

    def plan(self, shape, pad_fn=periodic_pad):
        """What :meth:`apply_local` makes of a block of lattice ``shape``
        (the ``mg_transfer_plan`` event's fields). Per axis: ``split`` (the
        major axis: its even and odd planes, the wrap a roll by a plane),
        ``contract`` (a minor axis: the wrapped weight matrix) or, where
        ``pad_fn``'s halo is a neighbour's rows and not the block's own
        wrap, ``contract_halo`` (that axis alone padded, the unwrapped
        matrix); and the contractions' flop count."""
        # periodic_pad wraps every axis, a decomposition's pad_with_halos
        # those it does not shard; of another pad nothing is known
        proc = (1, 1, 1) if pad_fn is periodic_pad else getattr(
            getattr(pad_fn, "__self__", None), "proc_shape", (0, 0, 0))
        forms = ["contract" if p == 1 else "contract_halo" for p in proc]
        if proc[0] == 1:
            forms[0] = "split"
        sites, flops = int(np.prod(shape)), 0
        for n, form in zip(shape, forms):
            sites //= 2  # coarse along this axis and those before it
            if form != "split":
                cols = n if form == "contract" else n + 2 * self.pad
                flops += 2 * sites * cols
        return {"axes": forms, "precision": _PRECISION.name.lower(),
                "flops": flops}

    def apply_local(self, x, pad_fn=periodic_pad):
        """Restrict the trailing 3 (lattice) axes of a local block ``x``
        (even extents) to half resolution: per axis the form :meth:`plan`
        names, the contractions at ``lax.Precision.HIGHEST`` (on the TPU
        three bfloat16 pieces carry a float32 exactly and weights like
        1/4, 1/2, 1 are exact in bfloat16, so every product is exact and a
        single-offset operator such as :class:`Injection` returns
        ``x[2i, 2j, 2k]`` bit for bit). A contraction multiplies every
        fine value of a line by a weight, most of them zero: a NaN or Inf
        spreads along its y and z lines of the coarse block, where picked
        offsets would carry it to its neighbouring coarse points only."""
        la = x.ndim - 3
        for d, form in enumerate(self.plan(x.shape[la:], pad_fn)["axes"]):
            ax, n = la + d, x.shape[la + d]
            if form == "split":
                parts = x.reshape(x.shape[:ax] + (n // 2, 2) + x.shape[ax + 1:])
                acc = None
                for o, c in sorted(self.coefs.items()):
                    part = lax.index_in_dim(parts, o % 2, ax + 1,
                                            keepdims=False)
                    if o // 2:
                        part = jnp.roll(part, -(o // 2), ax)
                    acc = c * part if acc is None else acc + c * part
                x = acc
                continue
            if form == "contract_halo":
                x = pad_fn(x, tuple(self.pad * (e == d) for e in range(3)))
            w = self.weights(n, x.dtype, wrap=form == "contract")
            x = jnp.moveaxis(jnp.tensordot(
                w, x, axes=(1, ax), precision=_PRECISION), 0, ax)
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


def _local_program(op, label, decomp=None, outer_axes=0):
    """``op.apply_local`` as one named program: under ``decomp``'s
    ``shard_map`` with its ``ppermute`` halos (arrays of ``outer_axes``
    leading component axes), or (``decomp=None``) on a whole array with
    periodic wraps. A restriction says once a traced program what it made
    of each axis (``mg_transfer_plan``)."""
    pad_fn = periodic_pad if decomp is None else decomp.pad_with_halos
    proc = (1, 1, 1) if decomp is None else decomp.proc_shape

    def body(blk):
        if isinstance(op, RestrictionBase):
            local = blk.shape[-3:]
            _events.emit(
                "mg_transfer_plan", operator=type(op).__name__, label=label,
                grid_shape=[n * p for n, p in zip(local, proc)],
                local_shape=list(local), dtype=str(blk.dtype),
                **op.plan(local, pad_fn))
        return op.apply_local(blk, pad_fn=pad_fn)

    if decomp is not None:
        spec = decomp.spec(outer_axes)
        body = decomp.shard_map(body, spec, spec)
    return _obs_memory.instrument_jit(body, label=label)


def _run_local(op, x, decomp):
    """Apply ``op.apply_local`` on a global array — under ``shard_map`` when
    a sharded decomp is supplied, else locally with periodic wraps.
    Compiled wrappers are cached on ``op`` so repeated calls reuse the
    executable. The replicated branch is jitted too: eagerly it issues
    ~a dozen sliced ops per transfer, each a separate device dispatch
    (what a dispatch costs on the chip is not measured)."""
    cache = getattr(op, "_jit_cache", None)
    if cache is None:
        cache = op._jit_cache = {}
    if decomp is not None and all(p == 1 for p in decomp.proc_shape):
        decomp = None
    key = "local" if decomp is None else (decomp, x.ndim)
    fn = cache.get(key)
    if fn is None:
        fn = cache[key] = _local_program(
            op, f"mg.transfer.{type(op).__name__}."
            + ("local" if decomp is None else "sharded"), decomp, x.ndim - 3)
    return fn(x)
