"""Fully distributed pencil FFTs: per-axis local FFT stages with the
inter-stage redistributions expressed as explicit ``lax.all_to_all``
transpose collectives inside ``shard_map``.

This is the TPU-native analog of mpi4py-fft's ``PFFT`` pencil transform
(the reference's multi-rank path, /root/reference/pystella/fourier/
dft.py:391-417): data NEVER replicates — every stage holds exactly
``1/ndev`` of the lattice — and every transpose is a named collective
the latency-hiding scheduler can overlap with neighboring local FFT
work. Contrast :class:`~pystella_tpu.fourier.dft.DFT`, whose
declarative ``reshard`` tiers leave the collective choice (and, on its
partial tier, a transient per-stage replication) to the SPMD
partitioner.

Transpose plan (forward, r2c), per-device block starting at the
position-space home ``(X/px, Y/py, Z/pz)`` over a ``(px, py, pz)``
mesh:

====  ==========================================  =====================
step  collective / compute                        block after
====  ==========================================  =====================
A     ``all_to_all`` over z: split x, concat z    ``(X/(px·pz), Y/py, Z)``
B     local ``rfft``/``fft`` along z              ``(…, …, Zh)``
C     ``all_to_all`` over y: split x, concat y    ``(X/P, Y, Zh)``
D     local ``fft`` along y                       ``(X/P, Y, Zh)``
E     ``all_to_all`` over (x, z, y) combined:     ``(X, Y/P, Zh)``
      split y, concat x
F     local ``fft`` along x                       ``(X, Y/P, Zh)``
====  ==========================================  =====================

(``P = px·py·pz``, ``Zh = Z//2 + 1``; size-1 mesh axes skip their
step.) The k-space layout is therefore the transform's NATURAL pencil
layout — x local, y sharded over the combined ``(x, z, y)`` mesh axes,
half-spectrum z local — NOT the ``DFT`` classes' x/y home layout.
``np.asarray`` of the result is the ordinary global ``rfftn`` array
either way, and :meth:`PencilFFT.k_axis_array` /
:meth:`PencilFFT.k_sharding` hand every k-space consumer
(spectra binning, projectors, Poisson, spectral derivatives)
constants in the matching layout, so nothing downstream needs to know.
The inverse runs the exact mirror (each ``all_to_all`` inverted by
swapping its split/concat axes).

Feasibility: grid ``X % P == 0`` and ``Y % P == 0`` (plus the per-axis
home divisibility every sharded array already satisfies). Infeasible
shapes raise at construction with the feasible alternatives named —
use :func:`pystella_tpu.fourier.plan.make_dft` to fall back to the
``DFT`` tiers automatically.

Batched (multi-field) transforms pipeline the transposes: field
``k+1``'s ``all_to_all`` is issued BEFORE field ``k``'s local FFT
stage, so the collective is in flight while dependence-free compute
runs — the same issue-first discipline as the PR-3 halo overlap. Each
stage carries a ``fft_stage`` scope and each transpose an
``fft_transpose`` scope; the perf ledger's ``fft`` report section
derives its exposed-vs-hidden transpose split from those rows.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from pystella_tpu.fourier.dft import (
    DFT, ifft_matmul, irfft_matmul, irfftn3)

__all__ = ["PencilFFT", "pencil_feasible"]


def pencil_feasible(decomp, grid_shape):
    """``(ok, reasons)``: can the shard_map pencil tier serve this
    grid/mesh pair? Every failure is named (the construction error and
    the planner's fallback log both use them)."""
    nproc = int(np.prod(decomp.proc_shape))
    reasons = []
    for d, label in ((0, "x"), (1, "y")):
        if grid_shape[d] % nproc:
            reasons.append(
                f"grid {label}={grid_shape[d]} is not divisible by the "
                f"total device count {nproc} (the transpose stages "
                f"redistribute the {label} axis over ALL devices)")
    for d, p in enumerate(decomp.proc_shape):
        if grid_shape[d] % p:
            reasons.append(
                f"grid axis {d} ({grid_shape[d]}) is not divisible by "
                f"mesh axis {d} ({p}) — the position-space home "
                "sharding itself is infeasible")
    return not reasons, reasons


class PencilFFT(DFT):
    """Distributed 3-D r2c/c2c FFT with explicit all_to_all pencil
    transposes (see module docstring).

    Same constructor and call surface as
    :class:`~pystella_tpu.fourier.dft.DFT`; k-space arrays live in the
    transform's natural pencil layout (:meth:`k_sharding`) rather than
    the x/y home layout. Raises ``ValueError`` at construction when the
    grid/mesh pair cannot be served (:func:`pencil_feasible`).
    """

    is_pencil = True

    def __init__(self, decomp, context=None, queue=None, grid_shape=None,
                 dtype=np.float64, **kwargs):
        if grid_shape is None:
            raise ValueError("grid_shape is required")
        ok, reasons = pencil_feasible(decomp, tuple(grid_shape))
        if not ok:
            nproc = int(np.prod(decomp.proc_shape))
            raise ValueError(
                f"PencilFFT {tuple(grid_shape)} on mesh "
                f"{decomp.proc_shape} ({nproc} devices) is infeasible: "
                + "; ".join(reasons)
                + ". Choose grid x/y divisible by the device count, or "
                "use pystella_tpu.make_dft(..., scheme='auto') to fall "
                "back to the partial/replicate DFT tiers "
                "(pystella_tpu.advise_shapes lists feasible meshes)")
        # the base constructor resolves k_axis_array, _dft_impl/
        # _idft_impl, and _jit_labels through this subclass, so the
        # jits it builds ARE the pencil transform and sub_k_device
        # lands in the natural layout — nothing to rebuild here
        self._sm_cache = {}
        super().__init__(decomp, context=context, queue=queue,
                         grid_shape=grid_shape, dtype=dtype, **kwargs)

    def _jit_labels(self):
        return "fourier.pencil_forward", "fourier.pencil_inverse"

    # -- layout ------------------------------------------------------------

    @property
    def scheme(self):
        return "pencil-a2a"

    def _combo(self):
        """Mesh axis names the k-space y axis is sharded over, in
        transpose-nesting order ``(x, z, y)`` (size-1 axes dropped)."""
        names = self._names()
        return tuple(n for n in (names[0], names[2], names[1])
                     if n is not None)

    def k_spec(self, outer_axes=0):
        combo = self._combo()
        return P(*((None,) * outer_axes),
                 None, combo if combo else None, None)

    def k_sharding(self, outer_axes=0):
        """Natural pencil k layout: x local, y sharded over the
        combined ``(x, z, y)`` mesh axes, half-spectrum z local."""
        return NamedSharding(self.decomp.mesh, self.k_spec(outer_axes))

    def k_axis_array(self, mu, values):
        values = np.asarray(values)
        shape = [1, 1, 1]
        shape[mu] = len(values)
        spec = [None, None, None]
        combo = self._combo()
        if mu == 1 and combo:
            spec[1] = combo if len(combo) > 1 else combo[0]
        return jax.device_put(
            values.reshape(shape),
            NamedSharding(self.decomp.mesh, P(*spec)))

    # -- the shard_map transform -------------------------------------------

    def _a2a(self, blk, name, split, concat):
        """One pencil transpose: tiled ``all_to_all`` over mesh axis (or
        combined axis tuple) ``name``, on trailing-lattice axes."""
        with jax.named_scope("fft_transpose"):
            return lax.all_to_all(
                blk, name, blk.ndim - 3 + split, blk.ndim - 3 + concat,
                tiled=True)

    def _forward_stages(self):
        """``(transpose_or_None, fft_fn)`` pairs, in execution order,
        each operating on one field's local block (trailing 3 lattice
        axes)."""
        _, ay, az = self._names()
        combo = self._combo()
        fft1 = jnp.fft.rfft if self.is_real else jnp.fft.fft

        stages = []
        t_a = (lambda b: self._a2a(b, az, 0, 2)) if az else None
        stages.append((t_a, lambda b: fft1(b, axis=-1)))
        t_c = (lambda b: self._a2a(b, ay, 0, 1)) if ay else None
        stages.append((t_c, lambda b: jnp.fft.fft(b, axis=-2)))
        t_e = None
        if combo:
            cname = combo if len(combo) > 1 else combo[0]
            t_e = lambda b: self._a2a(b, cname, 1, 0)  # noqa: E731
        stages.append((t_e, lambda b: jnp.fft.fft(b, axis=-3)))
        return stages

    def _inverse_stages(self):
        """Mirror of :meth:`_forward_stages`: ``(fft_fn,
        transpose_or_None)`` pairs — each ``all_to_all`` inverted by
        swapping its split/concat axes."""
        _, ay, az = self._names()
        combo = self._combo()
        nz = self.grid_shape[-1]
        if self._matmul_inverse:
            ifft1 = lambda b: irfft_matmul(b, nz)  # noqa: E731
        elif self.is_real:
            ifft1 = lambda b: jnp.fft.irfft(b, n=nz, axis=-1)  # noqa: E731
        else:
            ifft1 = lambda b: jnp.fft.ifft(b, axis=-1)  # noqa: E731

        stages = []
        t_e = None
        if combo:
            cname = combo if len(combo) > 1 else combo[0]
            t_e = lambda b: self._a2a(b, cname, 0, 1)  # noqa: E731
        # real_inverse="matmul": XLA's inverse real transform is wrong
        # on the TPU (``dft.ifft_matmul``)
        ifft = ifft_matmul if self._matmul_inverse else (
            lambda b, axis: jnp.fft.ifft(b, axis=axis))
        stages.append(((lambda b: ifft(b, -3)), t_e))
        t_c = (lambda b: self._a2a(b, ay, 1, 0)) if ay else None
        stages.append(((lambda b: ifft(b, -2)), t_c))
        t_a = (lambda b: self._a2a(b, az, 2, 0)) if az else None
        stages.append((ifft1, t_a))
        return stages

    @staticmethod
    def _split_fields(x):
        """A batched block as a list of per-field blocks (trailing 3
        lattice axes each); scalars fields through unchanged."""
        outer = x.ndim - 3
        if outer == 0:
            return [x], ()
        oshape = x.shape[:outer]
        flat = x.reshape((-1,) + x.shape[outer:])
        return [flat[i] for i in range(flat.shape[0])], oshape

    @staticmethod
    def _join_fields(blocks, oshape):
        if not oshape:
            return blocks[0]
        return jnp.stack(blocks).reshape(oshape + blocks[0].shape)

    def transpose_plan(self):
        """:meth:`DFT.transpose_plan` of this tier: an ``all_to_all`` a
        mesh axis the transform crosses and one over all of them, the
        inverse its mirror; a field's k-space block is ``1 / P`` of the
        half spectrum at every stage."""
        forward = sum(t is not None for t, _ in self._forward_stages())
        inverse = sum(t is not None for _, t in self._inverse_stages())
        nbytes = (int(np.prod(self.shape(True)))
                  * np.dtype(self.cdtype).itemsize // self._nproc)
        return forward, inverse, nbytes if forward else 0

    def _forward_body(self, x):
        blocks, oshape = self._split_fields(x)
        for transpose, fft_fn in self._forward_stages():
            if transpose is None:
                with jax.named_scope("fft_stage"):
                    blocks = [fft_fn(b) for b in blocks]
                continue
            # pipeline: field k+1's transpose is ISSUED before field
            # k's local FFTs, handing the scheduler dependence-free
            # compute to hide the collective behind (single-field
            # transforms degrade to transpose-then-compute)
            out = []
            prev = transpose(blocks[0])
            for b in blocks[1:]:
                nxt = transpose(b)
                with jax.named_scope("fft_stage"):
                    out.append(fft_fn(prev))
                prev = nxt
            with jax.named_scope("fft_stage"):
                out.append(fft_fn(prev))
            blocks = out
        return self._join_fields(blocks, oshape)

    def _inverse_body(self, x):
        blocks, oshape = self._split_fields(x)
        for fft_fn, transpose in self._inverse_stages():
            out = []
            for b in blocks:
                # compute-then-issue: field k's transpose flies while
                # field k+1's local FFTs run (natural program order
                # already interleaves them)
                with jax.named_scope("fft_stage"):
                    y = fft_fn(b)
                out.append(transpose(y) if transpose is not None else y)
            blocks = out
        return self._join_fields(blocks, oshape)

    def _sm(self, direction, outer):
        """The shard_map-wrapped transform for ``outer`` leading
        unsharded field axes, cached per (direction, outer)."""
        key = (direction, outer)
        fn = self._sm_cache.get(key)
        if fn is None:
            decomp = self.decomp
            o = (None,) * outer
            home = P(*o, *self._names())
            nat = self.k_spec(outer)
            if direction == "fwd":
                fn = decomp.shard_map(self._forward_body,
                                      in_specs=home, out_specs=nat)
            else:
                fn = decomp.shard_map(self._inverse_body,
                                      in_specs=nat, out_specs=home)
            self._sm_cache[key] = fn
        return fn

    def _dft_impl(self, fx):
        if self._nproc == 1:
            with jax.named_scope("fft_stage"):
                return (jnp.fft.rfftn if self.is_real
                        else jnp.fft.fftn)(fx, axes=(-3, -2, -1))
        return self._sm("fwd", fx.ndim - 3)(fx)

    def _idft_impl(self, fk):
        if self._nproc == 1:
            with jax.named_scope("fft_stage"):
                if self._matmul_inverse:
                    return irfftn3(fk, self.grid_shape)
                if self.is_real:
                    return jnp.fft.irfftn(fk, s=self.grid_shape,
                                          axes=(-3, -2, -1))
                return jnp.fft.ifftn(fk, axes=(-3, -2, -1))
        return self._sm("inv", fk.ndim - 3)(fk)
