"""Finite-difference operators on sharded 3-D lattices.

TPU-native counterpart of /root/reference/pystella/derivs.py:37-470. The
reference expands symbolic stencils into loopy kernels with local-memory
prefetch; here each operator is a jitted ``shard_map`` body that (1) pads its
local block with periodic halos via ``lax.ppermute`` (one neighbor exchange
per sharded axis, fused with the compute — the analog of
``decomp.share_halos`` + Stencil kernel in derivs.py:412-415) and (2) applies
the stencil as shifted static slices of the padded block, which XLA fuses
into a single VPU loop. A ``mode="roll"`` variant expresses the stencil as
``jnp.roll`` on the global sharded array and lets XLA infer the collectives.

The stencil coefficient tables and the *stencil eigenvalues* (load-bearing
for projector/Poisson consistency; reference derivs.py:127-191) are
reproduced exactly.
"""

from __future__ import annotations

import logging

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from pystella_tpu.obs import memory as _obs_memory
from pystella_tpu.obs.scope import host_span

logger = logging.getLogger(__name__)

__all__ = [
    "FirstCenteredDifference", "SecondCenteredDifference",
    "FiniteDifferencer", "expand_stencil", "centered_diff",
]


def expand_stencil(f, coefs):
    """Expand a symbolic stencil over a field: ``sum_s coefs[s] * f@s``
    where ``s`` ranges over 3-tuple site offsets (reference
    ``pystella.derivs.expand_stencil``, derivs.py:37-58). The result
    evaluates to periodic rolls via :func:`pystella_tpu.field.evaluate` —
    useful for custom operators without touching the Pallas/halo tiers."""
    from pystella_tpu.field import shift_fields
    return sum(c * shift_fields(f, offset) for offset, c in coefs.items())


def centered_diff(f, coefs, direction, order):
    """Centered-difference stencil from its non-redundant coefficients:
    ``direction`` in (1, 2, 3) picks the axis, ``order``'s parity sets the
    sign of the mirrored coefficients (reference
    ``pystella.derivs.centered_diff``, derivs.py:61-108)."""
    all_coefs = {}
    for s, c in coefs.items():
        offset = [0, 0, 0]
        if s != 0 or order % 2 == 0:
            offset[direction - 1] = s
            all_coefs[tuple(offset)] = c
        if s != 0:
            offset = [0, 0, 0]
            offset[direction - 1] = -s
            all_coefs[tuple(offset)] = (-1) ** order * c
    return expand_stencil(f, all_coefs)


class FiniteDifferenceStencil:
    """Base class bundling centered-difference coefficients and analytic
    eigenvalues (reference derivs.py:111-124)."""

    #: dict: offset (>0) → coefficient; offset 0 included for even order
    coefs = NotImplemented
    truncation_order = NotImplemented
    order = NotImplemented

    def get_eigenvalues(self, k, dx):
        raise NotImplementedError


# first-derivative coefficients, truncation order 2h (derivs.py:127-131)
_grad_coefs = {
    1: {1: 1 / 2},
    2: {1: 8 / 12, 2: -1 / 12},
    3: {1: 45 / 60, 2: -9 / 60, 3: 1 / 60},
    4: {1: 672 / 840, 2: -168 / 840, 3: 32 / 840, 4: -3 / 840},
}

# second-derivative coefficients (derivs.py:160-165)
_lap_coefs = {
    1: {0: -2.0, 1: 1.0},
    2: {0: -30 / 12, 1: 16 / 12, 2: -1 / 12},
    3: {0: -490 / 180, 1: 270 / 180, 2: -27 / 180, 3: 2 / 180},
    4: {0: -14350 / 5040, 1: 8064 / 5040, 2: -1008 / 5040,
        3: 128 / 5040, 4: -9 / 5040},
}


def stencil_radius(h, who):
    """``h`` as the radius of a centred stencil, or a ``ValueError``
    that says what the coefficient tables hold (a ``KeyError`` of
    ``_lap_coefs[5]`` told a caller nothing)."""
    if h not in _lap_coefs:
        raise ValueError(
            f"{who}: halo_shape {h!r} is not a stencil radius the "
            f"coefficient tables hold: 1-{max(_lap_coefs)} (order 2-"
            f"{2 * max(_lap_coefs)}; upstream's --halo-shape takes 0-"
            f"{max(_lap_coefs)}, and 0, spectral derivatives, is "
            "SpectralCollocator's, not a stencil's)")
    return int(h)


class FirstCenteredDifference(FiniteDifferenceStencil):
    """Antisymmetric centered first difference of order ``2h``
    (reference derivs.py:134-157)."""

    order = 1

    def __init__(self, h):
        self.h = h = stencil_radius(h, type(self).__name__)
        self.coefs = _grad_coefs[h]
        self.truncation_order = 2 * h

    def get_eigenvalues(self, k, dx):
        """Effective wavenumber of the stencil applied to a plane wave:
        the stencil maps ``exp(i k x)`` to ``i * eff_k * exp(i k x)``."""
        th = np.asarray(k) * dx
        return sum(2 * c * np.sin(s * th) for s, c in self.coefs.items()) / dx


class SecondCenteredDifference(FiniteDifferenceStencil):
    """Symmetric centered second difference of order ``2h``
    (reference derivs.py:168-191)."""

    order = 2

    def __init__(self, h):
        self.h = h = stencil_radius(h, type(self).__name__)
        self.coefs = _lap_coefs[h]
        self.truncation_order = 2 * h

    def get_eigenvalues(self, k, dx):
        """Effective ``-k**2``: the stencil maps ``exp(i k x)`` to
        ``eig * exp(i k x)`` (negative semidefinite)."""
        th = np.asarray(k) * dx
        eig = self.coefs[0] * np.ones_like(th)
        eig = eig + sum(2 * c * np.cos(s * th)
                        for s, c in self.coefs.items() if s != 0)
        return eig / dx**2


def _shifted(x, axis, offset, h):
    """Static slice of halo-padded ``x`` at stencil offset ``offset`` along
    lattice ``axis`` (padded width h on each side)."""
    n = x.shape[axis] - 2 * h
    return lax.slice_in_dim(x, h + offset, h + offset + n, axis=axis)


def _apply_centered(x, axis, coefs, h, order, inv_dx):
    """Apply a centered 1-D stencil along ``axis`` of the halo-padded ``x``."""
    sgn = (-1) ** order
    acc = None
    for s, c in sorted(coefs.items()):
        if s == 0:
            term = c * _shifted(x, axis, 0, h)
        else:
            plus = _shifted(x, axis, s, h)
            minus = _shifted(x, axis, -s, h)
            term = c * (plus + sgn * minus)
        acc = term if acc is None else acc + term
    return acc * inv_dx


class FiniteDifferencer:
    """Gradient/Laplacian/divergence operators (reference
    ``FiniteDifferencer``, derivs.py:194-470), functional: they return new
    arrays instead of writing into passed-in output buffers.

    :arg decomp: a :class:`~pystella_tpu.DomainDecomposition`.
    :arg halo_shape: the stencil radius ``h`` (1..4 → order 2..8; any
        other is a ``ValueError`` of the stencil classes that names the
        tables' range).
    :arg dx: lattice spacing per axis (scalar or 3-tuple).
    :arg mode: ``"pallas"`` (streaming Pallas stencil kernels — the fast
        TPU path, default on unsharded lattices), ``"halo"`` (shard_map +
        ppermute halos, XLA stencils) or ``"roll"`` (global jnp.roll; XLA
        infers collectives). ``"auto"`` picks pallas when the lattice y/z
        axes are unsharded, else halo.
    :arg overlap: overlap the halo exchange with interior compute on
        sharded meshes (interior/shell split — bit-exact with the padded
        path; see :mod:`pystella_tpu.parallel.overlap`). ``None``
        resolves ``PYSTELLA_HALO_OVERLAP`` / auto (on when the mesh is
        sharded). Applies to the halo-mode XLA stencils (any sharded
        axes) and to x-sharded pallas-mode kernels; infeasible
        configurations fall back to the padded path.
    """

    def __init__(self, decomp, halo_shape, dx, *, rank_shape=None,
                 first_stencil_factory=FirstCenteredDifference,
                 stencil_factory=SecondCenteredDifference,
                 mode="auto", overlap=None, **kwargs):
        from pystella_tpu.parallel import overlap as _overlap
        self.decomp = decomp
        self.overlap = _overlap.enabled(decomp, override=overlap)
        self.h = int(halo_shape)
        if np.isscalar(dx):
            dx = (dx,) * 3
        self.dx = tuple(float(d) for d in dx)
        self.first = first_stencil_factory(self.h)
        self.second = stencil_factory(self.h)
        if mode == "auto":
            # pallas only on TPU (Mosaic is TPU-only; on CPU it would run
            # in slow interpret mode — tests opt in explicitly)
            pz = decomp.proc_shape[2]
            mode = "pallas" if (jax.default_backend() == "tpu"
                                and pz == 1 and self.h <= 8) else "halo"
            logger.info(
                "FiniteDifferencer(h=%d, proc_shape=%s): mode='auto' "
                "selected the %s path on backend %s", self.h,
                decomp.proc_shape, mode, jax.default_backend())
        if mode not in ("halo", "roll", "pallas"):
            raise ValueError(f"unknown mode {mode}")
        if mode == "pallas" and decomp.proc_shape[2] != 1:
            raise ValueError(
                "pallas mode supports x/y sharding only (the z axis is "
                "the VMEM lane dimension); use halo mode")
        self.mode = mode
        self._sharded_cache = {}
        self._pallas_infeasible = set()

    # -- eigenvalues (consumed by fourier/) --------------------------------

    def get_eigenvalues(self, k, dx, order=1):
        stencil = self.first if order == 1 else self.second
        return stencil.get_eigenvalues(k, dx)

    # -- local-block stencil bodies ----------------------------------------
    #
    # Each op is a *core* acting on a halo-padded block plus a thin
    # wrapper that routes it through ``decomp.overlap_stencil`` — with
    # overlap on (sharded meshes), the ppermutes are issued first, the
    # interior inset is computed from local data while the collectives
    # fly, and the boundary shells are stitched once halos land;
    # otherwise the same core runs once on the padded block. Both paths
    # are bit-exact (identical taps and per-element reduction order).

    def _stencil(self, x, axes, core, overlap=None):
        halo = tuple(self.h if d in axes else 0 for d in range(3))
        return self.decomp.overlap_stencil(
            x, halo, core,
            overlap=self.overlap if overlap is None else overlap)

    def _grad_core(self, padded):
        la = padded.ndim - 3  # first lattice axis
        parts = []
        for d in range(3):
            y = padded
            # strip halos on the other two axes before slicing this one
            for other in range(3):
                if other != d:
                    y = _shifted(y, la + other, 0, self.h)
            parts.append(_apply_centered(y, la + d, self.first.coefs,
                                         self.h, 1, 1 / self.dx[d]))
        return jnp.stack(parts, axis=la)

    def _local_grad(self, x):
        return self._stencil(x, (0, 1, 2), self._grad_core)

    def _lap_core(self, padded):
        la = padded.ndim - 3
        acc = None
        for d in range(3):
            y = padded
            for other in range(3):
                if other != d:
                    y = _shifted(y, la + other, 0, self.h)
            term = _apply_centered(y, la + d, self.second.coefs,
                                   self.h, 2, 1 / self.dx[d]**2)
            acc = term if acc is None else acc + term
        return acc

    def _local_lap(self, x):
        return self._stencil(x, (0, 1, 2), self._lap_core)

    def _grad_lap_core(self, padded):
        la = padded.ndim - 3
        grads, lap = [], None
        for d in range(3):
            y = padded
            for other in range(3):
                if other != d:
                    y = _shifted(y, la + other, 0, self.h)
            grads.append(_apply_centered(y, la + d, self.first.coefs,
                                         self.h, 1, 1 / self.dx[d]))
            term = _apply_centered(y, la + d, self.second.coefs,
                                   self.h, 2, 1 / self.dx[d]**2)
            lap = term if lap is None else lap + term
        return jnp.stack(grads, axis=la), lap

    def _local_grad_lap(self, x):
        return self._stencil(x, (0, 1, 2), self._grad_lap_core)

    def _local_pd(self, x, d, overlap=None):
        def pd_core(padded, d=d):
            la = padded.ndim - 3
            return _apply_centered(padded, la + d, self.first.coefs,
                                   self.h, 1, 1 / self.dx[d])
        return self._stencil(x, (d,), pd_core, overlap=overlap)

    def _local_div(self, v):
        # v: (..., 3, nx, ny, nz) local block; divergence = sum_d pd_d(v[d])
        #
        # kept on the PADDED path even with overlap on: each component's
        # derivative is exchanged along a different axis, so the three
        # stitched terms carry mismatched concat boundaries — summing
        # them lets XLA re-fuse (and re-contract FMAs) differently per
        # intersection piece, breaking the bit-exactness contract at the
        # 1-ulp level (measured on the CPU backend). A single split
        # would need the whole vector padded on all three axes — 3x the
        # ICI bytes — for an operator that is not on the hot path.
        la = v.ndim - 3
        acc = None
        for d in range(3):
            comp = lax.index_in_dim(v, d, axis=la - 1, keepdims=False)
            term = self._local_pd(comp, d, overlap=False)
            acc = term if acc is None else acc + term
        return acc

    # -- roll-mode bodies (global arrays) ----------------------------------

    def _roll_apply(self, x, axis, coefs, order, inv_dx):
        sgn = (-1) ** order
        acc = None
        for s, c in sorted(coefs.items()):
            if s == 0:
                term = c * x
            else:
                term = c * (jnp.roll(x, -s, axis)
                            + sgn * jnp.roll(x, s, axis))
            acc = term if acc is None else acc + term
        return acc * inv_dx

    # -- public ops --------------------------------------------------------

    def _sharded(self, name, outer_axes, extra_out_axis=False,
                 vector_in=False):
        key = (name, outer_axes, extra_out_axis, vector_in)
        cached = self._sharded_cache.get(key)
        if cached is not None:
            return cached
        fn = {"grad": self._local_grad, "lap": self._local_lap,
              "grad_lap": self._local_grad_lap, "div": self._local_div,
              "pdx": lambda x: self._local_pd(x, 0),
              "pdy": lambda x: self._local_pd(x, 1),
              "pdz": lambda x: self._local_pd(x, 2)}[name]
        in_spec = self.decomp.spec(outer_axes + (1 if vector_in else 0))
        out_spec = self.decomp.spec(outer_axes + (1 if extra_out_axis else 0))
        if name == "grad_lap":
            out_spec = (out_spec, self.decomp.spec(outer_axes))
        result = _obs_memory.instrument_jit(
            self.decomp.shard_map(fn, in_spec, out_spec),
            label=f"derivs.{name}")
        self._sharded_cache[key] = result
        return result

    def _dispatch(self, name, x, extra_out_axis=False, vector_in=False):
        outer = x.ndim - 3 - (1 if vector_in else 0)
        if self.mode == "roll":
            return self._roll_dispatch(name, x)
        if self.mode == "pallas":
            return self._pallas_dispatch(name, x, vector_in)
        return self._sharded(name, outer, extra_out_axis, vector_in)(x)

    # -- pallas-mode bodies (streaming VMEM-window kernels) -----------------

    def _pallas_bodies(self, name, n_out):
        """Kernel body for op ``name`` on a window of ``C`` components
        (``C = 3*n_out`` for divergence)."""
        inv_dx = [1.0 / d for d in self.dx]
        inv_dx2 = [1.0 / d**2 for d in self.dx]
        first, second = self.first.coefs, self.second.coefs

        from pystella_tpu.ops.pallas_stencil import (
            grad_from_taps, lap_from_taps)

        def off(d, s):
            o = [0, 0, 0]
            o[d] = s
            return o

        def lap_of(taps):
            return lap_from_taps(taps, second, inv_dx2)

        def grad_of(taps):
            return jnp.stack(grad_from_taps(taps, first, inv_dx), axis=1)

        if name == "lap":
            return lambda taps, e, s: {"lap": lap_of(taps)}
        if name == "grad":
            return lambda taps, e, s: {"grad": grad_of(taps)}
        if name == "grad_lap":
            return lambda taps, e, s: {"grad": grad_of(taps),
                                       "lap": lap_of(taps)}
        if name in ("pdx", "pdy", "pdz"):
            d = {"pdx": 0, "pdy": 1, "pdz": 2}[name]

            def pd_body(taps, e, s, d=d):
                acc = 0
                for st, c in first.items():
                    acc = acc + c * inv_dx[d] * (taps(*off(d, st))
                                                 - taps(*off(d, -st)))
                return {"pd": acc}
            return pd_body
        if name == "div":
            def div_body(taps, e, s):
                acc = 0
                for d in range(3):
                    for st, c in first.items():
                        diffv = taps(*off(d, st)) - taps(*off(d, -st))
                        sel = diffv.reshape((n_out, 3)
                                            + diffv.shape[1:])[:, d]
                        acc = acc + c * inv_dx[d] * sel
                return {"div": acc}
            return div_body
        raise ValueError(name)

    def _pallas_op(self, name, n_comp, dtype, vector_in, global_shape):
        from pystella_tpu.ops.pallas_stencil import (
            ResidentStencil, StreamingStencil)

        key = ("pallas", name, n_comp, str(dtype), vector_in, global_shape)
        cached = self._sharded_cache.get(key)
        if cached is not None:
            return cached

        px, py = self.decomp.proc_shape[:2]
        # rank_shape validates divisibility (a non-divisible grid raises
        # the ValueError _pallas_dispatch turns into the halo fallback)
        local_shape = self.decomp.rank_shape(global_shape)
        n_out = n_comp // 3 if vector_in else n_comp
        out_defs = {"lap": {"lap": (n_out,)},
                    "grad": {"grad": (n_out, 3)},
                    "grad_lap": {"grad": (n_out, 3), "lap": (n_out,)},
                    "pdx": {"pd": (n_out,)}, "pdy": {"pd": (n_out,)},
                    "pdz": {"pd": (n_out,)},
                    "div": {"div": (n_out,)}}[name]
        body = self._pallas_bodies(name, n_out)
        try:
            st = StreamingStencil(local_shape, {"f": n_comp}, self.h, body,
                                  out_defs, dtype=dtype, kind=name,
                                  x_slab=(px > 1), y_slab=(py > 1))
        except ValueError:
            if px > 1 or py > 1:
                raise  # resident kernels assume local periodicity
            # streaming infeasible (Z below the 128-lane tile, or no
            # blocking): whole-lattice-resident kernel — all-roll taps,
            # no windowed DMAs (fixes the wave-64^3-class cliff)
            st = ResidentStencil(local_shape, {"f": n_comp}, self.h, body,
                                 out_defs, dtype=dtype)

        if px > 1 or py > 1:
            from pystella_tpu.ops.pallas_stencil import (
                OverlapStreamingStencil)
            decomp = self.decomp
            # x-sharded windows admit the interior/shell launch split
            # (y shells have no legal sublane blocking); the event says
            # which path the operator takes, and why
            ov = OverlapStreamingStencil.plan_for(
                st, self.h, enabled=self.overlap,
                label=type(self).__name__)

            def sharded_fn(x):
                if ov is not None:
                    return tuple(ov(x, decomp).values())
                # the shard itself is the window operand; its edges are
                # ppermuted h-row slabs (no padded copy)
                return tuple(
                    st(x, slabs=st.halo_slabs(decomp, x)).values())

            in_spec = decomp.spec(1)
            out_specs = tuple(
                decomp.spec(len(lead)) for lead in out_defs.values())
            fn = _obs_memory.instrument_jit(
                decomp.shard_map(
                    sharded_fn, in_spec,
                    out_specs if len(out_specs) > 1 else out_specs[0],
                    check_vma=False),
                label=f"derivs.{name}")

            def call(x, fn=fn):
                res = fn(x)
                if not isinstance(res, tuple):
                    res = (res,)
                return dict(zip(out_defs, res))
        else:
            call = st

        self._sharded_cache[key] = call
        return call

    def _pallas_dispatch(self, name, x, vector_in=False):
        # flatten outer axes (and the vector axis for div) into one
        # component axis for the window
        lat = tuple(x.shape[-3:])
        outer = x.shape[:-3]
        n_comp = int(np.prod(outer)) if outer else 1
        fallback_key = (name, n_comp, str(x.dtype), vector_in, lat)
        if fallback_key in self._pallas_infeasible:
            op = None
        else:
            try:
                op = self._pallas_op(name, n_comp, x.dtype, vector_in, lat)
            except ValueError as err:
                # no feasible (bx, by) blocking for this lattice (e.g. axes
                # not divisible by any block size): fall back to the XLA
                # halo path, warning once per (op, shape) — not per call
                logger.warning(
                    "pallas %s kernel infeasible for lattice %s (%s); "
                    "falling back to the shard_map+halo XLA path for this "
                    "operator", name, lat, err)
                self._pallas_infeasible.add(fallback_key)
                op = None
        if op is None:
            n_outer = len(outer) - (1 if vector_in else 0)
            extra = name in ("grad", "grad_lap")
            return self._sharded(name, n_outer, extra, vector_in)(x)
        xf = x.reshape((n_comp,) + lat)
        res = op(xf)
        n_out = n_comp // 3 if vector_in else n_comp
        out_outer = outer[:-1] if vector_in else outer

        def unflatten(arr, lead):
            return arr.reshape(tuple(out_outer) + tuple(lead[1:])
                               + tuple(arr.shape[-3:]))

        if name == "grad_lap":
            lead = {"grad": (n_out, 3), "lap": (n_out,)}
            return (unflatten(res["grad"], lead["grad"]),
                    unflatten(res["lap"], lead["lap"]))
        out_name = next(iter(res))
        lead = {"lap": (n_out,), "grad": (n_out, 3), "pd": (n_out,),
                "div": (n_out,)}[out_name]
        return unflatten(res[out_name], lead)

    def _roll_dispatch(self, name, x):
        la = x.ndim - 3
        if name == "lap":
            return sum(self._roll_apply(x, la + d, self.second.coefs, 2,
                                        1 / self.dx[d]**2) for d in range(3))
        if name == "grad":
            return jnp.stack([
                self._roll_apply(x, la + d, self.first.coefs, 1,
                                 1 / self.dx[d]) for d in range(3)], axis=la)
        if name == "grad_lap":
            return self._roll_dispatch("grad", x), self._roll_dispatch("lap", x)
        if name in ("pdx", "pdy", "pdz"):
            d = {"pdx": 0, "pdy": 1, "pdz": 2}[name]
            return self._roll_apply(x, la + d, self.first.coefs, 1,
                                    1 / self.dx[d])
        if name == "div":
            return sum(self._roll_apply(
                lax.index_in_dim(x, d, axis=la - 1, keepdims=False),
                la - 1 + d, self.first.coefs, 1, 1 / self.dx[d])
                for d in range(3))
        raise ValueError(name)

    def lap(self, f):
        """Laplacian of ``f`` (lattice axes trailing)."""
        with host_span("lap_dispatch"):
            return self._dispatch("lap", f)

    def grad(self, f):
        """Gradient; inserts a length-3 component axis before the lattice
        axes (matching the reference's ``pd`` field layout,
        /root/reference/pystella/field/__init__.py:250-258)."""
        with host_span("grad_dispatch"):
            return self._dispatch("grad", f, extra_out_axis=True)

    def grad_lap(self, f):
        """Fused gradient + Laplacian: one halo exchange, one pass."""
        return self._dispatch("grad_lap", f, extra_out_axis=True)

    def pdx(self, f):
        return self._dispatch("pdx", f)

    def pdy(self, f):
        return self._dispatch("pdy", f)

    def pdz(self, f):
        return self._dispatch("pdz", f)

    def divergence(self, vec):
        """Divergence of a vector field with component axis just before the
        lattice axes (reference derivs.py:431-470)."""
        return self._dispatch("div", vec, vector_in=True)

    def __call__(self, fx, *, lap=False, grd=False, div=False):
        """Batch interface echoing the reference's out-kwarg style
        (derivs.py:339-429) but functional: returns a dict of results for the
        requested outputs."""
        out = {}
        if lap and grd:
            g, lp = self.grad_lap(fx)
            out["grd"], out["lap"] = g, lp
        elif lap:
            out["lap"] = self.lap(fx)
        elif grd:
            out["grd"] = self.grad(fx)
        if div:
            out["div"] = self.divergence(fx)
        return out
