"""k-space projections: transverse/longitudinal/polarization decompositions
of vectors and transverse-traceless projection of rank-2 tensors.

TPU-native counterpart of /root/reference/pystella/fourier/projectors.py:
30-464. The reference builds seven loopy kernels; here each projection is a
pure jitted jnp function over the sharded k-space arrays (XLA fuses the
polarization-vector construction into each consumer). All projections are
implemented relative to *stencil-effective* momenta: ``effective_k(k, dx)``
with zero and Nyquist modes zeroed (projectors.py:67-86), so spectral
identities hold exactly for fields differentiated with the matching stencil.

Functional API: methods return new arrays rather than filling out-args.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from pystella_tpu.models.sectors import tensor_index
from pystella_tpu.obs import memory as _obs_memory
from pystella_tpu.obs.scope import trace_scope

__all__ = ["Projector", "tensor_index"]


class Projector:
    """k-space projector (see module docstring).

    :arg fft: a :class:`~pystella_tpu.fourier.DFT`.
    :arg effective_k: callable ``(k, dx) -> k_eff`` or an integer ``h``
        selecting :class:`~pystella_tpu.FirstCenteredDifference(h)`
        eigenvalues; ``0`` means continuum momenta.
    :arg dk: momentum-space grid spacing per axis.
    :arg dx: position-space grid spacing per axis.
    :arg scheme: transform-scheme override
        (:func:`~pystella_tpu.fourier.plan.ensure_spectral_fft`):
        ``"pencil"`` rebuilds the transform on the fully distributed
        pencil tier; projections are elementwise in k-space, so with
        the momentum constants in the transform's own layout (below)
        the TT-projection runs shard-local on any tier.
    """

    def __init__(self, fft, effective_k, dk, dx, scheme=None):
        from pystella_tpu.fourier.plan import (
            emit_spectra_plan, ensure_spectral_fft)
        fft = ensure_spectral_fft(fft, scheme)
        emit_spectra_plan("Projector", fft)
        self.fft = fft

        if not callable(effective_k):
            if effective_k != 0:
                from pystella_tpu.ops.derivs import FirstCenteredDifference
                effective_k = FirstCenteredDifference(
                    int(effective_k)).get_eigenvalues
            else:
                def effective_k(k, dx):  # noqa: ARG001
                    return k

        rdtype = fft.rdtype

        # stencil-effective momenta with zero & Nyquist modes zeroed
        # (reference projectors.py:77-86), placed in the TRANSFORM'S
        # k-space layout (fft.k_axis_array) so projections stay
        # elementwise/shard-local on every tier — the pencil tier keeps
        # x local and shards y over the combined mesh axes
        self.eff_mom = {}
        self._eff_dev = []
        for mu, (name, kk) in enumerate(zip(
                ("eff_mom_x", "eff_mom_y", "eff_mom_z"),
                fft.sub_k.values())):
            kk_int = kk.astype(int)
            eff = np.asarray(
                effective_k(dk[mu] * kk.astype(rdtype), dx[mu]), rdtype)
            eff[np.abs(kk_int) == fft.grid_shape[mu] // 2] = 0.0
            eff[kk_int == 0] = 0.0
            self.eff_mom[name] = eff
            self._eff_dev.append(fft.k_axis_array(mu, eff))

        self._transversify = jax.jit(self._transversify_impl)
        self._vec_to_pol = jax.jit(self._vec_to_pol_impl)
        self._pol_to_vec = jax.jit(self._pol_to_vec_impl)
        self._decompose_vector = jax.jit(self._decompose_vector_impl,
                                         static_argnums=1)
        self._decomp_to_vec = jax.jit(self._decomp_to_vec_impl,
                                      static_argnums=3)
        # the TT projection is the one projection of the main path
        # (spectra.gw, every -gws output): a program and a scope of its
        # own name, so a device trace reads jit_tt_project/...
        self._tt = _obs_memory.instrument_jit(
            self._tt_impl, label="fourier.tt_project")
        self._tensor_to_pol = jax.jit(self._tensor_to_pol_impl)
        self._pol_to_tensor = jax.jit(self._pol_to_tensor_impl)

    # -- shared geometry ---------------------------------------------------

    def _geometry(self):
        kx, ky, kz = self._eff_dev
        ksq = kx * kx + ky * ky + kz * kz
        kvec_zero = ksq < 1e-28  # all components < 1e-14 (projectors.py:101)
        ksq_safe = jnp.where(kvec_zero, 1.0, ksq)
        kmag = jnp.sqrt(ksq_safe)
        return (kx, ky, kz), kvec_zero, ksq_safe, kmag

    def _eps(self):
        """Transverse polarization vector ε (reference projectors.py:122-142):
        for kx=ky=0 use (1, i, 0)/sqrt(2) if kz != 0 else 0."""
        (kx, ky, kz), kvec_zero, ksq_safe, kmag = self._geometry()
        kap_sq = kx * kx + ky * ky
        kx_ky_zero = kap_sq < 1e-20  # both < 1e-10 (projectors.py:127-128)
        kz_nonzero = jnp.abs(kz) > 1e-10
        kappa_safe = jnp.sqrt(jnp.where(kx_ky_zero, 1.0, kap_sq))
        rt2 = np.sqrt(2.0)

        eps0 = jnp.where(
            kx_ky_zero,
            jnp.where(kz_nonzero, 1 / rt2, 0.0) + 0j,
            (kx * kz / kmag - 1j * ky) / kappa_safe / rt2)
        eps1 = jnp.where(
            kx_ky_zero,
            jnp.where(kz_nonzero, 1j / rt2, 0.0),
            (ky * kz / kmag + 1j * kx) / kappa_safe / rt2)
        eps2 = jnp.where(kx_ky_zero, 0.0 + 0j, -kappa_safe / kmag / rt2)
        return (eps0, eps1, eps2), kvec_zero, ksq_safe, kmag

    # -- implementations ---------------------------------------------------

    def _transversify_impl(self, vector):
        (kx, ky, kz), kvec_zero, ksq_safe, _ = self._geometry()
        kvec = (kx, ky, kz)
        div = sum(kvec[mu] * vector[mu] for mu in range(3))
        return jnp.stack([
            jnp.where(kvec_zero, 0.0,
                      vector[mu] - kvec[mu] / ksq_safe * div)
            for mu in range(3)])

    def _vec_to_pol_impl(self, vector):
        eps, *_ = self._eps()
        plus = sum(vector[mu] * jnp.conj(eps[mu]) for mu in range(3))
        minus = sum(vector[mu] * eps[mu] for mu in range(3))
        return plus, minus

    def _pol_to_vec_impl(self, plus, minus):
        eps, *_ = self._eps()
        return jnp.stack([plus * eps[mu] + minus * jnp.conj(eps[mu])
                          for mu in range(3)])

    def _decompose_vector_impl(self, vector, times_abs_k):
        eps, kvec_zero, ksq_safe, kmag = self._eps()
        (kx, ky, kz), *_ = self._geometry()
        kvec = (kx, ky, kz)
        plus = sum(vector[mu] * jnp.conj(eps[mu]) for mu in range(3))
        minus = sum(vector[mu] * eps[mu] for mu in range(3))
        div = sum(kvec[mu] * vector[mu] for mu in range(3))
        denom = kmag if times_abs_k else ksq_safe
        lng = jnp.where(kvec_zero, 0.0, -1j * div / denom)
        return plus, minus, lng

    def _decomp_to_vec_impl(self, plus, minus, lng, times_abs_k):
        eps, kvec_zero, ksq_safe, kmag = self._eps()
        (kx, ky, kz), *_ = self._geometry()
        kvec = (kx, ky, kz)
        out = []
        for mu in range(3):
            v = plus * eps[mu] + minus * jnp.conj(eps[mu])
            scale = kvec[mu] if times_abs_k else kvec[mu] / kmag
            v = v + jnp.where(kvec_zero, 0.0, 1j * scale * lng)
            out.append(v)
        return jnp.stack(out)

    def _tt_impl(self, hij):
        (kx, ky, kz), kvec_zero, ksq_safe, kmag = self._geometry()
        khat = tuple(k / kmag for k in (kx, ky, kz))

        def tid(a, b):
            return tensor_index(a, b)

        P = {}
        for a in range(1, 4):
            for b in range(a, 4):
                delta = 1.0 if a == b else 0.0
                P[tid(a, b)] = delta - khat[a - 1] * khat[b - 1]

        def P_(a, b):
            return P[tid(a, b)]

        out = []
        for a in range(1, 4):
            for b in range(a, 4):
                acc = 0.0
                for c in range(1, 4):
                    for d in range(1, 4):
                        acc = acc + (P_(a, c) * P_(d, b)
                                     - P_(a, b) * P_(c, d) / 2) * hij[tid(c, d)]
                out.append(jnp.where(kvec_zero, 0.0, acc))
        return jnp.stack(out)

    def _tensor_to_pol_impl(self, hij):
        eps, *_ = self._eps()
        plus = sum(hij[tensor_index(c, d)] * jnp.conj(eps[c - 1])
                   * jnp.conj(eps[d - 1])
                   for c in range(1, 4) for d in range(1, 4))
        minus = sum(hij[tensor_index(c, d)] * eps[c - 1] * eps[d - 1]
                    for c in range(1, 4) for d in range(1, 4))
        return plus, minus

    def _pol_to_tensor_impl(self, plus, minus):
        eps, *_ = self._eps()
        return jnp.stack([
            plus * eps[a - 1] * eps[b - 1]
            + minus * jnp.conj(eps[a - 1]) * jnp.conj(eps[b - 1])
            for a in range(1, 4) for b in range(a, 4)])

    # -- public API (functional versions of projectors.py:238-464) ---------

    def transversify(self, vector, vector_T=None, queue=None):
        """Project out the longitudinal component: returns
        ``v - k (k·v)/k²`` (zero where k = 0)."""
        return self._transversify(vector)

    def vec_to_pol(self, vector, queue=None):
        """Project a vector onto the (plus, minus) polarization basis;
        returns ``(plus, minus)``."""
        return self._vec_to_pol(vector)

    def pol_to_vec(self, plus, minus, queue=None):
        """Build the vector field from its (plus, minus) polarizations;
        returns the ``(3,)+kshape`` array."""
        return self._pol_to_vec(plus, minus)

    def decompose_vector(self, vector, *, times_abs_k=False, queue=None):
        """Full decomposition; returns ``(plus, minus, lng)`` where the
        longitudinal mode is ``-i k·v / |k|²`` (or ``-i k·v / |k|`` with
        ``times_abs_k``)."""
        return self._decompose_vector(vector, times_abs_k)

    def decomp_to_vec(self, plus, minus, lng, *, times_abs_k=False,
                      queue=None):
        """Inverse of :meth:`decompose_vector`."""
        return self._decomp_to_vec(plus, minus, lng, times_abs_k)

    def transverse_traceless(self, hij, hij_TT=None, queue=None):
        """Transverse-traceless projection of a packed symmetric tensor
        ``(6,)+kshape``: ``(P_ac P_db - P_ab P_cd / 2) h_cd``."""
        with trace_scope("tt_project"):
            return self._tt(hij)

    def tensor_to_pol(self, hij, queue=None):
        """Project a tensor onto polarizations; returns ``(plus, minus)``."""
        return self._tensor_to_pol(hij)

    def pol_to_tensor(self, plus, minus, queue=None):
        """Build the packed tensor from its polarizations."""
        return self._pol_to_tensor(plus, minus)
