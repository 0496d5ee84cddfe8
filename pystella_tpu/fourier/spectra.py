"""Radially-binned power spectra.

TPU-native counterpart of /root/reference/pystella/fourier/spectra.py:29-419.
The reference bins ``|f(k)|²`` with an atomic histogram kernel plus MPI
allreduce; here the binned sums are the one-hot contraction of
:mod:`pystella_tpu.ops.histogram` on each device's shard inside
``shard_map`` (deterministic, no atomics): float32 partials of at most
2**18 modes each, fetched unreduced and summed on the host in float64 —
there is no device-side ``psum``. All conventions are preserved: bin
index ``round(|k| / bin_width)``, r2c double-count weighting (2 except
on the ``kz ∈ {0, Nyquist}`` planes, spectra.py:81-87,112-119),
bin-count normalization, and the overall ``1/(2π²V) · (d³x)²``
normalization (spectra.py:74-75).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from pystella_tpu.fourier.projectors import tensor_index
from pystella_tpu.obs.scope import host_span

__all__ = ["PowerSpectra"]


class PowerSpectra:
    """Power spectra of scalar, vector, and tensor fields.

    :arg decomp: a :class:`~pystella_tpu.DomainDecomposition`.
    :arg fft: a :class:`~pystella_tpu.fourier.DFT` (or
        :class:`~pystella_tpu.fourier.pencil.PencilFFT`).
    :arg dk: momentum-space grid spacing per axis.
    :arg volume: physical grid volume.
    :arg bin_width: defaults to ``min(dk)``.
    :arg scheme: transform-scheme override
        (:func:`~pystella_tpu.fourier.plan.ensure_spectral_fft`):
        ``"pencil"`` rebuilds the transform on the fully distributed
        shard_map pencil tier, whose spectra then run shard-local end
        to end — transform, ``|f(k)|²`` weighting, and per-device
        binning in ONE jitted dispatch, with only the ``num_bins``
        scalar partials crossing devices at finalize time. Default:
        the ``PYSTELLA_FFT_SCHEME`` env (``auto`` keeps the transform
        as passed).
    """

    def __init__(self, decomp, fft, dk, volume, **kwargs):
        from pystella_tpu.fourier.plan import (
            emit_spectra_plan, ensure_spectral_fft)
        fft = ensure_spectral_fft(fft, kwargs.pop("scheme", None))
        emit_spectra_plan("PowerSpectra", fft)
        self.decomp = decomp
        self.fft = fft
        self.grid_shape = fft.grid_shape
        self.dtype = fft.dtype
        self.rdtype = fft.rdtype
        self.cdtype = fft.cdtype
        self.kshape = fft.shape(True)
        self.dk = dk
        self.bin_width = kwargs.pop("bin_width", min(dk))

        d3x = volume / np.prod(self.grid_shape)
        self.norm = (1 / 2 / np.pi**2 / volume) * d3x**2

        sub_k = list(fft.sub_k.values())
        kvecs = np.meshgrid(*sub_k, indexing="ij", sparse=False)
        kmags = np.sqrt(sum((dki * ki)**2 for dki, ki in zip(self.dk, kvecs)))

        # float64 whatever the field dtype: np.histogram accumulates its
        # weights in THEIR dtype, and float32 stops counting by ones at
        # 2**24 modes — at 512**3 the sparse corner bins came out rounded
        # to multiples of 8 and the last one (6 modes) as 0, an inf in
        # every spectrum
        if fft.is_real:
            counts = np.full(kmags.shape, 2.0, np.float64)
            counts[kvecs[2] == 0] = 1.0
            counts[kvecs[2] == self.grid_shape[-1] // 2] = 1.0
        else:
            counts = np.ones(kmags.shape, np.float64)

        max_k = np.max(kmags)
        self.num_bins = int(max_k / self.bin_width + 0.5) + 1
        bins = np.arange(-0.5, self.num_bins + 0.5) * self.bin_width
        self.bin_counts = np.histogram(kmags, weights=counts, bins=bins)[0]

        # device-side bin indices and count weights, sharded like k-space
        # (x/y as the decomposition, half-spectrum z axis local)
        sharding = fft.k_sharding(0)
        bin_idx = np.round(kmags / self.bin_width).astype(np.int32)
        self._bin_idx = jax.device_put(bin_idx, sharding)
        self._counts = jax.device_put(
            counts.astype(self.rdtype), sharding)
        self._kmags = jax.device_put(
            kmags.astype(self.rdtype), sharding)

        # the sharded k-arrays are jit ARGUMENTS, not closure captures:
        # multi-controller jax forbids closing over arrays that span
        # non-addressable devices (exercised by tests/multihost_worker.py)
        def weights_impl(fk, k_power, counts, kmags, bin_idx):
            w = counts * kmags**k_power * jnp.abs(fk)**2
            b = jnp.broadcast_to(bin_idx, w.shape)
            return b, w

        from pystella_tpu.obs import memory as _obs_memory
        jitted = _obs_memory.instrument_jit(
            weights_impl, label="fourier.spectra_bin_weights")
        self._weights = lambda fk, k_power: jitted(
            fk, k_power, self._counts, self._kmags, self._bin_idx)
        #: one-dispatch (transform + weights + shard-local binning)
        #: spectrum programs, keyed (outer_shape, k_power) — the pencil
        #: tier's end-to-end path (built lazily in _spectrum_fn)
        self._spectrum_cache = {}

    def _spectrum_fn(self, outer_shape, k_power):
        """The fused pencil-tier spectrum program: ONE jitted dispatch
        from the position-space field to per-device partial bin sums —
        the distributed transform (explicit all_to_all transposes), the
        ``counts·|k|^p·|f(k)|²`` weighting, and the per-device binning
        kernel all in one module, shard-local throughout; only the
        ``num_bins``-scalar partials leave the devices (the binning
        "all-reduce" finalized on host in wide precision). The sharded
        k-constants ride as arguments, not captures (multi-controller
        rule, as for ``_weights``)."""
        key = (tuple(outer_shape), int(k_power))
        fn = self._spectrum_cache.get(key)
        if fn is not None:
            return fn
        from pystella_tpu.ops.histogram import bincount_core
        core = bincount_core(
            self.decomp, tuple(outer_shape), self.num_bins, True,
            lattice_names=tuple(self.fft.k_sharding(0).spec))
        kp = int(k_power)

        def impl(fx, counts, kmags, bin_idx):
            fk = self.fft._dft_impl(fx)
            w = counts * kmags**kp * jnp.abs(fk)**2
            b = jnp.broadcast_to(bin_idx, w.shape)
            return core(b, w)

        from pystella_tpu.obs import memory as _obs_memory
        fn = _obs_memory.instrument_jit(
            impl, label=f"fourier.spectra_pencil_k{kp}")
        self._spectrum_cache[key] = fn
        return fn

    def spectrum_program(self, outer_shape=(), k_power=3):
        """``(jitted_fn, k_args)`` of the fused pencil-tier spectrum
        program for ``outer_shape`` leading field axes — call as
        ``fn(fx, *k_args)``. Exposed so the lint IR audit (and the
        smoke driver) can lower and audit the very program the pencil
        tier dispatches: its compiled module must carry only
        ``all-to-all`` transpose collectives — an all-gather of a
        field-sized operand there means the transform replicated."""
        fn = self._spectrum_fn(tuple(outer_shape), k_power)
        return fn, (self._counts, self._kmags, self._bin_idx)

    def _pencil_spectrum(self, fx, k_power):
        """Dispatch the fused program and finalize on host (exact
        analog of ``weighted_bincount``'s wide-precision finalize)."""
        from pystella_tpu.ops.histogram import fetch_partials
        outer_shape = tuple(fx.shape[:-3])
        fn = self._spectrum_fn(outer_shape, k_power)
        with host_span("spectra_dispatch"):
            partials = fn(fx, self._counts, self._kmags, self._bin_idx)
        with host_span("spectra_fetch"):
            partials = fetch_partials(partials)
        h = partials.astype(np.float64).sum(axis=0)
        hist = h.reshape(outer_shape + (self.num_bins,))
        return self.norm * (hist / self.bin_counts)

    def bin_power(self, fk, queue=None, k_power=3, allocator=None):
        """Unnormalized binned power spectrum of a momentum-space field,
        weighted by ``|k|**k_power`` (reference spectra.py:140-175). Outer
        axes batch through a single distributed binning pass."""
        from pystella_tpu.ops.histogram import weighted_bincount
        if isinstance(fk, np.ndarray):
            fk = self.fft.shard_k(fk)
        with host_span("spectra_dispatch"):
            b, w = self._weights(fk, k_power)
        # k-space layout: x/y as the decomposition, half-spectrum z local
        hist = weighted_bincount(self.decomp, b, w, self.num_bins,
                                 lattice_names=tuple(
                                     self.fft.k_sharding(0).spec),
                                 owner="spectra")
        return np.asarray(hist) / self.bin_counts

    def __call__(self, fx, queue=None, k_power=3, allocator=None):
        """Power spectrum Δ²_f(k) of a position-space field; outer axes are
        batched through the transform and a single binning pass
        (the reference loops host-side instead, spectra.py:177-226).
        On the pencil tier the whole thing — transform, weighting,
        binning — is ONE fused device dispatch (see
        :meth:`spectrum_program`); the DFT tiers keep their separate
        transform/weights/binning dispatches."""
        with host_span("spectra"):
            if isinstance(fx, np.ndarray):
                fx = self.decomp.shard(np.asarray(fx, self.fft.dtype))
            if self.fft.is_pencil and self.fft._nproc > 1:
                return self._pencil_spectrum(fx, k_power)
            with host_span("spectra_dispatch"):
                fk = self.fft.dft(fx)
            return self.norm * self.bin_power(fk, k_power=k_power)

    def polarization(self, vector, projector, queue=None, k_power=3,
                     allocator=None):
        """Spectra of the plus/minus polarizations of a vector field;
        returns shape ``vector.shape[:-4] + (2, num_bins)``
        (reference spectra.py:228-271, which loops components host-side;
        here every outer slice batches through ONE transform, one
        projection, and one distributed binning pass)."""
        vec_k = self.fft.dft(vector)            # (outer..., 3, kshape)
        vec_k = jnp.moveaxis(vec_k, -4, 0)      # components lead
        plus, minus = projector.vec_to_pol(vec_k)
        pm = jnp.stack([plus, minus], axis=-4)  # (outer..., 2, kshape)
        return self.norm * self.bin_power(pm, k_power=k_power)

    def vector_decomposition(self, vector, projector, queue=None, k_power=3,
                             allocator=None):
        """Spectra of the plus/minus polarizations and longitudinal
        component; returns ``vector.shape[:-4] + (3, num_bins)``
        (reference spectra.py:273-320; batched like
        :meth:`polarization`)."""
        vec_k = self.fft.dft(vector)
        vec_k = jnp.moveaxis(vec_k, -4, 0)
        plus, minus, lng = projector.decompose_vector(
            vec_k, times_abs_k=True)
        pml = jnp.stack([plus, minus, lng], axis=-4)
        return self.norm * self.bin_power(pml, k_power=k_power)

    def gw(self, hij, projector, hubble, queue=None, k_power=3,
           allocator=None):
        """Spectral abundance Δ²_h(k) of transverse-traceless gravitational
        waves from the (6,)-packed tensor ``hij`` (reference
        spectra.py:322-370)."""
        with host_span("gw_spectra"):
            with host_span("spectra_dispatch"):
                hij_k = self.fft.dft(hij)
                hij_tt = projector.transverse_traceless(hij_k)
                # dropped before the binning allocates its weights: an
                # output's peak at 384^3 is 9.03 GB without it, 9.65 with
                del hij_k
            # (6, num_bins); bin_power holds its own dispatch and fetch
            gw_spec = self.bin_power(hij_tt, k_power=k_power)
        gw_tot = sum(gw_spec[tensor_index(i, j)]
                     for i in range(1, 4) for j in range(1, 4))
        return self.norm / 12 / hubble**2 * gw_tot

    def gw_polarization(self, hij, projector, hubble, queue=None, k_power=3,
                        allocator=None):
        """GW spectral abundance decomposed onto circular polarizations;
        returns shape ``(2, num_bins)`` (reference spectra.py:372-419)."""
        hij_k = self.fft.dft(hij)
        plus, minus = projector.tensor_to_pol(hij_k)
        pm = jnp.stack([plus, minus])  # one binning pass for both
        return self.norm / 12 / hubble**2 * self.bin_power(
            pm, k_power=k_power)
