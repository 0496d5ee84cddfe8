"""Distributed FFTs on sharded lattices.

TPU-native counterpart of /root/reference/pystella/fourier/dft.py:41-515.
The reference dispatches to clFFT/VkFFT on one rank or mpi4py-fft's ``PFFT``
(pencil decomposition, explicit MPI transposes) on many. Here there is one
path: ``jnp.fft.rfftn``/``irfftn`` on the x,y-sharded global array under
jit — XLA plans the axis FFTs and inserts the all-to-all transposes over ICI
itself, playing exactly the role mpi4py-fft's ``Subcomm`` pencils play
(dft.py:391-417).

Conventions match the reference:

- forward transform unnormalized, backward normalized (``idft(dft(x)) == x``);
- mode numbers from :func:`fftfreq` with *positive* Nyquist
  (reference dft.py:327-332);
- the r2c half-spectrum z axis stays local in *k-space* on every mesh.
  Unlike the reference (which forbids z decomposition outright,
  decomp.py:129-130), position-space z sharding is supported: the transform
  reshards to an x-only pencil first so z is local.
"""

from __future__ import annotations

import logging

import numpy as np

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

__all__ = ["DFT", "fftfreq", "pfftfreq", "make_hermitian",
           "get_real_dtype_with_matching_prec",
           "get_complex_dtype_with_matching_prec"]


def get_real_dtype_with_matching_prec(dtype):
    dtype = np.dtype(dtype)
    return np.dtype({8: np.float32, 16: np.float64}[dtype.itemsize] if
                    dtype.kind == "c" else dtype)


def get_complex_dtype_with_matching_prec(dtype):
    dtype = np.dtype(dtype)
    if dtype.kind == "c":
        return dtype
    return np.dtype({4: np.complex64, 8: np.complex128}[dtype.itemsize])


def fftfreq(n):
    """Integer FFT mode numbers with positive Nyquist
    (reference dft.py:327-332)."""
    freq = np.fft.fftfreq(n, 1 / n)
    if n % 2 == 0:
        freq[n // 2] = np.abs(freq[n // 2])
    return freq


pfftfreq = fftfreq


def get_sliced_momenta(grid_shape, dtype, local_slice=None):
    """Per-slice FFT mode numbers (reference ``get_sliced_momenta``,
    /root/reference/pystella/fourier/dft.py:335-349). With a single
    controller and global sharded arrays the "local slice" is the whole
    k-space axis set; pass ``local_slice`` (a tuple of slices) to subset."""
    rdtype = get_real_dtype_with_matching_prec(dtype)
    k = [fftfreq(n).astype(rdtype) for n in grid_shape]
    if np.dtype(dtype).kind == "f":
        n = grid_shape[-1]
        k[-1] = np.fft.rfftfreq(n, 1 / n).astype(rdtype)
    if local_slice is not None:
        k = [ki[sl] for ki, sl in zip(k, local_slice)]
    return k


def _self_conjugate_and_negative(n):
    """Partition axis indices under mode negation ``i -> (-i) % n``: the
    fixed points (``0`` and, for even ``n``, the Nyquist index) and the
    strictly-negative-mode half ``i > n//2``."""
    i = np.arange(n)
    fixed = (i == 0) | ((n % 2 == 0) & (i == n // 2))
    negative = i > n // 2
    return fixed, negative


def make_hermitian(fk):
    """Impose the Hermitian symmetry a real field's Fourier modes satisfy on
    the r2c-layout array ``fk`` (shape ``(..., Nx, Ny, Nz//2+1)``): on the
    ``kz = 0`` and ``kz = Nyquist`` planes, ``fk[-i, -j] = conj(fk[i, j])``,
    and the eight self-conjugate corner modes are real (same contract as
    reference rayleigh.py:35-54).

    Vectorized formulation: the (x, y) mirror ``fk[(-i) % Nx, (-j) % Ny]``
    is a flip-then-roll, and each mode in the negative half-plane (``ky``
    negative, or ``ky`` self-conjugate and ``kx`` negative) is overwritten
    by the conjugate of its mirror — one ``where`` over the whole array, no
    index loops. jit- and shard-compatible, so it runs on-device on the
    sharded k-grid; per-mode amplitudes are preserved (each surviving mode
    keeps its drawn amplitude), like the reference's copy-from-positive-half
    assignment."""
    on_host = isinstance(fk, np.ndarray)
    arr = jnp.asarray(fk)
    nx, ny, nzh = arr.shape[-3:]
    nz = 2 * (nzh - 1)

    # mirror in (x, y): index i -> (-i) % n  ==  roll(flip(axis), 1)
    mirror = jnp.roll(jnp.flip(arr, axis=(-3, -2)), (1, 1), axis=(-3, -2))

    fix_x, neg_x = _self_conjugate_and_negative(nx)
    fix_y, neg_y = _self_conjugate_and_negative(ny)
    # keep the positive half-plane, overwrite the negative one; ties on the
    # self-conjugate ky columns are broken by kx
    replace_xy = neg_y[None, :] | (fix_y[None, :] & neg_x[:, None])
    corner_xy = fix_x[:, None] & fix_y[None, :]
    kz_fixed = np.zeros(nzh, bool)
    kz_fixed[0] = True
    if nz:
        kz_fixed[nz // 2] = True

    replace = replace_xy[:, :, None] & kz_fixed
    corner = corner_xy[:, :, None] & kz_fixed
    out = jnp.where(replace, jnp.conj(mirror), arr)
    out = jnp.where(corner, jnp.real(out).astype(out.dtype), out)
    return np.asarray(out) if on_host else out


class DFT:
    """Forward/backward 3-D (r2c or c2c) FFTs of sharded lattice arrays.

    :arg decomp: a :class:`~pystella_tpu.DomainDecomposition`. All mesh
        shapes are supported (the reference forbids z decomposition,
        decomp.py:129-130); on z-sharded meshes the transform first
        reshards to an x-only pencil so the z axis is local, and k-space
        arrays keep the (half-spectrum) z axis unsharded.
    :arg grid_shape: position-space shape.
    :arg dtype: position-space dtype; a real dtype selects r2c transforms.

    Unlike the reference there are no attached scratch arrays or host↔device
    glue: ``dft``/``idft`` are pure functions on ``jax.Array``s.
    """

    def __init__(self, decomp, context=None, queue=None, grid_shape=None,
                 dtype=np.float64, **kwargs):
        if grid_shape is None:
            raise ValueError("grid_shape is required")
        self.decomp = decomp
        self.grid_shape = tuple(grid_shape)
        self.dtype = np.dtype(dtype)
        self.is_real = self.dtype.kind == "f"
        self.rdtype = get_real_dtype_with_matching_prec(self.dtype)
        self.cdtype = get_complex_dtype_with_matching_prec(self.dtype)

        # Pencil-scheme selection (three tiers, VERDICT r3 #7):
        #
        # - "pencil": the x (then y) axis is resharded over the COMBINED
        #   mesh axes between per-axis FFTs — minimal memory; needs
        #   grid x and y divisible by the total device count.
        # - "partial": each FFT stage shards its long axis by ONE mesh
        #   axis only (x by px during the y-FFT, y by py during the
        #   x-FFT; the other mesh axis replicates). Needs only the
        #   per-axis divisibility the position-space home already
        #   guarantees; transient memory is max(px, py) x the home
        #   block instead of ndev x. (A classic 2-D pencil would shard
        #   the half-spectrum z axis instead, but Nz/2+1 is odd and jax
        #   shardings require even divisibility.)
        # - "replicate": transforms replicate the array on every device
        #   and run redundantly. Correct but an OOM/bandwidth cliff at
        #   production sizes, so above ``replicate_limit`` bytes
        #   (default 1 GiB) construction RAISES instead (pass
        #   ``allow_replicate=True`` to override).
        #
        # Unlike the reference (z decomposition is NotImplementedError,
        # decomp.py:129-130) z-sharded meshes are supported: the
        # transform reshards to an x-only pencil first so z is local,
        # and k-space arrays keep the (half-spectrum) z axis unsharded.
        nproc = int(np.prod(decomp.proc_shape))
        px, py, pz = decomp.proc_shape
        self._nproc = nproc
        self._z_sharded = pz > 1
        # pop the replicate-tier options unconditionally so they are
        # consumed (not silently swallowed) whichever scheme is selected
        # (ADVICE r4); the limit default is env-tunable so a production
        # deployment can tighten it fleet-wide
        replicate_limit = kwargs.pop("replicate_limit", None)
        if replicate_limit is None:
            from pystella_tpu import config as _config
            replicate_limit = _config.get_float(
                "PYSTELLA_FFT_REPLICATE_LIMIT")
        replicate_limit = float(replicate_limit)
        allow_replicate = bool(kwargs.pop("allow_replicate", False))
        if kwargs:
            import warnings
            warnings.warn(f"DFT: unrecognized keyword arguments ignored: "
                          f"{sorted(kwargs)}", stacklevel=2)
        if (self.grid_shape[0] % nproc == 0
                and self.grid_shape[1] % nproc == 0):
            self._scheme = "pencil"
        elif (pz == 1 and self.grid_shape[0] % px == 0
                and self.grid_shape[1] % py == 0):
            self._scheme = "partial"
            logger.info(
                "DFT %s on %d devices: using the partial-replication "
                "pencil scheme (per-stage long axis sharded by one mesh "
                "axis; transient memory ~%d x the home block). The "
                "fully distributed pencil tier (fourier.pencil) needs "
                "grid x AND y divisible by the total device count.",
                self.grid_shape, nproc, max(px, py))
        else:
            self._scheme = "replicate"
            # size the k-space array the fallback would replicate: for
            # r2c transforms that is the HALF spectrum (Nz//2+1), not
            # the full grid — the old full-grid figure overstated r2c
            # by ~2x and refused shapes whose replicas actually fit
            nbytes = (int(np.prod(self.shape(True)))
                      * np.dtype(self.cdtype).itemsize)
            if nproc > 1 and not allow_replicate \
                    and nbytes > replicate_limit:
                raise ValueError(
                    f"DFT {self.grid_shape} on {nproc} devices: no "
                    "distributed scheme is feasible (grid axes do not "
                    f"divide the mesh axes) and the k-space array "
                    f"(~{nbytes / 2**30:.1f} GiB) exceeds the "
                    "replicate-fallback limit — every device would hold "
                    "and transform the FULL array. Prefer grid x/y "
                    "axes divisible by the total device count, which "
                    "enable the fully distributed pencil tier "
                    "(pystella_tpu.make_dft / fourier.pencil — no "
                    "replication at any size); per-mesh-axis "
                    "divisibility enables the partial tier. "
                    "pystella_tpu.advise_shapes(grid_shape, n_devices) "
                    "lists which meshes keep a distributed scheme. As "
                    "a last resort pass allow_replicate=True / a "
                    "larger replicate_limit "
                    "(PYSTELLA_FFT_REPLICATE_LIMIT) to accept the cost")
            if nproc > 1:
                logger.warning(
                    "DFT %s on %d devices: grid axes do not divide the "
                    "mesh axes — transforms will REPLICATE the array on "
                    "every device and run redundantly (correct, but "
                    "wasteful). Choose grid x/y divisible by the device "
                    "count for the distributed pencil tier.",
                    self.grid_shape, nproc)
        self._pencil_ok = self._scheme != "replicate"

        k = [fftfreq(n).astype(self.rdtype) for n in self.grid_shape]
        if self.is_real:
            n = self.grid_shape[-1]
            k[-1] = np.fft.rfftfreq(n, 1 / n).astype(self.rdtype)

        #: mode-number arrays (host, full axes — with one controller every
        #: "rank slice" is the whole axis), keyed like the reference's sub_k
        self.sub_k = {name: ki for name, ki
                      in zip(("momenta_x", "momenta_y", "momenta_z"), k)}

        # device copies shaped for broadcasting against k-space arrays,
        # in THIS transform's k layout: k_axis_array and _dft_impl/
        # _idft_impl resolve through the subclass, so one constructor
        # serves every tier (the pencil tier's natural layout included)
        self.sub_k_device = [self.k_axis_array(mu, ki)
                             for mu, ki in enumerate(k)]

        from pystella_tpu.obs import memory as _obs_memory
        fwd_label, inv_label = self._jit_labels()
        self._dft = _obs_memory.instrument_jit(
            self._dft_impl, label=fwd_label)
        self._idft = _obs_memory.instrument_jit(
            self._idft_impl, label=inv_label)

    def shape(self, forward_output=True):
        """Global array shape (reference dft.py:124-133 reports per-rank
        shapes; with a single controller the global shape is the analog)."""
        if forward_output and self.is_real:
            return self.grid_shape[:-1] + (self.grid_shape[-1] // 2 + 1,)
        return self.grid_shape

    @property
    def proc_permutation(self):
        """k-space axes are not permuted relative to position space (XLA
        transposes internally and restores layout; cf. dft.py:412-417)."""
        return tuple(range(len(self.grid_shape)))

    #: True on the fully distributed shard_map pencil tier
    #: (:class:`pystella_tpu.fourier.pencil.PencilFFT`)
    is_pencil = False

    @property
    def scheme(self):
        """The selected transform scheme name (``"pencil"``/``"partial"``/
        ``"replicate"`` for this declarative-reshard class; the
        shard_map tier reports ``"pencil-a2a"``)."""
        return self._scheme

    def k_axis_array(self, mu, values):
        """Per-axis k-space constants (momenta, stencil eigenvalues)
        shaped for broadcasting against this transform's k-space
        arrays, sharded to match THEIR layout along lattice axis ``mu``
        — the one hook projector/Poisson/collocator constants go
        through, so every consumer works against any transform tier
        (the pencil tier keeps x local and shards y over the combined
        mesh axes, unlike this class's x/y home layout)."""
        return self.decomp.axis_array(mu, values, sharded=(mu != 2))

    def _jit_labels(self):
        """Compile-ledger labels for the forward/inverse jits."""
        return "fourier.dft_forward", "fourier.dft_inverse"

    # -- pencil transforms -------------------------------------------------
    #
    # Each 1-D FFT runs on a locally-contiguous axis; `reshard` between them
    # is the declarative pencil transpose — XLA emits the all-to-alls over
    # ICI, the role mpi4py-fft's explicit MPI transposes play in the
    # reference (dft.py:391-417).

    def _names(self):
        """Per-lattice-axis mesh axis names (None for size-1 axes)."""
        decomp = self.decomp
        return [n if decomp.proc_shape[i] > 1 else None
                for i, n in enumerate(decomp.axis_names)]

    def _replicated(self):
        """Fully-replicated NamedSharding (replicate-fallback target)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.decomp.mesh, P())

    def _specs(self, outer):
        from jax.sharding import NamedSharding, PartitionSpec as P
        names = self._names()
        if self._scheme == "partial":
            # per-stage long axis sharded by its OWN mesh axis only (the
            # other mesh axis replicates) — feasible whenever the home
            # sharding is, since that already requires X % px == 0 and
            # Y % py == 0 (the combined-axes pencil needs X % ndev)
            x_ent, y_ent = names[0], names[1]
        else:
            mixed = tuple(n for n in names if n is not None)
            x_ent = y_ent = mixed or None
        o = (None,) * outer
        # concrete NamedShardings (mesh embedded): ``reshard`` then needs
        # no ambient mesh context, so transforms trace identically in
        # eager calls and inside callers' jits
        ns = (lambda *ent: NamedSharding(self.decomp.mesh, P(*o, *ent)))
        return (ns(names[0], names[1], names[2]),   # position-space home
                ns(names[0], names[1], None),       # k-space home, z local
                ns(x_ent, None, None),              # x sharded, y/z local
                ns(None, y_ent, None))              # y sharded, x/z local

    def _mid_spec(self, outer):
        """Staging layout for z-sharded meshes: z local, z's mesh devices
        spread onto the y axis. Every transition home <-> mid <-> pencil is
        one the SPMD partitioner lowers as collectives; the direct
        home -> x-pencil jump triggers its involuntary-full-rematerialization
        fallback (replicate-then-repartition)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        names = self._names()
        yz = tuple(n for n in names[1:] if n is not None)
        return NamedSharding(
            self.decomp.mesh,
            P(*((None,) * outer), names[0], yz or None, None))

    def _dft_impl(self, fx):
        from jax.sharding import reshard
        outer = fx.ndim - 3
        if self._nproc == 1:
            return (jnp.fft.rfftn if self.is_real else jnp.fft.fftn)(
                fx, axes=(-3, -2, -1))
        phome, khome, x_shard, y_shard = self._specs(outer)
        if not self._pencil_ok:
            xk = reshard(fx, self._replicated())
            xk = (jnp.fft.rfftn if self.is_real else jnp.fft.fftn)(
                xk, axes=(-3, -2, -1))
            return reshard(xk, khome)
        if self._z_sharded:
            # make z local first (staged: home -> mid -> pencils, each a
            # partitioner-friendly transition — see _mid_spec)
            xk = reshard(fx, self._mid_spec(outer))
            xk = (jnp.fft.rfft if self.is_real else jnp.fft.fft)(xk, axis=-1)
            xk = reshard(xk, x_shard)
        else:
            xk = (jnp.fft.rfft if self.is_real else jnp.fft.fft)(fx, axis=-1)
            xk = reshard(xk, x_shard)
        xk = jnp.fft.fft(xk, axis=-2)
        xk = reshard(xk, y_shard)
        xk = jnp.fft.fft(xk, axis=-3)
        if self._z_sharded:
            xk = reshard(xk, self._mid_spec(outer))
        return reshard(xk, khome)

    def _idft_impl(self, fk):
        from jax.sharding import reshard
        outer = fk.ndim - 3
        if self._nproc == 1:
            if self.is_real:
                return jnp.fft.irfftn(fk, s=self.grid_shape, axes=(-3, -2, -1))
            return jnp.fft.ifftn(fk, axes=(-3, -2, -1))
        phome, khome, x_shard, y_shard = self._specs(outer)
        if not self._pencil_ok:
            xk = reshard(fk, self._replicated())
            if self.is_real:
                xk = jnp.fft.irfftn(xk, s=self.grid_shape, axes=(-3, -2, -1))
            else:
                xk = jnp.fft.ifftn(xk, axes=(-3, -2, -1))
            return reshard(xk, phome)
        if self._z_sharded:
            xk = reshard(fk, self._mid_spec(outer))
            xk = reshard(xk, y_shard)
        else:
            xk = reshard(fk, y_shard)
        xk = jnp.fft.ifft(xk, axis=-3)
        xk = reshard(xk, x_shard)
        xk = jnp.fft.ifft(xk, axis=-2)
        if self._z_sharded:
            # finish the z transform while z is still local, then go home
            # (staged again: pencil -> mid -> home)
            if self.is_real:
                xk = jnp.fft.irfft(xk, n=self.grid_shape[-1], axis=-1)
            else:
                xk = jnp.fft.ifft(xk, axis=-1)
            xk = reshard(xk, self._mid_spec(outer))
            return reshard(xk, phome)
        xk = reshard(xk, khome)
        if self.is_real:
            return jnp.fft.irfft(xk, n=self.grid_shape[-1], axis=-1)
        return jnp.fft.ifft(xk, axis=-1)

    def k_sharding(self, outer_axes=0):
        """``NamedSharding`` of k-space arrays: x/y as the decomposition,
        the (half-spectrum) z axis always local."""
        _, khome, _, _ = self._specs(outer_axes)
        return khome

    def shard_k(self, array, outer_axes=None):
        """Place a host k-space array on the mesh in the k-home layout."""
        if outer_axes is None:
            outer_axes = array.ndim - 3
        return jax.device_put(array, self.k_sharding(outer_axes))

    def dft(self, fx=None, fk=None, **kwargs):
        """Forward transform. Returns the momentum-space array (the ``fk``
        out-argument of the reference API is accepted and ignored — arrays
        are immutable here)."""
        arr = fx if not isinstance(fx, np.ndarray) else \
            self.decomp.shard(np.asarray(fx, self.dtype))
        return self._dft(arr)

    def idft(self, fk=None, fx=None, **kwargs):
        """Backward (normalized) transform. Returns the position-space
        array."""
        arr = fk if not isinstance(fk, np.ndarray) else \
            self.shard_k(np.asarray(fk, self.cdtype))
        out = self._idft(arr)
        if self.is_real:
            out = out.astype(self.dtype)
        return out

    def zero_corner_modes(self, array, only_imag=False):
        """Zero the eight corner modes (each wavenumber component 0 or
        Nyquist), or just their imaginary parts (reference dft.py:293-324,
        which loops per-rank corner indices on device). Here the corner
        set is a static open-mesh index (at most 2 x 2 x 2 .. 4 x 4 x 4
        sites) and the update a scatter — device arrays stay on device
        with their sharding, and no whole-lattice mask is ever
        materialized (a 512**3 boolean mask would be a ~67 MB transient
        per device to touch <= 64 sites; ADVICE r4)."""
        on_host = isinstance(array, np.ndarray)

        corners = []
        for mu, name in enumerate(self.sub_k):
            kk = self.sub_k[name].astype(int)
            corners.append(np.flatnonzero(
                (np.abs(kk) == 0)
                | (np.abs(kk) == self.grid_shape[mu] // 2)))
        idx = (Ellipsis,) + np.ix_(*corners)

        if on_host:
            arr = np.array(array)  # like np.where, never mutate the input
            if only_imag:
                arr[idx] = arr[idx].real.astype(arr.dtype)
            else:
                arr[idx] = 0
            return arr
        if only_imag:
            vals = jnp.real(array[idx]).astype(array.dtype)
            return array.at[idx].set(vals)
        return array.at[idx].set(0)
