"""Mesh-centric domain decomposition.

TPU-native replacement for the reference's MPI ``DomainDecomposition``
(/root/reference/pystella/decomp.py:32-725). The reference materializes
halo-padded per-rank pencils and moves ghost cells by device-pack →
host-staging → ``MPI.Sendrecv`` → unpack (decomp.py:365-449). Here the
lattice is a single *unpadded* global ``jax.Array`` sharded over a
``jax.sharding.Mesh``; the same verbs map onto XLA collectives riding ICI:

========================  =====================================================
reference verb             TPU-native mechanism
========================  =====================================================
``share_halos``            ``lax.ppermute`` of boundary slabs inside
                           ``shard_map`` (periodic wrap built into the perm)
``allreduce``              ``lax.psum``/``pmax``/``pmin`` — or plain ``jnp``
                           reductions on the global array under jit
``bcast``                  replicated shardings / ``multihost_utils``
``gather_array``           ``jax.device_get`` (addressable) /
                           ``multihost_utils.process_allgather``
``scatter_array``          ``jax.device_put`` with a ``NamedSharding``
``remove/restore_halos``   not needed — arrays are never padded
========================  =====================================================

Unlike the reference (2-D process grid only; z-decomposition is
``NotImplementedError``, decomp.py:129-130), all three lattice axes may be
sharded.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from pystella_tpu.obs import memory as _obs_memory
from pystella_tpu.obs.scope import trace_scope
from pystella_tpu.parallel.overlap import MIN_INTERIOR_FACTOR

__all__ = ["DomainDecomposition", "HaloShells", "ensemble_mesh",
           "make_mesh"]


def make_mesh(proc_shape=None, axis_names=("x", "y", "z"), devices=None):
    """Build a ``Mesh`` over the lattice axes.

    :arg proc_shape: devices per lattice axis, e.g. ``(2, 2, 1)``. Defaults to
        all devices on the first axis. Plays the role of the reference's
        ``proc_shape`` (/root/reference/pystella/decomp.py:61-66).
    """
    devices = devices if devices is not None else jax.devices()
    if proc_shape is None:
        proc_shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    proc_shape = tuple(int(p) for p in proc_shape)
    if int(np.prod(proc_shape)) != len(devices):
        raise ValueError(
            f"proc_shape {proc_shape} does not cover {len(devices)} devices")
    mesh_devices = np.asarray(devices).reshape(proc_shape)
    # Explicit axis types: required by the declarative pencil-FFT reshards
    # (jax.sharding.reshard refuses Auto axes). On a single-device mesh
    # nothing is ever resharded and explicit-sharding type tracking only
    # gets in the way (e.g. of pallas_call), so use Auto there.
    kind = AxisType.Explicit if len(devices) > 1 else AxisType.Auto
    return Mesh(mesh_devices, axis_names[:len(proc_shape)],
                axis_types=(kind,) * len(proc_shape))


def ensemble_mesh(proc_shape=None, ensemble_devices=None,
                  axis_names=("x", "y", "z"), ensemble_axis=None,
                  devices=None):
    """Build a ``(ensemble, x, y, z)`` device mesh — the ensemble
    tier's mapping surface (:mod:`pystella_tpu.ensemble`): small
    lattices keep ``proc_shape == (1, 1, 1)`` and pack the chip set
    along the leading ensemble axis, large ones keep spatial sharding
    with a smaller (possibly size-1) ensemble extent.

    :arg proc_shape: devices per LATTICE axis within one ensemble
        shard, e.g. ``(2, 2, 1)``; defaults to ``(1, 1, 1)`` (pure
        member packing).
    :arg ensemble_devices: devices along the ensemble axis; defaults to
        ``len(devices) // prod(proc_shape)`` (use everything). This is
        the DEVICE extent — the member count is independent: a batch of
        E members over an ensemble extent of D places E/D members per
        mesh slice.
    :arg ensemble_axis: leading axis name (default: the registered
        ``PYSTELLA_ENSEMBLE_AXIS``, normally ``"ensemble"``).

    The returned mesh uses Auto axis types: batched member programs are
    plain ``jit(vmap(...))`` over globally-sharded arrays, where the
    partitioner propagates shardings itself — the declarative reshards
    that want Explicit axes never run on the member axis.
    """
    from pystella_tpu import config as _config
    devices = list(devices) if devices is not None else jax.devices()
    if ensemble_axis is None:
        ensemble_axis = _config.getenv("PYSTELLA_ENSEMBLE_AXIS")
    if proc_shape is None:
        proc_shape = (1,) * len(axis_names)
    proc_shape = tuple(int(p) for p in proc_shape)
    spatial = int(np.prod(proc_shape))
    if ensemble_devices is None:
        if len(devices) % spatial:
            raise ValueError(
                f"{len(devices)} devices do not tile proc_shape "
                f"{proc_shape}; pass ensemble_devices or a device "
                "subset explicitly")
        ensemble_devices = len(devices) // spatial
    ensemble_devices = int(ensemble_devices)
    need = ensemble_devices * spatial
    if need > len(devices):
        raise ValueError(
            f"ensemble mesh ({ensemble_devices},)+{proc_shape} needs "
            f"{need} devices, have {len(devices)}")
    mesh_devices = np.asarray(devices[:need]).reshape(
        (ensemble_devices,) + proc_shape)
    names = (ensemble_axis,) + tuple(axis_names[:len(proc_shape)])
    return Mesh(mesh_devices, names,
                axis_types=(AxisType.Auto,) * len(names))


class DomainDecomposition:
    """Shards 3-D lattice arrays over a device mesh and provides halo
    exchange plus collective verbs.

    :arg proc_shape: devices per axis (builds a mesh), or pass ``mesh=``.
    :arg halo_shape: default halo width ``h`` (per-op widths may override).
    :arg ensemble_axis: name of a LEADING extra mesh axis carrying an
        ensemble of members (a mesh from :func:`ensemble_mesh`). The
        decomposition then describes each member's lattice — ``spec``/
        ``sharding``/halo verbs see only the trailing lattice axes —
        while :meth:`member_spec` / :meth:`member_sharding` /
        :meth:`shard_members` place batched ``(members, ...)`` arrays
        with the member axis over the ensemble devices.
    """

    def __init__(self, proc_shape=None, halo_shape=0, mesh=None,
                 axis_names=("x", "y", "z"), devices=None,
                 ensemble_axis=None):
        if mesh is None:
            if ensemble_axis is not None:
                raise ValueError("an ensemble decomposition needs an "
                                 "explicit mesh (ensemble_mesh(...))")
            mesh = make_mesh(proc_shape, axis_names, devices)
        self.mesh = mesh
        self.ensemble_axis = ensemble_axis
        names = tuple(mesh.axis_names)
        shape = tuple(mesh.devices.shape)
        if ensemble_axis is not None:
            if not names or names[0] != ensemble_axis:
                raise ValueError(
                    f"ensemble axis {ensemble_axis!r} must be the "
                    f"mesh's leading axis; mesh has {names}")
            names, shape = names[1:], shape[1:]
        self.axis_names = names
        self.proc_shape = shape
        if np.isscalar(halo_shape):
            halo_shape = (halo_shape,) * 3
        self.halo_shape = tuple(int(h) for h in halo_shape)
        self._share_halos_cache = {}
        # per-execution ICI bytes of each DISTINCT halo program traced
        # through this decomposition, recorded at trace-cache-miss time
        # (a traced pad runs once per consumer compile, so executions
        # cannot be counted here — this is the static per-call figure;
        # obs counter "halo_bytes_exchanged" accumulates the same)
        self._halo_program_bytes = {}

    # -- shardings ---------------------------------------------------------

    def spec(self, outer_axes=0):
        """``PartitionSpec`` for an array with ``outer_axes`` leading
        unsharded component axes followed by the 3 lattice axes."""
        names = [n if self.proc_shape[i] > 1 else None
                 for i, n in enumerate(self.axis_names)]
        return P(*((None,) * outer_axes + tuple(names)))

    def sharding(self, outer_axes=0):
        return NamedSharding(self.mesh, self.spec(outer_axes))

    # -- ensemble (member-axis) shardings ----------------------------------

    @property
    def ensemble_devices(self):
        """Device extent of the ensemble mesh axis (1 without one)."""
        if self.ensemble_axis is None:
            return 1
        return int(self.mesh.shape[self.ensemble_axis])

    def member_spec(self, outer_axes=0):
        """``PartitionSpec`` for a batched array ``(members,
        *outer, *lattice)``: the leading member axis rides the ensemble
        mesh axis, the trailing lattice axes keep their spatial
        sharding — the ``(ensemble, x, y, z)`` layout that lets small
        lattices pack the chip set and large ones keep sharding."""
        if self.ensemble_axis is None or self.ensemble_devices == 1:
            lead = (None,)
        else:
            lead = (self.ensemble_axis,)
        names = [n if self.proc_shape[i] > 1 else None
                 for i, n in enumerate(self.axis_names)]
        return P(*(lead + (None,) * outer_axes + tuple(names)))

    def member_sharding(self, outer_axes=0):
        return NamedSharding(self.mesh, self.member_spec(outer_axes))

    def shard_members(self, array, outer_axes=None):
        """Place a batched ``(members, ...)`` array (host or device)
        with the member axis over the ensemble devices and the lattice
        axes over the spatial mesh. The ensemble device extent must
        divide the member count. Leaves of rank below ``1 + lattice
        rank`` (per-member scalars/vectors riding in the state pytree)
        carry no lattice axes — only the member axis shards them."""
        ndev = self.ensemble_devices
        if ndev > 1 and array.shape[0] % ndev:
            raise ValueError(
                f"member count {array.shape[0]} not divisible by the "
                f"ensemble device extent {ndev}; pad the batch or "
                "choose a compatible mesh")
        if outer_axes is None:
            outer_axes = array.ndim - 1 - len(self.axis_names)
        if outer_axes < 0:
            lead = (None,) if (self.ensemble_axis is None or ndev == 1) \
                else (self.ensemble_axis,)
            spec = P(*(lead + (None,) * (array.ndim - 1)))
            return jax.device_put(array, NamedSharding(self.mesh, spec))
        return jax.device_put(array, self.member_sharding(outer_axes))

    @property
    def reduce_axes(self):
        """Mesh axis names lattice arrays are actually sharded over (size-1
        axes excluded) — the axes to ``psum`` over inside ``shard_map``."""
        return tuple(n for i, n in enumerate(self.axis_names)
                     if self.proc_shape[i] > 1)

    def psum(self, x):
        """``lax.psum`` over all sharded mesh axes; no-op on a single-device
        mesh. For use inside ``shard_map`` bodies."""
        names = self.reduce_axes
        return lax.psum(x, names) if names else x

    def axis_array(self, mu, values, sharded=True):
        """Device array of per-axis constants (momenta, stencil eigenvalues)
        shaped ``(1, .., len(values), .., 1)`` for broadcasting against
        lattice arrays, sharded to match lattice axis ``mu``. Pass
        ``sharded=False`` for axes that are local in the consuming layout
        (e.g. the r2c half-spectrum z axis, which k-space arrays keep
        unsharded on z-decomposed meshes)."""
        values = np.asarray(values)
        shape = [1] * len(self.axis_names)
        shape[mu] = len(values)
        spec = [None] * len(self.axis_names)
        if sharded and self.proc_shape[mu] > 1:
            spec[mu] = self.axis_names[mu]
        return jax.device_put(values.reshape(shape),
                              NamedSharding(self.mesh, P(*spec)))

    def shard(self, array, outer_axes=None):
        """Place ``array`` (host or device) with lattice axes sharded over
        the mesh. Replaces the reference's ``scatter_array``
        (/root/reference/pystella/decomp.py:652-725)."""
        if outer_axes is None:
            outer_axes = array.ndim - len(self.axis_names)
        return jax.device_put(array, self.sharding(outer_axes))

    # reference-API aliases
    scatter_array = shard

    def gather_array(self, array):
        """Bring a sharded lattice array fully to host as ``np.ndarray``
        (reference ``gather_array``, decomp.py:536-599)."""
        return np.asarray(jax.device_get(array))

    def zeros(self, grid_shape, dtype, outer_shape=()):
        sharding = self.sharding(len(outer_shape))
        return jnp.zeros(tuple(outer_shape) + tuple(grid_shape), dtype,
                         device=sharding)

    # -- collectives on global arrays -------------------------------------

    def allreduce(self, x, op="sum"):
        """Reduce over the full lattice. On global sharded arrays a plain
        ``jnp`` reduction already produces the collective (XLA inserts the
        cross-device reduce); kept as a verb for parity with
        /root/reference/pystella/decomp.py:470-491."""
        if op == "sum":
            return jnp.sum(x)
        if op == "max":
            return jnp.max(x)
        if op == "min":
            return jnp.min(x)
        if op == "prod":
            return jnp.prod(x)
        raise ValueError(f"unknown op {op}")

    def bcast(self, x, root=0):
        """Parity shim: with a single controller and replicated shardings
        there is nothing to broadcast (reference decomp.py:451-468)."""
        return x

    def barrier(self):
        jax.effects_barrier()

    @property
    def rank(self):
        return jax.process_index()

    @property
    def nranks(self):
        return jax.process_count()

    def rank_tuple(self, rank=None):
        """Cartesian coordinates of host process ``rank`` in the process
        grid (reference ``rank_tuple``, decomp.py:298-304). Processes are
        laid out along the x mesh axis; with one controller this is
        ``(0, 0, 0)``."""
        rank = self.rank if rank is None else rank
        return (rank % max(1, jax.process_count()), 0, 0)

    def rankID(self, *tup):
        """Flat id of process-grid coordinates with periodic wrap
        (reference ``rankID``, decomp.py:287-296)."""
        n = max(1, jax.process_count())
        return tup[0] % n

    # -- halo exchange (shard_map interior) --------------------------------

    def _perm(self, axis_name, shift):
        size = self.mesh.shape[axis_name]
        return [(i, (i + shift) % size) for i in range(size)]

    # -- halo traffic accounting -------------------------------------------

    def halo_bytes(self, shape, itemsize, halo, exchange=None,
                   lattice_axes=None):
        """Interconnect bytes ONE execution of a halo exchange with
        these parameters moves: two ``exchange[d]``-wide slabs per
        sharded axis (alignment rows beyond ``exchange`` are local
        zeros and move nothing; unsharded axes wrap locally). Mirrors
        the sequential exchange of :meth:`pad_with_halos` — later axes'
        slabs include earlier axes' padding."""
        if lattice_axes is None:
            lattice_axes = tuple(range(len(shape) - len(halo), len(shape)))
        extents = list(shape)
        total = 0
        for d, ax in enumerate(lattice_axes):
            h = halo[d]
            if h == 0:
                continue
            e = min(int(exchange[d]), h) if exchange is not None else h
            if self.proc_shape[d] > 1 and e > 0:
                slab = int(itemsize) * e
                for a, n in enumerate(extents):
                    if a != ax:
                        slab *= int(n)
                total += 2 * slab
            extents[ax] += 2 * h
        return total

    def _record_halo_bytes(self, key, nbytes):
        """Trace-cache-miss accounting: the first time a distinct halo
        program is traced, its per-execution ICI bytes land in the
        ``halo_bytes_exchanged`` counter and in
        :attr:`_halo_program_bytes` (see :meth:`traced_halo_bytes`)."""
        if not nbytes or key in self._halo_program_bytes:
            return
        self._halo_program_bytes[key] = nbytes
        from pystella_tpu.obs import metrics as _metrics
        _metrics.counter("halo_bytes_exchanged").inc(nbytes)

    def traced_halo_bytes(self):
        """Total per-execution ICI bytes over every distinct halo
        program traced through this decomposition so far — the
        ``bytes_per_step`` figure a driver that runs one such program
        per step can hand to the perf ledger (``halo_traffic`` event)."""
        return sum(self._halo_program_bytes.values())

    def pad_with_halos(self, x, halo, lattice_axes=None, exchange=None,
                       overlap=False):
        """Return ``x`` padded with periodic halos of width ``halo[d]`` along
        each lattice axis.

        MUST be called from inside a ``shard_map`` over this mesh: for sharded
        axes the halos are the neighbors' boundary slabs, moved with
        ``lax.ppermute`` (periodic wrap is encoded in the permutation, exactly
        the role of the reference's rankID wrap + Sendrecv,
        /root/reference/pystella/decomp.py:287-296,365-449); for unsharded
        axes the halo is a local periodic wrap (the reference's
        pack-unpack self-copy kernels, decomp.py:181-182).

        ``exchange[d]`` (default ``halo[d]``) bounds the width actually
        MOVED over the interconnect: when a consumer needs an
        alignment-padded halo wider than its stencil radius (the
        streaming kernels' 8-aligned y window pad,
        :func:`~pystella_tpu.ops.pallas_stencil.sharded_halo`), only the
        ``exchange[d]`` semantically-read rows ride ``ppermute`` and the
        remaining ``halo[d] - exchange[d]`` alignment rows are LOCAL
        zeros — cutting the per-stage ICI bytes by ``halo/exchange``
        (4x for the h=2 y halo) without touching the
        Mosaic-clean buffer layout. Callers must guarantee no tap reads
        beyond ``exchange[d]`` (stencil taps reach at most the radius).

        With ``overlap=True`` the padded block is instead returned SPLIT
        for communication/computation overlap, as ``(interior,
        shells)``: ``interior`` is ``x`` padded along the axes that need
        no interconnect traffic only (pure local data — a stencil
        applied to it yields the radius-``halo`` inset of the block,
        with no dependence on the collectives), and ``shells`` is a
        :class:`HaloShells` carrying the fully assembled padded block
        plus the region bookkeeping to compute the boundary shells (two
        per split axis) and stitch them around the interior. Requires
        trailing lattice axes and raises ``ValueError`` when no overlap
        split exists (nothing sharded, a sharded z axis, or a block
        thinner than ``MIN_INTERIOR_FACTOR * halo`` along a sharded
        axis — see :meth:`split_axes`) — use :meth:`overlap_stencil`
        for the driver that degrades to the padded path instead.
        """
        halo, exchange = self._canon_halo(halo, exchange)
        if lattice_axes is None:
            lattice_axes = tuple(range(x.ndim - len(self.axis_names), x.ndim))
        if overlap:
            return self._overlap_split(x, halo, lattice_axes, exchange)
        key = (tuple(x.shape), str(x.dtype), halo, exchange,
               tuple(lattice_axes))
        self._record_halo_bytes(key, self.halo_bytes(
            x.shape, np.dtype(x.dtype).itemsize, halo, exchange,
            lattice_axes))
        with jax.named_scope("halo_exchange"):
            return self._pad_with_halos(x, halo, lattice_axes, exchange)

    def _canon_halo(self, halo, exchange):
        if np.isscalar(halo):
            halo = (halo,) * len(self.axis_names)
        halo = tuple(int(h) for h in halo)
        if exchange is None:
            exchange = halo
        elif np.isscalar(exchange):
            exchange = (exchange,) * len(self.axis_names)
        return halo, tuple(int(e) for e in exchange)

    def comm_axes(self, halo):
        """Lattice axes whose halos actually ride the interconnect."""
        return tuple(d for d in range(len(self.axis_names))
                     if self.proc_shape[d] > 1 and halo[d] > 0)

    def split_axes(self, halo, shape):
        """The axes the interior/shell split divides, or ``()`` when the
        configuration must keep the padded path. The split is
        all-or-nothing over the communicated axes, and only x/y
        qualify: a sharded z (minor) axis — whether split into shells
        or exchanged up front as a concat into the interior input —
        was measured to shift the CPU backend's FMA contraction on
        sliced minor-axis pieces by ~1 ulp, breaking the bit-exactness
        contract, so any z communication sends the whole op down the
        padded path (the production pallas/fused layouts keep z whole
        per device anyway). Each split axis must also span at least
        ``MIN_INTERIOR_FACTOR * halo`` sites, or there is no interior
        to hide the transfer behind."""
        comm = self.comm_axes(halo)
        if not comm or 2 in comm:
            return ()
        if any(shape[d] < MIN_INTERIOR_FACTOR * halo[d] for d in comm):
            return ()
        return comm

    def _overlap_split(self, x, halo, lattice_axes, exchange):
        if tuple(lattice_axes) != tuple(range(x.ndim - 3, x.ndim)):
            raise ValueError("overlap split requires trailing lattice axes")
        shape = tuple(x.shape[-3:])
        split = self.split_axes(halo, shape)
        if not split:
            raise ValueError(
                f"no overlappable axis for block {shape} with halo "
                f"{halo} on mesh {self.proc_shape}: needs a sharded x/y "
                f"axis spanning >= {MIN_INTERIOR_FACTOR}*halo (the z "
                "axis is never split; see split_axes)")
        # trace the exchange FIRST so the collective starts are issued
        # ahead of the interior compute they will overlap with
        padded = self.pad_with_halos(x, halo, exchange=exchange)
        local_halo = tuple(0 if d in split else halo[d] for d in range(3))
        local_ex = tuple(0 if d in split else exchange[d]
                         for d in range(3))
        interior = self._pad_with_halos(
            x, local_halo, lattice_axes, local_ex)
        return interior, HaloShells(padded, halo, split, shape)

    def _pad_with_halos(self, x, halo, lattice_axes, exchange):
        for d, ax in enumerate(lattice_axes):
            h = halo[d]
            if h == 0:
                continue
            e = min(int(exchange[d]), h)
            # the unsharded alignment-pad branch below slices h rows, so
            # the guard must bound the full halo width, not just the
            # exchanged width
            if (h if self.proc_shape[d] == 1 else e) > x.shape[ax]:
                raise ValueError(
                    f"halo width {h if self.proc_shape[d] == 1 else e} "
                    f"exceeds the local block size {x.shape[ax]} along "
                    f"axis {d}; use a wider grid or a smaller mesh axis")
            name = self.axis_names[d]
            lo = lax.slice_in_dim(x, x.shape[ax] - e, x.shape[ax], axis=ax)
            hi = lax.slice_in_dim(x, 0, e, axis=ax)
            if self.proc_shape[d] > 1:
                # my right slab becomes right-neighbor's left halo and v.v.
                left_halo = lax.ppermute(lo, name, self._perm(name, +1))
                right_halo = lax.ppermute(hi, name, self._perm(name, -1))
            elif e < h:
                # unsharded with an alignment pad: wrap the full width
                # locally (free — no interconnect), keeping the legacy
                # all-real-rows layout
                left_halo = lax.slice_in_dim(
                    x, x.shape[ax] - h, x.shape[ax], axis=ax)
                right_halo = lax.slice_in_dim(x, 0, h, axis=ax)
                e = h
            else:
                left_halo, right_halo = lo, hi
            if e < h:
                zshape = list(x.shape)
                zshape[ax] = h - e
                zeros = jnp.zeros(zshape, x.dtype)
                left_halo = lax.concatenate([zeros, left_halo],
                                            dimension=ax)
                right_halo = lax.concatenate([right_halo, zeros],
                                             dimension=ax)
            x = lax.concatenate([left_halo, x, right_halo], dimension=ax)
        return x

    def exchange_slabs(self, x, d, width, lattice_axes=None, pad_to=None):
        """``(left_halo, right_halo)`` slabs of ``width`` rows along
        lattice axis ``d``, moved with periodic ``lax.ppermute``: what
        :meth:`pad_with_halos` moves over the interconnect, without
        the padded copy of ``x``. The slab-fed streaming kernels take
        them as operands (``StreamingStencil.halo_slabs``), the
        overlapped Pallas tier assembles its shells from them. With
        ``pad_to`` each slab is grown to that many rows by local zeros
        on its far side, the moved rows staying against the block (the
        8-aligned piece a y window wants; the zeros are never read).
        ``x`` may be a list of arrays of one dtype and lattice shape:
        their faces are stacked along the leading axis and moved
        together, one ``ppermute`` a direction. MUST be called from
        inside a ``shard_map``; ``d`` must be a sharded axis."""
        xs = list(x) if isinstance(x, (list, tuple)) else [x]
        x = xs[0]
        if lattice_axes is None:
            lattice_axes = tuple(range(x.ndim - len(self.axis_names), x.ndim))
        ax = lattice_axes[d]
        name = self.axis_names[d]

        def faces(s, e):
            cut = [lax.slice_in_dim(a, s, e, axis=ax) for a in xs]
            return cut[0] if len(cut) == 1 else lax.concatenate(cut, 0)

        lo = faces(x.shape[ax] - width, x.shape[ax])
        hi = faces(0, width)
        key = ("slabs", tuple(lo.shape), str(x.dtype), d)
        self._record_halo_bytes(
            key, 2 * lo.size * np.dtype(x.dtype).itemsize)
        with jax.named_scope("halo_exchange"):
            left_halo = lax.ppermute(lo, name, self._perm(name, +1))
            right_halo = lax.ppermute(hi, name, self._perm(name, -1))
            if pad_to is not None and pad_to > width:
                zshape = list(lo.shape)
                zshape[ax] = pad_to - width
                zeros = jnp.zeros(zshape, x.dtype)
                left_halo = lax.concatenate([zeros, left_halo],
                                            dimension=ax)
                right_halo = lax.concatenate([right_halo, zeros],
                                             dimension=ax)
        return left_halo, right_halo

    def overlap_stencil(self, xs, halo, apply_fn, extras=None,
                        exchange=None, overlap=True):
        """Apply a radius-``halo`` stencil with the halo exchange
        overlapped behind the interior compute.

        ``xs`` is a pytree of arrays with identical trailing 3 lattice
        axes; ``apply_fn(padded_xs[, extras])`` must treat its first
        argument as the halo-padded block (every lattice axis grown by
        ``2 * halo[d]``), return a pytree of outputs with trailing
        lattice axes equal to the unpadded extent, and be ELEMENTWISE
        over lattice sites (taps plus pointwise math — no cross-site
        reductions, whose order the region split would change).
        ``extras`` is an optional pytree of same-lattice unpadded
        arrays (plus scalars, passed through untouched) sliced to each
        computed region.

        The split: the ``ppermute``s are traced first; the interior
        (radius-``halo`` inset along communicated axes) is computed
        from purely local data while the collectives are in flight;
        the boundary shells are computed from the assembled padded
        block once halos land and stitched around the interior. The
        result is BIT-EXACT with the padded path at the operator
        output — identical tap offsets and per-element reduction order
        (pinned by tests/test_overlap.py) — so callers may flip
        ``overlap`` freely; infeasible configurations (nothing sharded,
        a communicated z axis, blocks thinner than
        ``MIN_INTERIOR_FACTOR * halo``) silently take the padded path.
        One scoping note: when the output feeds FURTHER pointwise
        arithmetic inside the same jit, the backend may contract FMAs
        differently across the stitch boundaries (~1 ulp per step,
        measured on CPU f64) — the same class of difference as any
        fusion-boundary change, not a reordering of the stencil math."""
        halo, exchange = self._canon_halo(halo, exchange)
        tm = jax.tree_util.tree_map
        leaves = jax.tree_util.tree_leaves(xs)
        shape = tuple(leaves[0].shape[-3:])
        split = self.split_axes(halo, shape) if overlap else ()

        def call(padded_xs, region):
            if extras is None:
                return apply_fn(padded_xs)
            return apply_fn(padded_xs, _slice_region(extras, region))

        if not split:
            padded = tm(lambda a: self.pad_with_halos(
                a, halo, exchange=exchange), xs)
            return call(padded, None)

        with trace_scope("halo_overlap"):
            # exchange first: the collective starts precede the interior
            # compute in program order, handing the latency-hiding
            # scheduler the dependence-free work to hide them behind
            padded = tm(lambda a: self.pad_with_halos(
                a, halo, exchange=exchange), xs)
            shells = HaloShells(padded, halo, split, shape)
            local_halo = tuple(0 if d in split else halo[d]
                               for d in range(3))
            local_ex = tuple(0 if d in split else exchange[d]
                             for d in range(3))
            with trace_scope("halo_overlap_interior"):
                interior_in = tm(
                    lambda a: self._pad_with_halos(
                        a, local_halo,
                        tuple(range(a.ndim - 3, a.ndim)), local_ex), xs)
                interior_out = call(interior_in, shells.interior_region())
            with trace_scope("halo_overlap_shells"):
                shell_outs = [call(inp, reg) for inp, reg in
                              zip(shells.inputs(), shells.regions())]
            return shells.stitch(interior_out, shell_outs)

    def share_halos(self, array, halo, outer_axes=0):
        """Standalone halo exchange on a global array: returns the *padded*
        global array (shape grown by ``2*halo`` per axis). Mostly useful for
        tests — production stencil ops fuse ``pad_with_halos`` into their own
        ``shard_map`` bodies. The jitted executable is cached per
        ``(halo, outer_axes)``, so repeated calls don't re-trace."""
        if np.isscalar(halo):
            halo = (halo,) * len(self.axis_names)
        halo = tuple(int(h) for h in halo)
        # exact host-level count of the per-axis exchanges this call
        # actually issues: only sharded axes with a nonzero halo ride
        # ppermute — unsharded axes wrap locally and an unsharded mesh
        # exchanges nothing at all (pad_with_halos itself runs at trace
        # time inside jitted consumers, where a counter would tally
        # traces, not executions)
        from pystella_tpu.obs import metrics as _metrics
        _metrics.counter("halo_exchanges").inc(len(self.comm_axes(halo)))
        fn = self._share_halos_cache.get((halo, outer_axes))
        if fn is None:
            spec = self.spec(outer_axes)

            def body(x):
                return self.pad_with_halos(x, halo)

            fn = _obs_memory.instrument_jit(
                self.shard_map(body, in_specs=spec, out_specs=spec),
                label="decomp.halo_pad")
            self._share_halos_cache[(halo, outer_axes)] = fn
        return fn(array)

    def shard_map(self, fn, in_specs, out_specs, **kwargs):
        """Thin wrapper over ``jax.shard_map`` bound to this mesh.
        ``check_vma=False`` is needed for bodies containing ``pallas_call``
        (jax 0.9.0: "`vma` on `jax.ShapeDtypeStruct` must not be `None`"
        — the kernels' ``out_shape`` carries no varying-mesh-axes
        annotation). On an ensemble decomposition the replication check
        is off by default: batched member bodies run under
        ``vmap(spmd_axis_name=<ensemble axis>)``, where member-batched
        operands are device-varying over
        the ensemble axis while unbatched captures (stencil
        coefficients, scalars) are replicated — a mix the checker
        rejects even though the program is correct (each member's
        stencil reads only its own ensemble slice)."""
        if self.ensemble_axis is not None:
            kwargs.setdefault("check_vma", False)
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, **kwargs)

    # -- decomposition from a device set (the re-mesh path) -----------------

    def with_devices(self, devices, proc_shape=None):
        """A new decomposition with the SAME halo widths and axis
        names over a different device set — the
        decomposition-from-device-set constructor the re-mesh library
        (:mod:`pystella_tpu.resilience.remesh`) builds degraded
        continuations from. ``proc_shape`` defaults to all devices
        along the leading axis; an ensemble decomposition cannot be
        rebuilt this way (its mesh carries the member axis — use
        :func:`ensemble_mesh` and the planner's ensemble path)."""
        if self.ensemble_axis is not None:
            raise ValueError(
                "with_devices rebuilds spatial decompositions only; "
                "build an ensemble_mesh for the member-axis path")
        return DomainDecomposition(
            proc_shape, halo_shape=self.halo_shape,
            axis_names=self.axis_names, devices=list(devices))

    # -- bookkeeping matching reference get_rank_shape_start ----------------

    def rank_shape(self, grid_shape):
        """Per-device block shape; requires divisibility (documented design
        decision — the reference supports uneven shards, decomp.py:322-337,
        but XLA sharding strongly prefers even blocks; pad the grid or choose
        a compatible mesh instead)."""
        for n, p in zip(grid_shape, self.proc_shape):
            if n % p:
                raise ValueError(
                    f"grid_shape {grid_shape} not divisible by proc_shape "
                    f"{self.proc_shape}; choose divisible shapes — "
                    "pystella_tpu.advise_shapes(grid_shape, n_devices) "
                    "lists the feasible meshes and the kernel tier each "
                    "subsystem takes on them")
        return tuple(n // p for n, p in zip(grid_shape, self.proc_shape))

    def __repr__(self):
        ens = (f", ensemble={self.ensemble_devices}"
               if self.ensemble_axis is not None else "")
        return f"DomainDecomposition(proc_shape={self.proc_shape}{ens})"


def _slice_region(tree, region):
    """Slice every lattice-shaped leaf (ndim >= 3, trailing lattice
    axes) of ``tree`` to the block-coordinate ``region`` (three
    ``(start, stop)`` pairs); scalars and low-rank leaves pass through
    untouched. ``region=None`` means the full block."""
    if tree is None or region is None:
        return tree

    def cut(a):
        nd = getattr(a, "ndim", 0)
        if nd < 3:
            return a
        idx = [slice(None)] * nd
        for d, (s, e) in enumerate(region):
            idx[nd - 3 + d] = slice(s, e)
        return a[tuple(idx)]

    return jax.tree_util.tree_map(cut, tree)


class HaloShells:
    """The shells half of the overlapped halo-exchange contract
    (:meth:`DomainDecomposition.pad_with_halos` with ``overlap=True``).

    Holds the fully assembled padded block(s) — the part that waits on
    the collectives — plus the bookkeeping that partitions the
    radius-``halo`` boundary into ``2 * len(comm_axes)`` shells (an
    onion partition: the shell pair of the k-th communicated axis spans
    the interior of earlier communicated axes and the full extent of
    everything else, so shells tile the boundary exactly once) and
    stitches shell outputs around an independently computed interior.

    All lattice axes are trailing, in both inputs and outputs.
    """

    def __init__(self, padded, halo, comm_axes, block_shape):
        self.padded = padded
        self.halo = tuple(halo)
        self.comm_axes = tuple(comm_axes)
        self.block_shape = tuple(block_shape)

    def interior_region(self):
        """Block-coordinate region the interior compute covers: the
        radius-``halo`` inset along communicated axes, full extent
        elsewhere."""
        return tuple(
            (self.halo[d], self.block_shape[d] - self.halo[d])
            if d in self.comm_axes else (0, self.block_shape[d])
            for d in range(3))

    def regions(self):
        """Output regions (block coordinates) of the shells, ordered
        ``(low, high)`` per communicated axis."""
        out = []
        for k, d in enumerate(self.comm_axes):
            n, h = self.block_shape[d], self.halo[d]
            for bounds in ((0, h), (n - h, n)):
                region = []
                for a in range(3):
                    na, ha = self.block_shape[a], self.halo[a]
                    if a == d:
                        region.append(bounds)
                    elif a in self.comm_axes[:k]:
                        region.append((ha, na - ha))
                    else:
                        region.append((0, na))
                out.append(tuple(region))
        return out

    def inputs(self):
        """One padded input block per shell — its stencil footprint:
        output rows ``[a, b)`` along an axis read padded rows
        ``[a, b + 2*halo)``."""
        ins = []
        for region in self.regions():
            def cut(p, region=region):
                idx = [slice(None)] * p.ndim
                for a, (s, e) in enumerate(region):
                    idx[p.ndim - 3 + a] = slice(s, e + 2 * self.halo[a])
                return p[tuple(idx)]
            ins.append(jax.tree_util.tree_map(cut, self.padded))
        return ins

    def stitch(self, interior_out, shell_outs):
        """Concatenate the shell outputs around the interior, innermost
        communicated axis first — the inverse of the onion partition.
        Works on matching pytrees of outputs (trailing lattice axes)."""
        res = interior_out
        for k in range(len(self.comm_axes) - 1, -1, -1):
            d = self.comm_axes[k]
            low, high = shell_outs[2 * k], shell_outs[2 * k + 1]
            res = jax.tree_util.tree_map(
                lambda lo, mid, hi, d=d: lax.concatenate(
                    [lo, mid, hi], dimension=mid.ndim - 3 + d),
                low, res, high)
        return res
