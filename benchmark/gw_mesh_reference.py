"""The plain reference of upstream's ``-gws`` run on a mesh:
``benchmark/gw_reference.py``'s steps and ``benchmark/reference.py``'s
outputs, for a lattice that several chips share, **without ever laying a
lattice-sized array on one chip**. It imports nothing of ``pystella_tpu``;
only ``benchmark/families/gw_mesh_preheat.py`` imports it.

**Why a file of its own** (PR 50, ``preheat-gw-mesh4-f32``: global
(768, 768, 384) on ``(2, 2, 1)``, 0.91 GB a component). The accepted
``-gws`` references run on one chip and need no edit there; on a mesh
three things in them do not hold, and none of those files is this PR's
to edit:

- ``gw_reference.run`` starts its 24 tensor and register components as
  ``jnp.zeros(lattice)``, which lays each whole on the first chip: 21.7
  GB there at this size. Here they start as zeros of the fields' own
  sharding (:func:`run`; the stage, the gradients and the Laplacian are
  ``gw_reference``'s and ``reference``'s own, imported);
- ``reference._mode_power`` and ``gw_reference._transform`` take
  ``jnp.fft.rfftn`` of a whole component, which on a component that four
  chips share gathers it onto every chip, and then fold the half
  spectrum by slices that the program's mesh (whose axes are typed
  explicitly) refuses outright: ``slicing on sharded dims where out dim
  (385) is not divisible by mesh axes (2)``. Here the transform is
  ``spectral_mesh_reference``'s (three one-axis ``jnp.fft.fft`` calls
  inside ``jax.shard_map``, resharded between by sharding constraints:
  the benchmark's own, imported), a component at a time, and the fold
  is taken an axis at a time where that axis is whole on every chip
  (:func:`_folded`);
- everything is first laid on the reference's own mesh of plain
  automatic axes (``spectral_mesh_reference.at_home``: the same blocks
  on the same chips, nothing moves).

**The full spectrum for the half.** The program and the one-chip
references bin the half spectrum of a real transform (``k_z`` = 0 ...
N/2, a mode counted twice unless it lies on the zero or the Nyquist
plane). Here the whole complex spectrum is taken and the ``-k_z`` half
folded onto the ``+k_z`` half like the other two axes: for a real field
``|x(-k)|^2 = |x(k)|^2``, so once the signs of ``k_x`` and ``k_y`` are
summed over, the mode ``-k_z`` brings exactly the second count. The
result has the shape ``reference.SpectrumBins`` bins: ``(|k_x|, |k_y|,
k_z)``, an eighth of the lattice; the x and y folds are made on the
chips, the z fold and the binning on the host in float64.

The projector's momenta are the stencil's (``gw_reference.
effective_momenta``, odd in ``k``, mirrored here onto the ``-k_z``
half); the projection itself is ``gw_reference.tt_project``.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import gw_reference, reference
from benchmark.gw_reference import (  # noqa: F401  (re-exported)
    PAIRS, gw_gap, tensor_gap)
from benchmark.reference import RK54_A, RK54_B
from benchmark.spectral_mesh_reference import (
    _fftn, _local, at_home, layouts)


# -- the steps ---------------------------------------------------------------

def run(f, dfdt, nsteps, dt, phys, dx, h, grid_size, background,
        dtype=jnp.float32, carry_dtype=None):
    """``gw_reference.run`` on a mesh: ``nsteps`` RK54 steps of the
    scalars (consumed) and of the tensor perturbations, which start from
    zero in the fields' own layout. Returns ``({"f", "dfdt", "hij",
    "dhijdt"}, a, hubble)``."""
    if background["mode"] != "coupled":
        raise ValueError("the -gws reference runs a coupled background")
    dtype = jnp.dtype(dtype)
    carry_dtype = jnp.dtype(carry_dtype or dtype)
    with jax.default_matmul_precision("highest"):
        f, dfdt = at_home(f).astype(dtype), at_home(dfdt).astype(dtype)
        home = layouts(f)[0]

        def zeros(dt_):
            return [jnp.zeros(f.shape[1:], dt_, device=home) for _ in PAIRS]

        kf = jnp.zeros_like(f, dtype=carry_dtype)
        kdf = jnp.zeros_like(f, dtype=carry_dtype)
        hij, dhij = zeros(dtype), zeros(dtype)
        khij, kdhij = zeros(carry_dtype), zeros(carry_dtype)
        a, adot = float(background["a"]), float(background["adot"])
        mpl = float(background["mpl"])
        hub = adot / a
        kw = dict(phys=tuple(sorted(phys.items())), carry_dtype=carry_dtype)
        dx, h = tuple(float(d) for d in dx), int(h)
        for _ in range(nsteps):
            ka = kadot = 0.0
            for s in range(5):
                # the tensors first: their source is of the f this stage
                # starts from, which the scalar stage consumes
                grads = gw_reference.gradients(f, dx, h)
                scal = jnp.asarray([hub, RK54_A[s], RK54_B[s], dt], dtype)
                for c, (i, j) in enumerate(PAIRS):
                    hij[c], dhij[c], khij[c], kdhij[c] = \
                        gw_reference._tensor_stage(
                            hij[c], dhij[c], khij[c], kdhij[c],
                            gw_reference._lap_component(hij[c], dx, h),
                            [g[i] for g in grads], [g[j] for g in grads],
                            scal, carry_dtype=carry_dtype)
                del grads
                scal = jnp.asarray([a, hub, RK54_A[s], RK54_B[s], dt],
                                   dtype)
                f, dfdt, kf, kdf, sums = reference._stage(
                    f, dfdt, kf, kdf, reference.laplacian(f, dx, h), scal,
                    **kw)
                rho, p = reference.rho_and_p(sums, a, grid_size)
                addot = 4 * math.pi * a ** 3 / 3 / mpl ** 2 * (rho - 3 * p)
                ka = RK54_A[s] * ka + dt * adot
                kadot = RK54_A[s] * kadot + dt * addot
                a, adot = a + RK54_B[s] * ka, adot + RK54_B[s] * kadot
                hub = adot / a
        return ({"f": f, "dfdt": dfdt, "hij": jnp.stack(hij),
                 "dhijdt": jnp.stack(dhij)}, a, hub)


# -- mode powers over (|k_x|, |k_y|, k_z), no chip holding a whole array -----

@functools.lru_cache(maxsize=None)
def _programs(lay):
    """The jitted pieces for one layout: a component's whole spectrum,
    laid out with x whole, and the x and y folds of a power."""
    x_whole = lay[2]
    mesh = x_whole.mesh
    # y whole: z shared by all the chips
    y_local = NamedSharding(mesh, P(None, None, mesh.axis_names))

    @functools.partial(jax.jit, static_argnames=("dtype",),
                       out_shardings=x_whole)
    def forward(x, *, dtype):
        """The unnormalised transform of one real component, its mean
        taken off; what goes in rounded to ``dtype`` (the control)."""
        x = reference._rounded(x.astype(jnp.float32), dtype)
        return _fftn(x - jnp.mean(x), lay)

    @functools.partial(jax.jit, static_argnames=("power_dtype",),
                       out_shardings=x_whole)
    def power_of(xk, *, power_dtype):
        return reference._rounded(xk.real ** 2 + xk.imag ** 2, power_dtype)

    @functools.partial(jax.jit, static_argnames=("power_dtype",),
                       out_shardings=x_whole)
    def tt_power(hk, kx, ky, kz, *, power_dtype):
        """``sum_ab |h^TT_ab(k)|^2`` over the whole spectrum."""
        power = sum(c.real ** 2 + c.imag ** 2 for row in
                    gw_reference.tt_project(hk, kx, ky, kz) for c in row)
        return reference._rounded(power, power_dtype)

    @functools.partial(jax.jit, out_shardings=y_local)
    def fold_xy(power):
        """Each fold on blocks that hold its axis whole."""
        p = _local(functools.partial(reference._fold, axis=0), x_whole)(power)
        p = jax.lax.with_sharding_constraint(p, y_local)
        return _local(functools.partial(reference._fold, axis=1), y_local)(p)

    return forward, power_of, tt_power, fold_xy


def _folded(power, fold_xy):
    """A whole-spectrum power over ``(|k_x|, |k_y|, k_z)`` on the host,
    float64: x and y folded on the chips, each where it is whole, z on
    the host."""
    p = np.asarray(fold_xy(power)).astype(np.float64)
    n = p.shape[2]
    out = p[:, :, :n // 2 + 1].copy()
    out[:, :, 1:n // 2] += p[:, :, :n // 2:-1]
    return out


def _binned(power, bins):
    return np.bincount(bins.index, weights=power.ravel() * bins.weight,
                       minlength=bins.num_bins) / bins.bin_counts


def spectrum(x, bins, dtype=jnp.float32, power_dtype=None):
    """``reference.spectrum``: ``Delta^2(k)`` per bin of one real lattice
    array."""
    x = at_home(x)
    forward, power_of, _, fold_xy = _programs(layouts(x))
    with jax.default_matmul_precision("highest"):
        power = power_of(forward(x, dtype=jnp.dtype(dtype)),
                         power_dtype=jnp.dtype(power_dtype or dtype))
        return bins.norm * _binned(_folded(power, fold_xy), bins)


def output(f, dfdt, a, hubble, phys, dx, h, mpl, bins, hist_bins,
           dtype=jnp.float32, power_dtype=None):
    """``reference.output``: everything one scalar output writes, the
    energy density and the histograms ``reference``'s own."""
    f, dfdt = at_home(f), at_home(dfdt)
    rho = reference.energy_density(f, dfdt, a, hubble, phys, dx, h, mpl,
                                   dtype)
    out = {"hist": reference.histograms(rho, hist_bins),
           "scalar": np.stack([spectrum(f[c], bins, dtype, power_dtype)
                               for c in range(f.shape[0])]),
           "rho": spectrum(rho, bins, dtype, power_dtype)}
    del rho
    return out


def whole_axis_momenta(grid_shape, box_dim, dx, h):
    """``gw_reference.effective_momenta`` with the last axis' modes in
    ``fftfreq`` order too: the stencil's momenta are odd in ``k``."""
    kx, ky, kz = gw_reference.effective_momenta(grid_shape, box_dim, dx, h)
    return kx, ky, np.concatenate([kz, -kz[-2:0:-1]])


def gw_spectrum(dhijdt, hubble, bins, grid_shape, box_dim, dx, h,
                dtype=jnp.float32, power_dtype=None):
    """``gw_reference.gw_spectrum``: ``Omega_gw(k)`` per bin from the six
    packed components of ``h_ij'`` and the conformal Hubble rate."""
    dtype = jnp.dtype(dtype)
    dhijdt = at_home(dhijdt)
    forward, _, tt_power, fold_xy = _programs(layouts(dhijdt[0]))
    with jax.default_matmul_precision("highest"):
        hk = [forward(dhijdt[c], dtype=dtype) for c in range(len(PAIRS))]
        eff = [jnp.asarray(e, jnp.float32) for e in
               whole_axis_momenta(grid_shape, box_dim, dx, h)]
        power = tt_power(hk, *eff,
                         power_dtype=jnp.dtype(power_dtype or dtype))
        del hk
        sums = _binned(_folded(power, fold_xy), bins)
    return bins.norm / 12 / float(hubble) ** 2 * sums
