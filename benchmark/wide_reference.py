"""The plain reference of upstream's ``--halo-shape h`` runs, h = 1..4:
the scalar reference's RK54 and Friedmann stages (``benchmark/reference.py``:
its ``_stage``, ``rho_and_p``, ``_rho`` and coefficients, imported, not
copied) with centred differences of every radius the published tables
hold, where ``reference.py``'s own rows stop at h = 3.

It imports nothing of ``pystella_tpu``; only
``benchmark/families/wide_preheat.py`` imports it.

The rows are typed in from the tables (Fornberg 1988, table 1; upstream
``zachjweiner/pystella`` ``derivs.py:127-131`` and ``160-165`` hold the
same numbers over a common denominator), reduced fractions::

    second difference, offsets 0, 1, ... h        order
    h = 1:   -2        1                            2
    h = 2:   -5/2      4/3    -1/12                 4
    h = 3:   -49/18    3/2    -3/20   1/90          6
    h = 4:   -205/72   8/5    -1/5    8/315  -1/560 8

    first difference, offsets 1, ... h (antisymmetric)
    h = 1:   1/2
    h = 2:   2/3   -1/12
    h = 3:   3/4   -3/20   1/60
    h = 4:   4/5   -1/5    4/105  -1/280

No kernels, no decomposition, no blocking: a copy of one component
wrapped along one axis and shifted windows of the copy, one component
and one axis at a time so that 512^3 fits beside the state. Everything
is float32 (or the ``dtype`` the control lowers it to); no matrix product
appears anywhere in it, so ``jax.default_matmul_precision`` has nothing
to set.

**The control** ``h3``: the same code with the sixth-order rows where the
configuration states the eighth-order ones (``h=3`` for ``h=4``). A run
that takes a narrower stencil than the configuration states is a
different result, not a faster one, and the cell's limits have to say so.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import RK54_A, RK54_B, _rho, _stage, rho_and_p

#: centred second-difference rows by radius: offsets 0, 1, ... h
LAP_COEFS = {1: (-2.0, 1.0),
             2: (-5 / 2, 4 / 3, -1 / 12),
             3: (-49 / 18, 3 / 2, -3 / 20, 1 / 90),
             4: (-205 / 72, 8 / 5, -1 / 5, 8 / 315, -1 / 560)}

#: centred first-difference rows by radius: offsets 1, ... h
GRAD_COEFS = {1: (1 / 2,),
              2: (2 / 3, -1 / 12),
              3: (3 / 4, -3 / 20, 1 / 60),
              4: (4 / 5, -1 / 5, 4 / 105, -1 / 280)}


def _windows(fc, axis, h):
    """``window(s)``: ``fc`` shifted by ``s`` sites along ``axis`` with
    periodic wrap, ``|s| <= h``, as slices of one wrapped copy (the
    arithmetic of ``jnp.roll``, which the TPU compiler would keep whole
    in memory once per shift)."""
    n = fc.shape[axis]
    pad = [(0, 0)] * 3
    pad[axis] = (h, h)
    fp = jnp.pad(fc, pad, mode="wrap")

    def window(shift):
        idx = [slice(None)] * 3
        idx[axis] = slice(h + shift, h + shift + n)
        return fp[tuple(idx)]
    return window


@functools.partial(jax.jit, static_argnames=("axis", "h", "inv_dx2"),
                   donate_argnums=(1,))
def _lap_axis(fc, acc, *, axis, h, inv_dx2):
    """``acc`` plus the second difference of one component along one
    axis."""
    coefs = LAP_COEFS[h]
    window = _windows(fc, axis, h)
    out = coefs[0] * fc
    for s in range(1, h + 1):
        out = out + coefs[s] * (window(s) + window(-s))
    return acc + out * inv_dx2


@functools.partial(jax.jit, static_argnames=("axis", "h", "inv_dx"))
def _pd_axis(fc, *, axis, h, inv_dx):
    """The first difference of one component along one axis."""
    coefs = GRAD_COEFS[h]
    window = _windows(fc, axis, h)
    return sum(coefs[s - 1] * (window(s) - window(-s))
               for s in range(1, h + 1)) * inv_dx


@functools.partial(jax.jit, donate_argnums=(1,))
def _add_square(d, acc):
    return acc + d * d


def _radius(h):
    h = int(h)
    if h not in LAP_COEFS:
        raise ValueError(f"stencil radius {h}: the tables hold "
                         f"{min(LAP_COEFS)}-{max(LAP_COEFS)}")
    return h


def laplacian(f, dx, h):
    """Periodic centred Laplacian of ``f[comp, x, y, z]`` at radius
    ``h``; the components as a tuple."""
    h = _radius(h)
    comps = []
    for c in range(f.shape[0]):
        fc = f[c]
        acc = jnp.zeros_like(fc)
        for axis, d in enumerate(dx):
            acc = _lap_axis(fc, acc, axis=axis, h=h,
                            inv_dx2=1.0 / float(d) ** 2)
        comps.append(acc)
    return tuple(comps)


def partial(fc, dx, h, axis):
    """``d f / d x_axis`` of one component ``fc[x, y, z]``."""
    return _pd_axis(fc, axis=axis, h=_radius(h),
                    inv_dx=1.0 / float(dx[axis]))


def gradient(fc, dx, h):
    """``(d_x f, d_y f, d_z f)`` of one component ``fc[x, y, z]``."""
    return tuple(partial(fc, dx, h, axis) for axis in range(3))


def energy_density(f, dfdt, a, hubble, phys, dx, h, mpl, dtype=jnp.float32):
    """``rho / rho_bar`` on the lattice (``reference.py``'s ``_rho``) with
    the gradient energy from the first differences of radius ``h``."""
    dtype = jnp.dtype(dtype)
    f, dfdt = f.astype(dtype), dfdt.astype(dtype)
    grad_sq = jnp.zeros(f.shape[1:], dtype)
    for c in range(f.shape[0]):
        for d in gradient(f[c], dx, h):
            grad_sq = _add_square(d, grad_sq)
    scal = jnp.asarray(
        [a, 3 * mpl ** 2 * hubble ** 2 / (8 * math.pi)], dtype)
    return _rho(f, dfdt, grad_sq, scal, phys=tuple(sorted(phys.items())))


def run(f, dfdt, nsteps, dt, phys, dx, h, grid_size, background,
        dtype=jnp.float32, carry_dtype=None):
    """Advance ``nsteps`` RK54 steps from ``(f, dfdt)`` (consumed):
    ``benchmark/reference.py``'s ``run`` with the Laplacian of radius
    ``h``. ``background`` is ``{"mode": "fixed", "a", "hubble"}`` or
    ``{"mode": "coupled", "a", "adot", "mpl"}``. Returns ``(f, dfdt, a,
    hubble)``, ``hubble`` being the conformal ``a'/a`` it ended on."""
    h = _radius(h)
    dtype = jnp.dtype(dtype)
    carry_dtype = jnp.dtype(carry_dtype or dtype)
    f, dfdt = f.astype(dtype), dfdt.astype(dtype)
    kf = jnp.zeros_like(f, dtype=carry_dtype)
    kdf = jnp.zeros_like(f, dtype=carry_dtype)
    coupled = background["mode"] == "coupled"
    a = float(background["a"])
    if coupled:
        adot, mpl = float(background["adot"]), float(background["mpl"])
        hub = adot / a
    else:
        adot, hub = None, float(background["hubble"])
    kw = dict(phys=tuple(sorted(phys.items())), carry_dtype=carry_dtype)
    for _ in range(nsteps):
        ka = kadot = 0.0
        for s in range(5):
            scal = jnp.asarray([a, hub, RK54_A[s], RK54_B[s], dt], dtype)
            f, dfdt, kf, kdf, sums = _stage(
                f, dfdt, kf, kdf, laplacian(f, dx, h), scal, **kw)
            if coupled:
                rho, p = rho_and_p(sums, a, grid_size)
                addot = 4 * math.pi * a ** 3 / 3 / mpl ** 2 * (rho - 3 * p)
                ka = RK54_A[s] * ka + dt * adot
                kadot = RK54_A[s] * kadot + dt * addot
                a, adot = a + RK54_B[s] * ka, adot + RK54_B[s] * kadot
                hub = adot / a
    return f, dfdt, a, hub


@jax.jit
def _gap(got, ref):
    ref = ref.astype(jnp.float32)
    got = got.astype(jnp.float32)
    return (jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)),
            jnp.all(jnp.isfinite(got)))


def gap(got, ref):
    """``max |got - ref| / max |ref|`` of one lattice array; ``inf`` where
    ``got`` holds a non-finite value."""
    rel, finite = _gap(got, ref)
    return float(rel) if bool(finite) else math.inf
