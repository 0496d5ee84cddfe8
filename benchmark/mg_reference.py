"""The multigrid family's plain reference: upstream's multigrid benchmark
(``zachjweiner/pystella test/test_multigrid.py:42-106``) in ``jax.numpy``
and float32. It imports nothing of ``pystella_tpu``.

What upstream describes, and what is here:

- Two problems on a periodic box, solved side by side: Poisson ``lap f =
  rho`` and Helmholtz ``lap f2 - f2 = rho2``. Here a problem is its
  ``mass`` (0 or 1): ``L f = lap f - mass f``.
- ``NewtonIterator`` with ``omega = 1/2``, ``h = 1``: one sweep is ``f <-
  f - omega (L f - rho) / D`` at every site from the old ``f`` (a damped
  Jacobi step for a linear problem), ``D = dL/df`` with the Laplacian's
  centre weight in it: ``-6/dx**2 - mass``, typed in below. The
  Laplacian is the second-order centred one by ``jnp.roll``.
- ``FullApproximationScheme``, default cycle V(25, 50) to depth
  ``log2(N/8)``: 25 sweeps at each level going down, 50 at the coarsest
  and at each level coming up. Down: the unknown is restricted, and the
  coarse source is the restricted fine residual plus the coarse operator
  on the restricted unknown (the tau correction). Up: the coarse change
  ``f_coarse - R f_fine`` is interpolated and added (restrict-and-correct,
  interpolate-and-correct).
- Full weighting (1/4, 1/2, 1/4 an axis, centred on the fine site ``(2i,
  2j, 2k)``) down, linear interpolation up.
- The error of a level is the residual ``rho - L f``: its largest
  absolute value and ``sqrt(mean(r**2))``.

Departures from upstream's description, each for a reason:

- float32 where upstream runs float64 (the configuration's ``dtype``:
  the chip has no float64); ``dtype`` below lowers it further for the
  control, which keeps the unknowns and does the arithmetic in bfloat16.
- The two problems do not touch each other, so each is solved by a
  V-cycle of its own; upstream's kernels sweep both in one pass. The
  numbers are the same: no operation mixes them.
- Upstream ping-pongs two arrays with a halo exchange between sweeps; on
  one device a sweep is a function of the whole old array, with the
  periodic wrap in ``jnp.roll``.
- Upstream's solver records the error before and after every smooth of a
  cycle; only the finest level's before the first and after the last
  are asked of this reference (``v_cycle`` returns the latter).
"""

import functools

import jax
import jax.numpy as jnp

OMEGA = 0.5
NU = (25, 50)


def laplacian(f, dx):
    """Second-order centred Laplacian on a periodic lattice."""
    acc = -6.0 * f
    for axis in range(3):
        acc = acc + jnp.roll(f, 1, axis) + jnp.roll(f, -1, axis)
    return acc * jnp.asarray(1.0 / dx ** 2, f.dtype)


def operator(f, dx, mass):
    """``L f = lap f - mass f``."""
    lap = laplacian(f, dx)
    return lap - f if mass else lap


def residual(f, rho, dx, mass):
    return rho - operator(f, dx, mass)


def norms(r):
    """``(L-infinity, L2)`` of a residual, in at least float32."""
    r = r.astype(jnp.promote_types(r.dtype, jnp.float32))
    return jnp.max(jnp.abs(r)), jnp.sqrt(jnp.mean(r * r))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def smooth(f, rho, dx, mass, nu):
    """``nu`` Newton sweeps with ``omega = 1/2``."""
    diag = -6.0 / dx ** 2 - (1.0 if mass else 0.0)
    step = jnp.asarray(OMEGA / diag, f.dtype)

    def sweep(_, f):
        return f - step * (operator(f, dx, mass) - rho)

    return jax.lax.fori_loop(0, nu, sweep, f)


@jax.jit
def restrict(x):
    """Full weighting: coarse site ``i`` takes 1/4, 1/2, 1/4 of the fine
    sites ``2i - 1, 2i, 2i + 1`` along each axis."""
    for axis in range(3):
        x = (0.25 * jnp.roll(x, 1, axis) + 0.5 * x
             + 0.25 * jnp.roll(x, -1, axis))
        x = jax.lax.slice_in_dim(x, 0, x.shape[axis], stride=2, axis=axis)
    return x


@jax.jit
def interpolate(x):
    """Linear interpolation: fine site ``2i`` is coarse site ``i``, fine
    site ``2i + 1`` the mean of coarse sites ``i`` and ``i + 1``."""
    for axis in range(3):
        odd = 0.5 * (x + jnp.roll(x, -1, axis))
        shape = list(x.shape)
        shape[axis] *= 2
        x = jnp.stack([x, odd], axis=axis + 1).reshape(shape)
    return x


@functools.partial(jax.jit, static_argnums=(2, 3))
def coarse_source(f_fine, rho_fine, dx, mass):
    """The restricted unknown and the tau-corrected coarse source:
    ``R(rho - L f) + L_coarse(R f)``."""
    f_coarse = restrict(f_fine)
    r = restrict(residual(f_fine, rho_fine, dx, mass))
    return f_coarse, r + operator(f_coarse, 2 * dx, mass)


@jax.jit
def correct(f_fine, f_coarse):
    """``f_fine + I(f_coarse - R f_fine)``."""
    return f_fine + interpolate(f_coarse - restrict(f_fine))


def v_cycle(f, rho, dx, mass, depth, nu=NU):
    """One V(nu1, nu2) FAS cycle of one problem from level 0 (spacing
    ``dx``) to level ``depth``; returns the new unknown."""
    fs, rhos = [f], [rho]
    for i in range(depth):
        fs[i] = smooth(fs[i], rhos[i], dx * 2 ** i, mass, nu[0])
        f_c, rho_c = coarse_source(fs[i], rhos[i], dx * 2 ** i, mass)
        fs.append(f_c)
        rhos.append(rho_c)
    fs[depth] = smooth(fs[depth], rhos[depth], dx * 2 ** depth, mass, nu[1])
    for i in range(depth - 1, -1, -1):
        fs[i] = correct(fs[i], fs.pop())
        fs[i] = smooth(fs[i], rhos[i], dx * 2 ** i, mass, nu[1])
    return fs[0]


def solve(f, rho, dx, mass, depth, cycles, nu=NU, dtype=None):
    """``cycles`` V-cycles of one problem, each on the last one's
    unknown. ``dtype``: what the unknown and the source are kept in and
    the arithmetic is done in (default: as given). Returns the unknown
    and the residual's ``(L-infinity, L2)`` before the first cycle and
    after the last, as the arithmetic of that precision sees them."""
    if dtype is not None:
        f, rho = f.astype(dtype), rho.astype(dtype)
    before = norms(residual(f, rho, dx, mass))
    for _ in range(cycles):
        f = v_cycle(f, rho, dx, mass, depth, nu)
    return f, before, norms(residual(f, rho, dx, mass))


def solution_gap(got, ref, mean_free):
    """``max|got - ref| / max|ref|``; with ``mean_free`` each has its mean
    taken off first (Poisson's solution on a periodic box is free up to
    a constant, which neither sweeps nor transfers pin)."""
    wide = jnp.promote_types(jnp.result_type(ref), jnp.float32)
    got, ref = jnp.asarray(got, wide), jnp.asarray(ref, wide)
    if mean_free:
        got, ref = got - jnp.mean(got), ref - jnp.mean(ref)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
