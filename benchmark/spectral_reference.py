"""The plain reference of upstream's ``--halo-shape 0`` run: the scalar
reference's RK54 and Friedmann steps (``benchmark/reference.py``: its
``_stage``, ``rho_and_p`` and coefficients, imported, not copied) with
spectral derivatives in place of the centred differences::

    lap f    = Re ifftn(-(kx^2 + ky^2 + kz^2) fftn(f))
    d_mu f   = Re ifftn(i k_mu fftn(f)),    k_mu = 0 at the zero and Nyquist modes

with ``k_mu = (2 pi / L_mu) * fftfreq`` mode numbers. It imports nothing
of ``pystella_tpu``; only ``benchmark/families/spectral_preheat.py``
imports it.

Departures from upstream's ``fourier/derivs.py:28-205``, each forced by
the chip or by what a reference is:

- upstream transforms real fields to a half spectrum (its ``DFT`` is
  r2c / c2r) and several fields a call. Here every field is cast to
  complex64 and goes through ``jnp.fft.fftn`` / ``ifftn`` whole, one field
  at a time: XLA's inverse *real* transform is wrong on the v5e
  (``PERF.md`` section 6, PR 28) while its complex transforms are right,
  and one complex field is 1.07 GB at 512^3. The imaginary part of the
  result (round-off of a Hermitian spectrum) is dropped.
- upstream folds the ``1 / grid_size`` of its unnormalised inverse into
  the symbol (``derivs.py:78-79``); ``ifftn`` is normalised.
- the Nyquist rule is upstream's (``derivs.py:53-60``): the odd
  derivative's momenta are zero at the Nyquist and zero modes, the
  Laplacian keeps ``k^2`` to the Nyquist mode, and nothing is dealiased.
- the momenta are arguments of the jitted functions, not constants of
  them: the compiler would fold ``kx^2 + ky^2 + kz^2`` into one array of
  the whole spectrum.

Everything runs under ``jax.default_matmul_precision("highest")``: on the
TPU a transform is matrix products, and so is a float32 product of any
other kind unless told otherwise.

**It checks itself** (:func:`roundtrip_gap`): ``ifftn(fftn(x))`` against
``x`` on the seeded fields, on the device it runs on.

**The control** (``inverse="matmul_bf16"``): the same code with the
inverse transform taken as real matrix products per axis in one bfloat16
pass (operands rounded to bfloat16, sums in float32), which is what the
TPU's *default* matmul precision does: the step below the full-precision
products the configuration states. Written out, so that it is the same
control on the CPU, where the default precision is float32's own.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import RK54_A, RK54_B, _stage, rho_and_p

INVERSES = ("fft", "matmul_bf16")


def momenta(grid_shape, box_dim, dtype=np.float32):
    """Per axis ``(k, k1)``: the momenta ``2 pi / L * fftfreq`` shaped to
    broadcast against a lattice array, and the same with the zero and
    Nyquist modes zeroed (the odd derivative's)."""
    out = []
    for mu, (n, length) in enumerate(zip(grid_shape, box_dim)):
        modes = np.fft.fftfreq(n, 1.0 / n)
        k = (2 * math.pi / float(length) * modes).astype(dtype)
        k1 = np.where((modes == 0) | (np.abs(modes) == n // 2), 0, k)
        shape = [1, 1, 1]
        shape[mu] = n
        out.append((jnp.asarray(k.reshape(shape)),
                    jnp.asarray(k1.astype(dtype).reshape(shape))))
    return out


def _ifft_axis_bf16(xk, axis):
    """The normalised inverse DFT of one axis as four real matrix
    products in one bfloat16 pass (the control's step down)."""
    n = xk.shape[axis]
    ang = 2 * np.pi * np.outer(np.arange(n), np.arange(n)) / n
    c = jnp.asarray(np.cos(ang) / n, jnp.float32)
    s = jnp.asarray(np.sin(ang) / n, jnp.float32)
    sub = {0: "ax,xyz->ayz", 1: "ay,xyz->xaz", 2: "az,xyz->xya"}[axis]

    def dot(m, v):
        return jnp.einsum(sub, m.astype(jnp.bfloat16),
                          v.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    re, im = jnp.real(xk), jnp.imag(xk)
    return jax.lax.complex(dot(c, re) - dot(s, im), dot(s, re) + dot(c, im))


def _inverse(fk, inverse):
    if inverse == "fft":
        return jnp.real(jnp.fft.ifftn(fk))
    for axis in range(3):
        fk = _ifft_axis_bf16(fk, axis)
    return jnp.real(fk)


@jax.jit
def _forward(fc):
    """The whole spectrum of one real component, in complex64 (a
    float64 component, which only the CPU tests bring, in complex128)."""
    return jnp.fft.fftn(fc.astype(jnp.result_type(fc.dtype, jnp.complex64)))


@functools.partial(jax.jit, static_argnames=("inverse",))
def _lap_of(fk, kx, ky, kz, *, inverse):
    return _inverse(-(kx * kx + ky * ky + kz * kz) * fk, inverse)


@functools.partial(jax.jit, static_argnames=("inverse",))
def _pd_of(fk, k1, *, inverse):
    return _inverse(1j * k1 * fk, inverse)


def laplacian(f, ks, inverse="fft", dtype=jnp.float32):
    """``lap f`` of ``f[comp, x, y, z]``, one component at a time; the
    components as a tuple, in ``dtype``."""
    if inverse not in INVERSES:
        raise ValueError(f"inverse {inverse!r}")
    (kx, _), (ky, _), (kz, _) = ks
    with jax.default_matmul_precision("highest"):
        return tuple(
            _lap_of(_forward(f[c]), kx, ky, kz,
                    inverse=inverse).astype(dtype)
            for c in range(f.shape[0]))


def gradient(fc, ks, inverse="fft"):
    """``(d_x f, d_y f, d_z f)`` of one component ``fc[x, y, z]``."""
    with jax.default_matmul_precision("highest"):
        fk = _forward(fc)
        return tuple(_pd_of(fk, k1, inverse=inverse) for _, k1 in ks)


def run(f, dfdt, nsteps, dt, phys, ks, grid_size, background,
        dtype=jnp.float32, carry_dtype=None, inverse="fft"):
    """Advance ``nsteps`` RK54 steps from ``(f, dfdt)`` (consumed), the
    background coupled: ``benchmark/reference.py``'s ``run`` with the
    spectral Laplacian. ``background`` is ``{"mode": "coupled", "a",
    "adot", "mpl"}``. Returns ``(f, dfdt, a, hubble)``."""
    if background["mode"] != "coupled":
        raise ValueError("the spectral reference steps a coupled "
                         "background only")
    dtype = jnp.dtype(dtype)
    carry_dtype = jnp.dtype(carry_dtype or dtype)
    f, dfdt = f.astype(dtype), dfdt.astype(dtype)
    kf = jnp.zeros_like(f, dtype=carry_dtype)
    kdf = jnp.zeros_like(f, dtype=carry_dtype)
    a, adot = float(background["a"]), float(background["adot"])
    mpl, hub = float(background["mpl"]), adot / a
    kw = dict(phys=tuple(sorted(phys.items())), carry_dtype=carry_dtype)
    for _ in range(nsteps):
        ka = kadot = 0.0
        for s in range(5):
            scal = jnp.asarray([a, hub, RK54_A[s], RK54_B[s], dt], dtype)
            f, dfdt, kf, kdf, sums = _stage(
                f, dfdt, kf, kdf, laplacian(f, ks, inverse, dtype), scal,
                **kw)
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


@functools.partial(jax.jit, static_argnames=("inverse",))
def _roundtrip(fc, *, inverse):
    return _inverse(_forward(fc), inverse)


def roundtrip_gap(f, inverse="fft"):
    """The reference's own transforms on this device: the largest
    ``|ifftn(fftn(x)) - x| / max |x|`` over the components of ``f``."""
    with jax.default_matmul_precision("highest"):
        return max(gap(_roundtrip(f[c], inverse=inverse), f[c])
                   for c in range(f.shape[0]))
