"""The plain reference of upstream's ``--halo-shape 0`` run on a mesh:
``benchmark/spectral_reference.py``'s derivatives::

    lap f    = Re ifftn(-(kx^2 + ky^2 + kz^2) fftn(f))
    d_mu f   = Re ifftn(i k_mu fftn(f)),    k_mu = 0 at the zero and Nyquist modes

on complex64 under ``jax.default_matmul_precision("highest")``, one
component at a time, stepping ``benchmark/reference.py``'s own RK54 and
Friedmann stages (its ``_stage``, ``rho_and_p`` and coefficients; the
momenta, ``gap`` and the ``matmul_bf16`` control are
``spectral_reference``'s: all imported, none copied), with a transform
that **does not gather**. It imports nothing of ``pystella_tpu``; only
``benchmark/families/spectral_mesh_preheat.py`` imports it.

**Why a file of its own** (PR 46, ``preheat-spectral-mesh4-f32``).
``spectral_reference`` takes ``jnp.fft.fftn`` of a whole component. On a
component that four chips share the partitioner answers that with an
``all-gather`` to the whole array on every chip (and so it does for a
one-axis ``jnp.fft.fft`` under plain ``jit`` with only its batch axes
sharded): 4.3 GB in and 4.3 GB out a chip at (1024, 1024, 512). Here
``fftn`` is three one-axis ``jnp.fft.fft`` calls, each taken inside a
``jax.shard_map`` on blocks that hold the transformed axis whole, with
the reshards between them written as sharding constraints over the same
chips::

    home P(x, y, None)  --fft z-->  P(x, None, y)  --fft y-->  P(None, x, y)  --fft x

and back the same way, so no chip ever holds more than its share (a
quarter, on four) of any lattice-sized array; which collectives move
the blocks is the partitioner's choice (``all-to-all``s: the CPU test
and the v5e compile read the module for an ``all-gather`` and find
none). The mesh is the reference's own, made of the chips the array
lies on, each where its block puts it (:func:`layouts`); an array on
one device takes the same code on a mesh of that one device, where it
is ``spectral_reference`` to round-off
(``tests/test_spectral_mesh_reference.py``).

It shares no line with the program's ``fourier/pencil.py`` or
``fourier/dft.py``: a complex full spectrum of one field at a time where
the program takes a real half spectrum of both; XLA's complex inverse
where the program takes matrix products; sharding constraints where
``PencilFFT`` calls ``all_to_all``.

**The control** (``inverse="matmul_bf16"``) is ``spectral_reference``'s
one-bfloat16-pass inverse of an axis, applied to the same blocks.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.reference import RK54_A, RK54_B, _stage, rho_and_p
from benchmark.spectral_reference import (  # noqa: F401  (re-exported)
    INVERSES, _ifft_axis_bf16, gap, momenta)


def layouts(x):
    """The three layouts of a component ``x[..., x, y, z]`` over the
    chips it lies on, each with another axis whole: ``(home, y_whole,
    x_whole)`` = ``P(x, y, None)``, ``P(x, None, y)``, ``P(None, x, y)``
    as ``NamedSharding``s of a mesh of the reference's own (plain
    automatic axes: the program's mesh types its axes explicitly, and a
    sharding constraint may not name those), whose chips stand where
    ``x``'s blocks put them: chip ``(i, j)`` holds block ``(i, j)``, so
    laying ``x`` out as ``home`` moves nothing."""
    starts = {
        device: tuple(s.start or 0 for s in index[-3:])
        for device, index in x.sharding.devices_indices_map(x.shape).items()}
    xs, ys, zs = (sorted({s[i] for s in starts.values()}) for i in range(3))
    chips = np.full((len(xs), len(ys)), None, object)
    for device, (x0, y0, _) in starts.items():
        chips[xs.index(x0), ys.index(y0)] = device
    if len(zs) > 1 or len(starts) != chips.size:
        raise ValueError(
            "spectral_mesh_reference: a component has to lie in (x, y) "
            f"blocks, one a chip, with z whole; got {x.sharding}")
    mesh = Mesh(chips, ("x", "y"))
    return tuple(NamedSharding(mesh, P(*spec)) for spec in (
        ("x", "y", None), ("x", None, "y"), (None, "x", "y")))


def at_home(x):
    """``x[..., x, y, z]`` on the reference's own mesh, in its home
    layout: the same blocks on the same chips."""
    home = layouts(x)[0]
    lead = (None,) * (x.ndim - 3)
    return jax.device_put(x, NamedSharding(home.mesh, P(*lead, *home.spec)))


def _local(fn, sharding):
    """``fn`` on every chip's own block of an array laid out as
    ``sharding``: the block holds the axis ``fn`` transforms whole, so
    nothing is exchanged and nothing gathered."""
    return jax.shard_map(fn, mesh=sharding.mesh, in_specs=sharding.spec,
                         out_specs=sharding.spec)


def _fftn(fc, lay):
    """The whole spectrum of one real component, complex64 (a float64
    component, which only the CPU tests bring, complex128), laid out
    with x whole."""
    home, y_whole, x_whole = lay
    x = fc.astype(jnp.result_type(fc.dtype, jnp.complex64))
    x = _local(lambda b: jnp.fft.fft(b, axis=2), home)(x)
    x = jax.lax.with_sharding_constraint(x, y_whole)
    x = _local(lambda b: jnp.fft.fft(b, axis=1), y_whole)(x)
    x = jax.lax.with_sharding_constraint(x, x_whole)
    return _local(lambda b: jnp.fft.fft(b, axis=0), x_whole)(x)


def _ifftn_real(fk, lay, inverse):
    """``Re ifftn`` of a spectrum laid out with x whole, back home."""
    home, y_whole, x_whole = lay
    if inverse == "fft":
        def axis_inverse(axis):
            return lambda b: jnp.fft.ifft(b, axis=axis)
    else:
        def axis_inverse(axis):
            return lambda b: _ifft_axis_bf16(b, axis)
    x = _local(axis_inverse(0), x_whole)(fk)
    x = jax.lax.with_sharding_constraint(x, y_whole)
    x = _local(axis_inverse(1), y_whole)(x)
    x = jax.lax.with_sharding_constraint(x, home)
    return jnp.real(_local(axis_inverse(2), home)(x))


@functools.lru_cache(maxsize=None)
def _programs(lay, inverse):
    """The jitted pieces for one layout and inverse. The momenta are
    arguments, not constants (the compiler would fold ``kx^2 + ky^2 +
    kz^2`` into one array of the whole spectrum)."""
    home = lay[0]

    @functools.partial(jax.jit, out_shardings=lay[2])
    def forward(fc):
        return _fftn(fc, lay)

    @functools.partial(jax.jit, out_shardings=home)
    def lap_of(fk, kx, ky, kz):
        return _ifftn_real(-(kx * kx + ky * ky + kz * kz) * fk, lay,
                           inverse)

    @functools.partial(jax.jit, out_shardings=home)
    def pd_of(fk, k1):
        return _ifftn_real(1j * k1 * fk, lay, inverse)

    @functools.partial(jax.jit, out_shardings=home)
    def roundtrip(fc):
        return _ifftn_real(_fftn(fc, lay), lay, inverse)

    return forward, lap_of, pd_of, roundtrip


def _prepared(fc, inverse):
    """``fc`` at home, and the four programs of its layout."""
    if inverse not in INVERSES:
        raise ValueError(f"inverse {inverse!r}")
    fc = at_home(fc)
    return (fc,) + _programs(layouts(fc), inverse)


def laplacian(f, ks, inverse="fft", dtype=jnp.float32):
    """``lap f`` of ``f[comp, x, y, z]``, one component at a time; the
    components as a tuple, in ``dtype``, each in the home layout."""
    (kx, _), (ky, _), (kz, _) = ks
    out = []
    with jax.default_matmul_precision("highest"):
        for c in range(f.shape[0]):
            fc, forward, lap_of, _, _ = _prepared(f[c], inverse)
            out.append(lap_of(forward(fc), kx, ky, kz).astype(dtype))
    return tuple(out)


def gradient(fc, ks, inverse="fft"):
    """``(d_x f, d_y f, d_z f)`` of one component ``fc[x, y, z]``."""
    with jax.default_matmul_precision("highest"):
        fc, forward, _, pd_of, _ = _prepared(fc, inverse)
        fk = forward(fc)
        return tuple(pd_of(fk, k1) for _, k1 in ks)


def partial_derivative(fc, ks, mu, inverse="fft"):
    """``d_mu f`` of one component alone: what a check that must never
    hold ``grad`` whole takes, a direction at a time."""
    with jax.default_matmul_precision("highest"):
        fc, forward, _, pd_of, _ = _prepared(fc, inverse)
        return pd_of(forward(fc), ks[mu][1])


def run(f, dfdt, nsteps, dt, phys, ks, grid_size, background,
        dtype=jnp.float32, carry_dtype=None, inverse="fft"):
    """Advance ``nsteps`` RK54 steps from ``(f, dfdt)`` (consumed), the
    background coupled: ``spectral_reference.run`` with this module's
    Laplacian. Returns ``(f, dfdt, a, hubble)``."""
    if background["mode"] != "coupled":
        raise ValueError("the spectral reference steps a coupled "
                         "background only")
    dtype = jnp.dtype(dtype)
    carry_dtype = jnp.dtype(carry_dtype or dtype)
    f, dfdt = at_home(f).astype(dtype), at_home(dfdt).astype(dtype)
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


def roundtrip_gap(f, inverse="fft"):
    """The reference's own transforms on these devices: the largest
    ``|ifftn(fftn(x)) - x| / max |x|`` over the components of ``f``."""
    gaps = []
    with jax.default_matmul_precision("highest"):
        for c in range(f.shape[0]):
            fc, _, _, _, roundtrip = _prepared(f[c], inverse)
            gaps.append(gap(roundtrip(fc), fc))
    return max(gaps)
