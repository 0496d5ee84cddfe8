"""The plain reference: scalar-field preheating in conformal FLRW time,
written out in ``jax.numpy`` from the published equations.

It imports nothing of ``pystella_tpu`` and takes nothing the program has
made except the seeded initial state, which is the input data of both.
No kernels, no decomposition, no blocking: periodic centred differences on
a wrapped copy of the field, the Carpenter-Kennedy RK54 coefficients typed in
from the paper, the Friedmann equations on host floats (float64).

Equations (upstream pystella ``sectors.py`` / ``expansion.py``; fields in
units of the Planck mass, time in units of ``1/mphi``)::

    f''   = lap f - 2 H f' - a^2 dV/df            (H = a'/a, conformal)
    rho   = sum_i (f_i'^2 - f_i lap f_i) / (2 a^2) + V
    p     = sum_i (f_i'^2 + f_i lap f_i / 3) / (2 a^2) - V
    a''   = 4 pi a^3 (rho - 3 p) / (3 mpl^2)

with lattice means for ``rho`` and ``p`` and exact feedback: every RK stage
of the fields is followed by the same stage of ``(a, a')`` fed with the
energy of the state that stage started from.

The arithmetic precision is a parameter so that the *control* of the
benchmark's ``correct`` decision can run the same code one precision
step down (``bfloat16`` for this ``float32`` configuration).

The second half is the reference of what an *output* writes (upstream
``fourier/spectra.py``, ``histogram.py``, ``reduction.py``): the energy
density map, its histograms, the power spectra of the fields and of the
map, and the statistics row::

    rho/rho_bar = (sum_i f_i'^2 / 2 + sum_i |grad f_i|^2 / 2 + a^2 V)
                  / (3 mpl^2 H^2 / 8 pi)
    Delta^2(k)  = (d^3x)^2 / (2 pi^2 V) * <|k|^3 |f(k)|^2>_bin

with ``f(k)`` the unnormalised forward transform, a mode's bin
``round(|k| / min dk)``, and a mode of the half spectrum counted twice
unless it lies on the ``k_z = 0`` or Nyquist plane. The transform is
``jnp.fft.rfftn``; the binning, the histograms (``numpy.histogram``) and
their float64 sums are done on the host.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: Carpenter & Kennedy (1994), five-stage fourth-order 2N-storage scheme
RK54_A = (0.0,
          -567301805773 / 1357537059087,
          -2404267990393 / 2016746695238,
          -3550918686646 / 2091501179385,
          -1275806237668 / 842570457699)
RK54_B = (1432997174477 / 9575080441755,
          5161836677717 / 13612068292357,
          1720146321549 / 2090206949498,
          3134564353537 / 4481467310338,
          2277821191437 / 14882151754819)

#: centred second-difference coefficients by stencil radius
LAP_COEFS = {1: (-2.0, 1.0),
             2: (-30 / 12, 16 / 12, -1 / 12),
             3: (-490 / 180, 270 / 180, -27 / 180, 2 / 180)}


def potential(f, phys):
    """``V(phi, chi) / mphi^2`` of the two-field model."""
    phi, chi = f[0], f[1]
    m2 = phys["mphi"] ** 2
    return (phys["mphi"] ** 2 / 2 * phi ** 2
            + phys["mchi"] ** 2 / 2 * chi ** 2
            + phys["gsq"] / 2 * phi ** 2 * chi ** 2
            + phys["sigma"] / 2 * phi * chi ** 2
            + phys["lambda4"] / 4 * chi ** 4) / m2


def dpotential(f, phys):
    """``dV/dphi, dV/dchi`` (over ``mphi^2``), by hand."""
    phi, chi = f[0], f[1]
    m2 = phys["mphi"] ** 2
    dphi = (phys["mphi"] ** 2 * phi + phys["gsq"] * phi * chi ** 2
            + phys["sigma"] / 2 * chi ** 2) / m2
    dchi = (phys["mchi"] ** 2 * chi + phys["gsq"] * phi ** 2 * chi
            + phys["sigma"] * phi * chi + phys["lambda4"] * chi ** 3) / m2
    return jnp.stack([dphi, dchi])


@functools.partial(jax.jit, static_argnames=("axis", "h", "inv_dx2"),
                   donate_argnums=(1,))
def _lap_axis(fc, acc, *, axis, h, inv_dx2):
    """``acc`` plus the second difference of one component along one axis:
    a copy of it wrapped along that axis, and shifted windows of the copy
    (the arithmetic of ``jnp.roll``, which the TPU compiler would keep
    whole in memory once per shift)."""
    coefs = LAP_COEFS[h]
    n = fc.shape[axis]
    pad = [(0, 0)] * 3
    pad[axis] = (h, h)
    fp = jnp.pad(fc, pad, mode="wrap")

    def window(shift):
        idx = [slice(None)] * 3
        idx[axis] = slice(h + shift, h + shift + n)
        return fp[tuple(idx)]

    out = coefs[0] * fc
    for s in range(1, h + 1):
        out = out + coefs[s] * (window(s) + window(-s))
    return acc + out * inv_dx2


def laplacian(f, dx, h):
    """Periodic centred Laplacian of ``f[comp, x, y, z]``, one component
    and one axis at a time so that 512^3 per chip fits beside the state;
    returns the components as a tuple."""
    comps = []
    for c in range(f.shape[0]):
        fc = f[c]
        acc = jnp.zeros_like(fc)
        for axis, d in enumerate(dx):
            acc = _lap_axis(fc, acc, axis=axis, h=h, inv_dx2=1.0 / d ** 2)
        comps.append(acc)
    return tuple(comps)


@functools.partial(jax.jit, static_argnames=("phys", "carry_dtype"),
                   donate_argnums=(0, 1, 2, 3))
def _stage(f, dfdt, kf, kdf, lap, scal, *, phys, carry_dtype):
    """One 2N-storage stage given ``lap f``; also the energy sums of the
    state it started from. ``scal = (a, hubble, A, B, dt)`` in the
    fields' dtype."""
    phys = dict(phys)
    a, hub, A, B, dt = (scal[i] for i in range(5))
    kf, kdf = kf.astype(f.dtype), kdf.astype(f.dtype)
    lap = jnp.stack(lap)
    rhs_df = lap - 2 * hub * dfdt - a * a * dpotential(f, phys)
    # sums in float32 whatever the fields' precision: the control lowers
    # the lattice arithmetic, not the bookkeeping of the background
    f32 = jnp.float32
    sums = jnp.stack([jnp.sum((dfdt * dfdt).astype(f32)),
                      jnp.sum((-f * lap).astype(f32)),
                      jnp.sum(potential(f, phys).astype(f32))])
    kf = A * kf + dt * dfdt
    f2 = f + B * kf
    kdf = A * kdf + dt * rhs_df
    df2 = dfdt + B * kdf
    return f2, df2, kf.astype(carry_dtype), kdf.astype(carry_dtype), sums


def rho_and_p(sums, a, grid_size):
    kin, grad, pot = (float(s) for s in sums)
    inv = 1.0 / (2.0 * a * a * grid_size)
    kin, grad, pot = kin * inv, grad * inv, pot / grid_size
    return kin + grad + pot, kin - grad / 3.0 - pot


def run(f, dfdt, nsteps, dt, phys, dx, h, grid_size, background,
        dtype=jnp.float32, carry_dtype=None):
    """Advance ``nsteps`` RK54 steps from ``(f, dfdt)`` (consumed).

    ``background`` is ``{"mode": "fixed", "a", "hubble"}`` or
    ``{"mode": "coupled", "a", "adot", "mpl"}``. Returns ``(f, dfdt, a,
    hubble)``, ``hubble`` being the conformal ``a'/a`` it ended on."""
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
    dx, h = tuple(float(d) for d in dx), int(h)
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
    """Largest difference over a field, against the reference's largest
    value of that component (one number per component)."""
    axes = tuple(range(1, ref.ndim))
    ref = ref.astype(jnp.float32)
    diff = jnp.max(jnp.abs(got.astype(jnp.float32) - ref), axis=axes)
    finite = jnp.all(jnp.isfinite(got))
    return diff / jnp.max(jnp.abs(ref), axis=axes), finite


def field_gap(got, ref):
    """``max |got - ref| / max |ref|`` over the components of both fields
    of a state; ``inf`` where ``got`` holds a non-finite value."""
    worst = 0.0
    for name in ("f", "dfdt"):
        rel, finite = _gap(got[name], ref[name])
        if not bool(finite):
            return math.inf
        worst = max(worst, float(jnp.max(rel)))
    return worst


# -- what an output writes --------------------------------------------------

#: centred first-difference coefficients by stencil radius
GRAD_COEFS = {1: (1 / 2,), 2: (8 / 12, -1 / 12),
              3: (45 / 60, -9 / 60, 1 / 60)}


@functools.partial(jax.jit, static_argnames=("axis", "h", "inv_dx"),
                   donate_argnums=(1,))
def _grad_sq_axis(fc, acc, *, axis, h, inv_dx):
    """``acc`` plus the squared first difference of one component along
    one axis (wrapped copy and shifted windows, as ``_lap_axis``)."""
    coefs = GRAD_COEFS[h]
    n = fc.shape[axis]
    pad = [(0, 0)] * 3
    pad[axis] = (h, h)
    fp = jnp.pad(fc, pad, mode="wrap")

    def window(shift):
        idx = [slice(None)] * 3
        idx[axis] = slice(h + shift, h + shift + n)
        return fp[tuple(idx)]

    d = sum(coefs[s - 1] * (window(s) - window(-s)) for s in range(1, h + 1))
    d = d * inv_dx
    return acc + d * d


@functools.partial(jax.jit, static_argnames=("phys",), donate_argnums=(2,))
def _rho(f, dfdt, grad_sq, scal, *, phys):
    a, a_sq_rho = scal[0], scal[1]
    t00 = (jnp.sum(dfdt * dfdt, axis=0) / 2 + grad_sq / 2
           + a * a * potential(f, dict(phys)))
    return t00 / a_sq_rho


def energy_density(f, dfdt, a, hubble, phys, dx, h, mpl, dtype=jnp.float32):
    """``rho / rho_bar`` on the lattice, in ``dtype`` arithmetic."""
    dtype = jnp.dtype(dtype)
    f, dfdt = f.astype(dtype), dfdt.astype(dtype)
    grad_sq = jnp.zeros(f.shape[1:], dtype)
    for c in range(f.shape[0]):
        for axis, d in enumerate(dx):
            grad_sq = _grad_sq_axis(f[c], grad_sq, axis=axis, h=int(h),
                                    inv_dx=1.0 / float(d))
    scal = jnp.asarray(
        [a, 3 * mpl ** 2 * hubble ** 2 / (8 * math.pi)], dtype)
    return _rho(f, dfdt, grad_sq, scal, phys=tuple(sorted(phys.items())))


def _multiplicity(n):
    """How many of an axis' modes share each ``|k|``: one at zero and at
    Nyquist, two between."""
    m = np.full(n // 2 + 1, 2.0)
    m[0] = m[-1] = 1.0
    return m


class SpectrumBins:
    """Per ``(|k_x|, |k_y|, k_z)`` of the half spectrum: its bin and its
    weight ``|k|^3`` (host, float64), and the number of modes per bin.
    Modes that differ in the sign of ``k_x`` or ``k_y`` share both, so
    the device adds their powers before the host bins them
    (``_fold``), which leaves the host an eighth of the lattice."""

    def __init__(self, grid_shape, box_dim):
        dk = [2 * math.pi / float(b) for b in box_dim]
        n = [int(g) for g in grid_shape]
        if n[0] % 2 or n[1] % 2 or n[2] % 2:
            raise ValueError("even grids only")
        kx, ky, kz = np.meshgrid(
            *[dki * np.arange(ni // 2 + 1) for dki, ni in zip(dk, n)],
            indexing="ij", sparse=True)
        kmag = np.sqrt(kx * kx + ky * ky + kz * kz)
        # a mode of the half spectrum stands for two of the full one,
        # unless it lies on the k_z = 0 or the Nyquist plane
        count = (_multiplicity(n[0])[:, None, None]
                 * _multiplicity(n[1])[None, :, None]
                 * _multiplicity(n[2])[None, None, :])
        self.index = np.rint(kmag / min(dk)).astype(np.int64).ravel()
        self.num_bins = int(self.index.max()) + 1
        self.weight = (kmag * kmag * kmag).ravel()
        self.bin_counts = np.bincount(self.index, weights=count.ravel(),
                                      minlength=self.num_bins)
        volume = float(np.prod([float(b) for b in box_dim]))
        d3x = volume / float(np.prod(n))
        self.norm = d3x ** 2 / (2 * math.pi ** 2 * volume)


def _fold(p, axis):
    """Add the entries of ``-k`` to those of ``+k`` along ``axis`` (in
    ``fftfreq`` order): ``n`` entries become ``n // 2 + 1``."""
    n = p.shape[axis]
    take = lambda sl: p[tuple(sl if a == axis else slice(None)
                              for a in range(p.ndim))]
    head = take(slice(0, n // 2 + 1))
    tail = jnp.flip(take(slice(n // 2 + 1, n)), axis)
    pad = [(0, 0)] * p.ndim
    pad[axis] = (1, 1)
    return head + jnp.pad(tail, pad)


def _rounded(x, dtype):
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


@functools.partial(jax.jit, static_argnames=("dtype", "power_dtype"))
def _mode_power(x, *, dtype, power_dtype):
    """``|x(k)|^2`` of one real lattice array, over ``(|k_x|, |k_y|,
    k_z)``, the half spectrum's second copy of a mode counted in. The
    mean is taken off first: it lives in the ``k = 0`` mode alone, whose
    weight is zero, and off the transform it would leak float32 round-off
    into every mode. The transform itself has no precision below float32,
    so the control rounds what goes in to ``dtype`` and what comes out to
    ``power_dtype`` (``reduce_precision``: the compiler drops a cast down
    and up again as excess precision allowed)."""
    x = _rounded(x.astype(jnp.float32), dtype)
    xk = jnp.fft.rfftn(x - jnp.mean(x))
    power = _rounded(xk.real ** 2 + xk.imag ** 2, power_dtype)
    nz = power.shape[2]
    twice = jnp.where((jnp.arange(nz) == 0) | (jnp.arange(nz) == nz - 1),
                      1.0, 2.0)
    return _fold(_fold(power * twice, 0), 1)


def spectrum(x, bins, dtype=jnp.float32, power_dtype=None):
    """``Delta^2(k)`` per bin of one real lattice array."""
    power = np.asarray(_mode_power(
        x, dtype=jnp.dtype(dtype),
        power_dtype=jnp.dtype(power_dtype or dtype)))
    sums = np.bincount(bins.index,
                       weights=power.ravel().astype(np.float64) * bins.weight,
                       minlength=bins.num_bins)
    return bins.norm * sums / bins.bin_counts


@functools.partial(jax.jit, static_argnames=("num_bins",))
def _bin_numbers(x, *, num_bins):
    """Each site's linear and log bin, ``floor(num_bins (x - min) / (max
    - min))`` with the last bin closed, and the four bounds."""
    logx = jnp.log(jnp.abs(x))

    def number(v):
        lo, hi = jnp.min(v), jnp.max(v)
        hi = jnp.where(hi > lo, hi, lo + 1)     # a constant array: one bin
        b = jnp.floor((v - lo) / (hi - lo) * num_bins)
        return jnp.clip(b, 0, num_bins - 1).astype(jnp.int16), lo, hi

    return number(x), number(logx)


def histograms(x, num_bins):
    """Linear and log histograms of a lattice array with the bounds taken
    from the data, as ``{"linear", "linear_bins", "log", "log_bins"}``:
    the sites' bin numbers from the device, counted on the host."""
    out = {}
    for name, (b, lo, hi) in zip(("linear", "log"), _bin_numbers(
            x.astype(jnp.float32), num_bins=int(num_bins))):
        out[name] = np.bincount(np.asarray(b).ravel(), minlength=num_bins)
        edges = np.linspace(float(lo), float(hi), num_bins + 1)
        out[name + "_bins"] = np.exp(edges) if name == "log" else edges
    return out


def output(f, dfdt, a, hubble, phys, dx, h, mpl, bins, hist_bins,
           dtype=jnp.float32, power_dtype=None):
    """Everything one output writes, from the state ``(f, dfdt)`` and the
    background ``(a, hubble)``."""
    rho = energy_density(f, dfdt, a, hubble, phys, dx, h, mpl, dtype)
    out = {"hist": histograms(rho, hist_bins),
           "scalar": np.stack([spectrum(f[c], bins, dtype, power_dtype)
                               for c in range(f.shape[0])]),
           "rho": spectrum(rho, bins, dtype, power_dtype)}
    del rho
    return out


@jax.jit
def _moments(fc):
    """A first mean of one component, then the mean and the mean square
    of what is left about it: two passes, so that the float32 sums of the
    second carry the fluctuation and not the offset."""
    c = jnp.mean(fc)
    d = fc - c
    return c, jnp.mean(d), jnp.mean(d * d)


def statistics(f):
    """``{"mean", "variance"}`` per component, float64 on the host."""
    mean, var = [], []
    for c in range(f.shape[0]):
        c0, m1, m2 = (float(v) for v in _moments(f[c].astype(jnp.float32)))
        mean.append(c0 + m1)
        var.append(m2 - m1 * m1)
    return {"mean": np.array(mean), "variance": np.array(var)}


# -- the gaps between what the program wrote and the reference -------------

def spectra_gaps(got, ref):
    """Per spectrum (``scalar0``, ``scalar1``, ..., ``rho``) the largest
    ``|got - ref| / ref`` over its bins (a bin the reference has empty
    must be empty). One number per spectrum, because their sound gaps lie
    four orders apart: a field with an offset leaks round-off into the
    few-mode bins of the program's spectrum, a field without does not."""
    gaps = {}
    for name in ("scalar", "rho"):
        g = np.asarray(got[name], np.float64)
        r = np.asarray(ref[name], np.float64)
        same = g.shape == r.shape and np.all(np.isfinite(g))
        g, r = g.reshape(-1, g.shape[-1]), r.reshape(-1, r.shape[-1])
        for c in range(r.shape[0]):
            key = name if name == "rho" else f"{name}{c}"
            if not same:
                gaps[key] = math.inf
                continue
            scale = np.where(r[c] > 0, r[c], np.max(r[c]) * 1e-30)
            gaps[key] = float(np.max(np.abs(g[c] - r[c]) / scale))
    return gaps


def hist_gaps(got, ref):
    """``(hist_gap, hist_edge_gap)``: the largest distance between the
    cumulative distributions (a share of the sites; sites the program
    failed to count are added), and the largest distance between bin
    edges (a share of the range; log bins in the log). Counts bin by bin
    cannot be compared: a float32 site on a bin edge falls either way."""
    gap = edge = 0.0
    for name in ("linear", "log"):
        g = np.asarray(got[name], np.float64)
        r = np.asarray(ref[name], np.float64)
        if g.shape[-1] != r.shape[-1] or not np.all(np.isfinite(g)):
            return math.inf, math.inf
        g = g.reshape(-1, g.shape[-1])[0]
        n = r.sum()
        gap = max(gap, float(np.max(np.abs(np.cumsum(g) - np.cumsum(r))) / n
                             + abs(g.sum() - n) / n))
        ge = np.asarray(got[name + "_bins"], np.float64)
        ge = ge.reshape(-1, ge.shape[-1])[0]
        re_ = np.asarray(ref[name + "_bins"], np.float64)
        if name == "log":
            ge, re_ = np.log(ge), np.log(re_)
        edge = max(edge, float(np.max(np.abs(ge - re_))
                               / (re_[-1] - re_[0])))
    return gap, edge


def stats_gap(got, ref):
    """Largest gap of a component's mean (against its root mean square)
    and of its second moment ``variance + mean^2`` (relative). The
    variance alone is not compared: in float32, about a mean five
    thousand standard deviations off zero, it is round-off."""
    gm = np.asarray(got["mean"], np.float64).ravel()
    gv = np.asarray(got["variance"], np.float64).ravel()
    rm, rv = ref["mean"], ref["variance"]
    if gm.shape != rm.shape or not (np.all(np.isfinite(gm))
                                    and np.all(np.isfinite(gv))):
        return math.inf
    second = rv + rm * rm
    return float(max(np.max(np.abs(gm - rm) / np.sqrt(second)),
                     np.max(np.abs(gv + gm * gm - second) / second)))
