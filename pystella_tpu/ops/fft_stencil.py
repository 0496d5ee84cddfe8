"""FFT-applied stencils: large-radius or repeated stencil application
as one k-space multiply through the distributed transform.

Per "Fast Stencil Computations using FFTs" (PAPERS.md, arxiv
2105.06676): a periodic linear stencil is a circular convolution, so
its application is diagonal in Fourier space — ``n`` applications of a
radius-``r`` stencil cost ONE forward/inverse transform pair plus an
elementwise multiply by the stencil symbol raised to the ``n``-th
power, instead of ``n`` sweeps of ``O(r)`` taps over the lattice. With
the sharded pencil transform (:mod:`pystella_tpu.fourier.pencil`) the
whole application is shard-local between its all_to_all transposes, so
the fast path scales to lattices no single device holds.

The crossover against the direct tier
(:class:`~pystella_tpu.FiniteDifferencer` /
:class:`~pystella_tpu.StreamingStencil`) is a flops model: direct
costs ``repeats · taps(r) · 2 · N`` flops (``taps = 6r + 1`` for the
axis-separable stencils the package builds), the transform pair
``2 · 5 N log₂ N`` — so FFT wins for large ``r·repeats`` and loses for
one application of a compact stencil. :func:`use_fft_stencil` applies
the model (with an env-tunable safety ratio for the transpose traffic
the flops model does not see); ``PYSTELLA_FFT_STENCIL=1/0`` forces
either path.

Symbols are *stencil-consistent* eigenvalues (``effective_k``-style,
like the Poisson solver's), so ``fft_laplacian(fft, dx, h)`` applied
once is EXACTLY the order-``2h`` finite-difference Laplacian of the
periodic field (up to transform roundoff), and applied ``n`` times is
exactly ``n`` sweeps of it.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["FFTStencil", "fft_laplacian", "stencil_flops",
           "transform_flops", "use_fft_stencil"]


def stencil_flops(grid_shape, radius, repeats=1, taps=None):
    """Direct-tier flops: ``repeats`` sweeps of a ``taps``-point
    stencil (default the axis-separable ``6r + 1`` the package's
    centered differences use), one multiply-add per tap per site."""
    n = int(np.prod(grid_shape))
    if taps is None:
        taps = 6 * int(radius) + 1
    return int(repeats) * int(taps) * 2 * n


def transform_flops(grid_shape, pair=True):
    """FFT-tier flops by the standard ``5 N log₂ N`` model (the same
    model the perf ledger's ``fft`` roofline section uses); ``pair``
    counts forward AND inverse."""
    n = int(np.prod(grid_shape))
    return (2 if pair else 1) * int(5 * n * math.log2(max(n, 2)))


def use_fft_stencil(grid_shape, radius, repeats=1, taps=None,
                    override=None):
    """Should this application take the k-space path? Resolution:
    explicit ``override`` > ``PYSTELLA_FFT_STENCIL`` env (1/0) > the
    flops crossover model — direct flops must exceed
    ``PYSTELLA_FFT_STENCIL_CROSSOVER`` × the transform-pair flops
    (the margin covers the transpose traffic the model ignores)."""
    if override is not None:
        return bool(override)
    from pystella_tpu import config as _config
    setting = (_config.getenv("PYSTELLA_FFT_STENCIL") or "auto")
    setting = str(setting).strip().lower()
    if setting in ("1", "true", "on", "yes"):
        return True
    if setting in ("0", "false", "off", "no"):
        return False
    ratio = _config.get_float("PYSTELLA_FFT_STENCIL_CROSSOVER")
    return (stencil_flops(grid_shape, radius, repeats, taps)
            > ratio * transform_flops(grid_shape))


class FFTStencil:
    """Apply a periodic stencil as a k-space multiply through ``fft``.

    :arg fft: a :class:`~pystella_tpu.fourier.DFT` or
        :class:`~pystella_tpu.fourier.pencil.PencilFFT` (use
        :func:`pystella_tpu.make_dft` for the distributed tier).
    :arg symbol: the stencil's k-space symbol as a device array
        broadcastable against the transform's k-space arrays (build
        per-axis factors with ``fft.k_axis_array``), or a callable
        ``(kx, ky, kz) -> symbol`` over those broadcast axis arrays.
    :arg radius: the equivalent direct-stencil radius (crossover
        accounting only).

    ``stencil(f, repeats=n)`` computes ``n`` applications in one
    transform pair (symbol raised to the ``n``-th power in-graph);
    ``apply_if_profitable`` consults :func:`use_fft_stencil` and
    returns ``None`` when the direct tier should run instead.
    """

    def __init__(self, fft, symbol, radius=1, name="fft_stencil"):
        self.fft = fft
        self.radius = int(radius)
        self.name = str(name)
        if callable(symbol):
            kx, ky, kz = (fft.k_axis_array(mu, kk)
                          for mu, kk in enumerate(fft.sub_k.values()))
            symbol = symbol(kx, ky, kz)
        self._symbol = symbol

        def impl(fx, symbol, repeats):
            with jax.named_scope("fft_stencil"):
                fk = self.fft._dft_impl(fx)
                fk = fk * (symbol if repeats == 1
                           else symbol ** repeats)
                out = self.fft._idft_impl(fk)
                return out.astype(fx.dtype) if self.fft.is_real else out

        from pystella_tpu.obs import memory as _obs_memory
        self._apply = _obs_memory.instrument_jit(
            impl, label=f"ops.{self.name}", static_argnums=2)

    def __call__(self, fx, repeats=1):
        """``repeats`` stencil applications through one transform
        pair."""
        return self._apply(fx, self._symbol, int(repeats))

    def apply_if_profitable(self, fx, repeats=1, override=None):
        """The k-space result when the crossover model (or the
        override/env) selects this path, else ``None`` — the caller
        then runs its direct tier; the decision is static (shapes and
        knobs only), so mixed programs stay jit-compatible."""
        if not use_fft_stencil(self.fft.grid_shape, self.radius,
                               repeats, override=override):
            return None
        return self(fx, repeats=repeats)


def fft_laplacian(fft, dx, halo_shape=2):
    """The order-``2h`` finite-difference Laplacian as an
    :class:`FFTStencil`: per-axis ``SecondCenteredDifference``
    eigenvalues summed into the (negative semi-definite) symbol —
    applied once it matches :meth:`FiniteDifferencer.lap` on periodic
    fields, applied ``n`` times it matches ``n`` sweeps, at one
    transform pair total."""
    from pystella_tpu.ops.derivs import SecondCenteredDifference
    h = int(halo_shape)
    eig = SecondCenteredDifference(h).get_eigenvalues
    if np.isscalar(dx):
        dx = (dx,) * 3
    grid = fft.grid_shape
    rdtype = fft.rdtype
    parts = []
    for mu, kk in enumerate(fft.sub_k.values()):
        dk = 2 * np.pi / (grid[mu] * dx[mu])
        vals = np.asarray(eig(dk * kk.astype(rdtype), dx[mu]), rdtype)
        parts.append(fft.k_axis_array(mu, vals))
    symbol = sum(parts)
    return FFTStencil(fft, symbol, radius=h, name="fft_laplacian")
