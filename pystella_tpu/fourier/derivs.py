"""Spectral-collocation derivatives.

TPU-native counterpart of /root/reference/pystella/fourier/derivs.py:28-205:
the same interface as :class:`~pystella_tpu.FiniteDifferencer`, computing
derivatives by FFT → multiply by ``i k`` (Nyquist modes zeroed for odd
derivatives) or ``-k²`` → inverse FFT. Because :meth:`DFT.idft` is already
normalized, no manual ``1/grid_size`` factor is needed (unlike
derivs.py:78-79).

What a trace and the event log say of it (``doc/observability.md``
"Trace scopes"): the five programs are ``jit_spectral_lap``, ``_grad``,
``_grad_lap``, ``_pd`` and ``_div``; inside them, and inside a caller's
program that inlines them (a stepper's stage program whose right-hand
side calls :meth:`SpectralCollocator.lap`), the ops lie under the scopes
``spectral_forward``, ``spectral_symbol`` and ``spectral_inverse``; the
host spans ``spectral_lap_dispatch`` and ``spectral_grad_dispatch`` lie
round the two calls a driver loop makes; one ``spectral_plan`` event a
built collocator says which transform and which inverse it got, on
which mesh, and what a transform pays between chips (the transposes a
forward and an inverse transform make, and the bytes of one field's
k-space block a chip, which each of them rearranges). On a mesh the
transform's own ops lie, inside the collocator's scopes, under
``fft_transpose`` (what goes between chips) and ``fft_stage`` (what
stays on one), on either tier (``fourier/dft.py``, ``fourier/pencil.py``).

**The Laplacian it last returned** (PR 47). The reference-style loop
takes ``derivs.lap(state["f"])`` for the energy and then runs a stage
program whose right-hand side takes ``derivs.lap`` of that same array:
ten two-field transform pairs a step where upstream, whose energy fills
the ``lap_f`` the next stage reads, does five. So an eager
:meth:`SpectralCollocator.lap` on a ``jax.Array`` remembers one pair, the
array it was given (weakly) and the array it returned, in place of the
pair before (:class:`pystella_tpu.handoff.LastLaplacian`). The pair is
forgotten at the start of the collocator's next eager call of any kind,
before that call allocates; when the array it was made from is
collected; and the moment a stage dispatch is passed that array, which
is what it is for: the generic stepper's per-stage dispatch
(``step.py``), about to pass a state or carry of which a leaf **is**
that array (the same object, not deleted), passes the Laplacian as one
more argument if nobody else holds it any more, as nobody holds the
loop's. It is donated: an output takes its buffer, and a ``weakref`` or
a second array over that buffer finds it deleted. One that anybody
holds by a name or in a container is not handed in, and survives. While
the program that takes one is traced, :meth:`~SpectralCollocator.lap`,
called by the right-hand side on the tracer of that very leaf, returns
the argument's tracer and builds no transform. Any other call under
trace (another array, a leaf the right-hand side touched first, a program
with nothing handed in) builds the transforms as ever. Nothing switches
this on or off: it depends on the identity of an array alone, and a hit
is the value the program would have computed again. ``grad``,
``grad_lap``, ``pd*`` and ``divergence`` remember nothing.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from pystella_tpu import handoff as _handoff
from pystella_tpu.obs import events as _events
from pystella_tpu.obs import memory as _obs_memory
from pystella_tpu.obs.scope import host_span, trace_scope

__all__ = ["SpectralCollocator"]


def _platform(decomp):
    """The platform of the devices ``decomp`` places arrays on."""
    return decomp.mesh.devices.flat[0].platform


class SpectralCollocator:
    """Spectral derivatives of sharded lattice fields (functional: returns
    new arrays).

    :arg fft: a :class:`~pystella_tpu.fourier.DFT`.
    :arg dk: momentum-space grid spacing per axis.
    """

    def __init__(self, fft, dk, **kwargs):
        self.fft = fft
        self.decomp = fft.decomp
        rdtype = fft.rdtype
        inverse = "matmul" if fft._matmul_inverse else "xla"
        if fft.is_real and inverse == "xla" \
                and _platform(self.decomp) == "tpu":
            raise ValueError(
                "SpectralCollocator: this DFT brings a real field back by "
                "XLA's inverse real transform, which is wrong on the TPU "
                "(a third of the signal's rms off: PERF.md section 6, PR "
                "28), so every derivative would be; build the transform "
                "with DFT(..., real_inverse=\"matmul\")")

        # momentum arrays in the transform's own k layout
        # (fft.k_axis_array): the multiplies stay elementwise on the
        # pencil tier's natural layout too
        self._k1 = []  # first-derivative momenta (zero & Nyquist zeroed)
        self._k2 = []  # second-derivative momenta
        for mu, kk in enumerate(fft.sub_k.values()):
            kk_int = kk.astype(int)
            k2 = (dk[mu] * kk.astype(rdtype))
            k1 = k2.copy()
            k1[np.abs(kk_int) == fft.grid_shape[mu] // 2] = 0.0
            k1[kk_int == 0] = 0.0
            self._k1.append(fft.k_axis_array(mu, k1))
            self._k2.append(fft.k_axis_array(mu, k2))

        def program(impl, name, **jit_kwargs):
            return _obs_memory.instrument_jit(
                impl, label="fourier.spectral_" + name, **jit_kwargs)

        self._lap = program(self._lap_impl, "lap")
        self._grad = program(self._grad_impl, "grad")
        self._grad_lap = program(self._grad_lap_impl, "grad_lap")
        self._pd = program(self._pd_impl, "pd", static_argnums=1)
        self._div = program(self._div_impl, "div")
        #: the last Laplacian an eager call returned, and of which array
        self._last_lap = _handoff.LastLaplacian("SpectralCollocator.lap")
        from pystella_tpu.fourier.plan import mesh_plan
        _events.emit("spectral_plan", inverse=inverse, fields_a_call="all",
                     **mesh_plan(fft))

    # -- the three parts of every derivative, each under its scope ---------

    def _forward(self, fx):
        with trace_scope("spectral_forward"):
            return self.fft._dft_impl(fx)

    def _inverse(self, fk, dtype):
        with trace_scope("spectral_inverse"):
            return self.fft._idft_impl(fk).astype(dtype)

    def _minus_ksq(self):
        """``-(kx² + ky² + kz²)``, added up where it is used. The three
        axes' momenta are constants of the program, and the compiler
        would fold their broadcast sum into one of the whole half
        spectrum (269 MB at 512³ in every program that takes a
        Laplacian, and minutes of compile time: PR 34); behind the
        barrier the sum fuses into the multiply that reads it."""
        kx, ky, kz = jax.lax.optimization_barrier(tuple(self._k2))
        return -(kx * kx + ky * ky + kz * kz)

    def _lap_impl(self, fx):
        fk = self._forward(fx)
        with trace_scope("spectral_symbol"):
            lap_k = self._minus_ksq() * fk
        return self._inverse(lap_k, fx.dtype)

    def _pd_k(self, fk, mu):
        with trace_scope("spectral_symbol"):
            return 1j * self._k1[mu] * fk

    def _pd_impl(self, fx, mu):
        return self._inverse(self._pd_k(self._forward(fx), mu), fx.dtype)

    def _grad_of(self, fk, dtype):
        return jnp.stack([self._inverse(self._pd_k(fk, mu), dtype)
                          for mu in range(3)], axis=fk.ndim - 3)

    def _grad_impl(self, fx):
        return self._grad_of(self._forward(fx), fx.dtype)

    def _grad_lap_impl(self, fx):
        fk = self._forward(fx)
        grd = self._grad_of(fk, fx.dtype)
        with trace_scope("spectral_symbol"):
            lap_k = self._minus_ksq() * fk
        return grd, self._inverse(lap_k, fx.dtype)

    def _div_impl(self, vec):
        # sum the i*k_mu-weighted spectra in k-space: one inverse FFT
        # instead of three (the forward transforms batch over the
        # component axis)
        fk = self._forward(vec)
        la = fk.ndim - 4
        with trace_scope("spectral_symbol"):
            div_k = sum(1j * self._k1[mu] * jnp.take(fk, mu, axis=la)
                        for mu in range(3))
        return self._inverse(div_k, vec.dtype)

    # -- public interface (mirrors FiniteDifferencer) ----------------------
    # (reshard targets carry their mesh, so no ambient context is needed
    # whether called eagerly or inside a caller's jit)

    def _eager(self, f):
        """Whether this call runs a program of its own (``f`` is no
        tracer of a caller's). One that does starts by forgetting the
        remembered Laplacian, before it allocates."""
        if isinstance(f, jax.core.Tracer):
            return False
        self._last_lap.forget()
        return True

    def lap(self, f):
        with host_span("spectral_lap_dispatch"):
            if not self._eager(f):
                handed = self._last_lap.offered(f)
                return self._lap(f) if handed is None else handed
            out = self._lap(f)
            if isinstance(f, jax.Array):
                self._last_lap.remember(f, out)
            return out

    def grad(self, f):
        self._eager(f)
        with host_span("spectral_grad_dispatch"):
            return self._grad(f)

    def grad_lap(self, f):
        self._eager(f)
        return self._grad_lap(f)

    def _pd_of(self, f, mu):
        self._eager(f)
        return self._pd(f, mu)

    def pdx(self, f):
        return self._pd_of(f, 0)

    def pdy(self, f):
        return self._pd_of(f, 1)

    def pdz(self, f):
        return self._pd_of(f, 2)

    def divergence(self, vec):
        self._eager(vec)
        return self._div(vec)

    def __call__(self, fx, *, lap=False, grd=False, div=False):
        out = {}
        if lap and grd:
            g, lp = self.grad_lap(fx)
            out["grd"], out["lap"] = g, lp
        elif lap:
            out["lap"] = self.lap(fx)
        elif grd:
            out["grd"] = self.grad(fx)
        if div:
            out["div"] = self.divergence(fx)
        return out
