"""Transform-scheme planning: one factory for the distributed-FFT tiers.

Two transform classes serve the package:

- :class:`~pystella_tpu.fourier.pencil.PencilFFT` — the fully
  distributed shard_map pencil tier (explicit ``all_to_all``
  transposes, no replication at any size); needs grid x/y divisible by
  the TOTAL device count;
- :class:`~pystella_tpu.fourier.dft.DFT` — the declarative-reshard
  tiers (``pencil``/``partial``/``replicate`` selected by
  divisibility, with the replicate tier refusing above
  ``PYSTELLA_FFT_REPLICATE_LIMIT``).

:func:`make_dft` picks between them; ``scheme`` resolution order is
explicit argument > ``PYSTELLA_FFT_SCHEME`` env > ``"auto"`` (the
pencil tier whenever feasible on a multi-device mesh — it is the
TPU-native scheme — else the DFT chain). :func:`ensure_spectral_fft`
is the consumer-side hook: :class:`~pystella_tpu.PowerSpectra`,
:class:`~pystella_tpu.Projector`, and
:class:`~pystella_tpu.SpectralPoissonSolver` pass their ``fft``
through it, so ``scheme="pencil"`` (or the env) upgrades an existing
transform in place of plumbing a new object through every call site.
"""

from __future__ import annotations

import logging

import numpy as np

logger = logging.getLogger(__name__)

__all__ = ["SCHEMES", "make_dft", "resolve_scheme", "ensure_spectral_fft",
           "emit_spectra_plan"]

#: accepted scheme names: "auto" plans; "pencil" forces the shard_map
#: tier; everything else forces the DFT class (whose own divisibility
#: tiering then applies — the dft/reshard/partial/replicate spellings
#: are synonyms at this level, kept so a knob can SAY what it expects)
SCHEMES = ("auto", "pencil", "dft", "reshard", "partial", "replicate",
           "local")


def resolve_scheme(scheme=None):
    """The effective scheme name: explicit argument >
    ``PYSTELLA_FFT_SCHEME`` env > ``"auto"``. Unknown names raise."""
    if scheme is None:
        from pystella_tpu import config as _config
        scheme = _config.getenv("PYSTELLA_FFT_SCHEME") or "auto"
    scheme = str(scheme).strip().lower()
    if scheme not in SCHEMES:
        raise ValueError(
            f"unknown FFT scheme {scheme!r}; known: {SCHEMES}")
    return scheme


def make_dft(decomp, context=None, queue=None, grid_shape=None,
             dtype=np.float64, scheme=None, **kwargs):
    """Construct the right transform for ``(decomp, grid_shape)`` —
    drop-in for the ``DFT(...)`` constructor plus a ``scheme`` knob
    (see module docstring for resolution)."""
    from pystella_tpu.fourier.dft import DFT
    from pystella_tpu.fourier.pencil import PencilFFT, pencil_feasible
    if grid_shape is None:
        raise ValueError("grid_shape is required")
    scheme = resolve_scheme(scheme)
    nproc = int(np.prod(decomp.proc_shape))
    if scheme == "pencil":
        # forced: infeasible shapes raise (PencilFFT's actionable error)
        return PencilFFT(decomp, grid_shape=grid_shape, dtype=dtype,
                         **kwargs)
    if scheme == "auto" and nproc > 1:
        ok, reasons = pencil_feasible(decomp, tuple(grid_shape))
        if ok:
            return PencilFFT(decomp, grid_shape=grid_shape, dtype=dtype,
                             **kwargs)
        logger.info(
            "make_dft %s on %d devices: pencil tier infeasible (%s); "
            "falling back to the DFT tiers", tuple(grid_shape), nproc,
            "; ".join(reasons))
    return DFT(decomp, grid_shape=grid_shape, dtype=dtype, **kwargs)


def ensure_spectral_fft(fft, scheme=None):
    """The transform a k-space consumer should actually use.

    With ``scheme`` unset and env ``auto`` (the default) the passed
    object is returned untouched — a caller-constructed transform is
    never silently swapped. ``scheme="pencil"`` (or the env set to it)
    rebuilds the transform on the pencil tier; ``"dft"`` et al. force
    the declarative class."""
    from pystella_tpu.fourier.dft import DFT
    from pystella_tpu.fourier.pencil import PencilFFT
    scheme = resolve_scheme(scheme)
    if scheme == "pencil":
        if fft.is_pencil:
            return fft
        return PencilFFT(fft.decomp, grid_shape=fft.grid_shape,
                         dtype=fft.dtype)
    if scheme == "auto":
        # a caller-constructed transform is never silently swapped:
        # the shapes the pencil tier could rescue (x/y divisible by
        # the total device count) are exactly the shapes the DFT class
        # already serves with its own distributed scheme, and its
        # replicate tier refuses above the limit at construction — so
        # auto-above-the-limit selection happens in make_dft, not by
        # rewriting an object the caller handed over
        return fft
    # an explicit DFT-family scheme: rebuild only if the object is the
    # wrong class (the DFT's internal tier choice is divisibility-driven)
    if fft.is_pencil:
        return DFT(fft.decomp, grid_shape=fft.grid_shape,
                   dtype=fft.dtype)
    return fft


def mesh_plan(fft):
    """What a plan event says of a transform on its mesh: the scheme,
    the lattice, ``proc_shape`` and what :meth:`DFT.transpose_plan`
    counts (the reshards between chips a forward and an inverse
    transform make, the bytes of one field's k-space block a chip)."""
    forward, inverse, nbytes = fft.transpose_plan()
    return dict(
        scheme=fft.scheme, grid_shape=list(fft.grid_shape),
        dtype=str(fft.dtype),
        proc_shape=[int(n) for n in fft.decomp.proc_shape],
        transposes_forward=forward, transposes_inverse=inverse,
        transpose_bytes=nbytes)


def emit_spectra_plan(consumer, fft):
    """One ``spectra_plan`` event for a k-space consumer of the outputs
    (:class:`~pystella_tpu.PowerSpectra`, :class:`~pystella_tpu.Projector`)
    built on a mesh: which transform its outputs take (the class, how a
    real field would come back) and :func:`mesh_plan`. A kind of its
    own: ``spectral_plan`` is the collocator's, which stands in every
    stage of a ``--halo-shape 0`` step. Nothing on one device."""
    if fft._nproc == 1:
        return
    from pystella_tpu.obs import events as _events
    _events.emit(
        "spectra_plan", consumer=consumer, tier=type(fft).__name__,
        real_inverse="matmul" if fft._matmul_inverse else "xla",
        **mesh_plan(fft))
