"""Weighted histograms over the lattice.

TPU-native counterpart of /root/reference/pystella/histogram.py:33-350. The
reference uses a two-level atomic scatter kernel (workgroup-local atomics,
barrier, global atomic flush) followed by an MPI allreduce of the host copy.
XLA has no atomics; instead each device computes local ``jnp.bincount``s
over its shard inside ``shard_map`` — deterministic by construction (no
write-race silencing needed, cf. histogram.py:111-112).

Accumulation precision (production lattices exceed f32's 2**24 integer
range — a 512**3 grid has 1.3e8 sites, so a single bin can overflow exact
f32 counting even though TPUs have no native f64): each device's flat shard
is split into chunks of at most 2**22 elements, each chunk is bincounted
separately (int32 for pure counts, f32 for weighted sums — every per-chunk
partial stays exactly representable), the per-device per-chunk partials are
returned without any device-side reduction, and the final sum over chunks
and devices happens on the host in int64/float64. Counts are therefore
exact at any scale regardless of ``jax_enable_x64`` (matching the
reference's f64 device accumulation, histogram.py:199-206); weighted sums
carry at most one f32 rounding per 2**22-element chunk.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

import weakref

from pystella_tpu import field as _field
from pystella_tpu.obs import memory as _obs_memory
from pystella_tpu.obs.scope import host_span
from pystella_tpu.ops.reduction import Reduction

__all__ = ["Histogrammer", "FieldHistogrammer", "weighted_bincount",
           "bincount_core", "fetch_partials"]

# cache keyed weakly on the decomp so discarded decompositions (and their
# compiled executables) remain collectable
_bincount_cache = weakref.WeakKeyDictionary()


#: largest per-chunk element count; keeps every per-chunk partial (int32
#: count or f32 weighted sum of same-order values) exactly representable
_CHUNK = 1 << 22


def _flat_names(lattice_names):
    """Per-axis layout entries flattened to the plain mesh-axis names
    actually sharded over: an entry may be ``None``, one name, or a
    TUPLE of names (the pencil-FFT k layout shards its y axis over the
    combined ``(x, z, y)`` mesh axes)."""
    out = []
    for n in lattice_names:
        if n is None:
            continue
        if isinstance(n, (tuple, list)):
            out.extend(m for m in n if m is not None)
        else:
            out.append(n)
    return tuple(out)


def bincount_core(decomp, outer_shape, num_bins, weighted,
                  lattice_names=None):
    """The UNJITTED shard_map-wrapped local bincount (cached): callers
    that fuse binning into a larger jitted program (the pencil-tier
    spectra path) compose this; :func:`_bincount_fn` wraps it in its
    own jit for standalone dispatch. Returns per-device, per-chunk
    partial histograms stacked along axis 0 (the host finalizes in
    wide precision). ``lattice_names`` are the per-lattice-axis mesh
    axis names of the input layout (default: the decomposition's
    position-space layout; k-space callers pass their own — entries
    may be combined-axis tuples)."""
    from jax.sharding import PartitionSpec as P
    if lattice_names is None:
        lattice_names = tuple(decomp.spec(0))
    lattice_names = tuple(lattice_names)
    per_decomp = _bincount_cache.setdefault(decomp, {})
    key = ("core", outer_shape, num_bins, weighted, lattice_names)
    cached = per_decomp.get(key)
    if cached is not None:
        return cached
    nouter = int(np.prod(outer_shape, dtype=np.int64)) if outer_shape else 1
    length = num_bins * nouter
    spec = P(*((None,) * len(outer_shape) + lattice_names))
    # partials stay sharded along the stacked chunk axis — no device-side
    # reduction, so no precision-losing f32/int32 cross-device sums;
    # stacking covers only the axes the input is actually sharded over
    # (mesh axes the input is replicated across would double count)
    stack = _flat_names(lattice_names)
    out_spec = P(stack or None, None)

    def flat_chunked_bins(b):
        if nouter > 1:
            # offset bins per outer slice: one bincount covers all slices
            offsets = jnp.arange(nouter, dtype=jnp.int32).reshape(
                outer_shape + (1, 1, 1))
            b = b + offsets * num_bins
        flat = b.reshape(-1)
        n = flat.size
        nchunks = -(-n // _CHUNK)
        chunk = -(-n // nchunks)
        pad = nchunks * chunk - n
        if pad:
            # padded elements go to a sentinel bin that is dropped below
            flat = jnp.concatenate(
                [flat, jnp.full((pad,), length, flat.dtype)])
        return flat.reshape(nchunks, chunk), nchunks, chunk, pad

    if weighted:
        def local(b, w):
            bb, nchunks, chunk, pad = flat_chunked_bins(b)
            flat_w = w.reshape(-1)
            if pad:
                flat_w = jnp.concatenate(
                    [flat_w, jnp.zeros((pad,), flat_w.dtype)])
            ww = flat_w.reshape(nchunks, chunk)
            return jax.vmap(
                lambda bi, wi: jnp.bincount(
                    bi, weights=wi, length=length + 1)[:length])(bb, ww)
        in_specs = (spec, spec)
    else:
        def local(b):
            bb, *_ = flat_chunked_bins(b)
            return jax.vmap(
                lambda bi: jnp.bincount(bi, length=length + 1)[:length])(bb)
        in_specs = (spec,)

    fn = decomp.shard_map(local, in_specs, out_spec)
    per_decomp[key] = fn
    return fn


#: who bins: the program's name in a trace, by the owner
#: :func:`weighted_bincount` is told (its spans are ``<owner>_dispatch``
#: and ``<owner>_fetch``)
_BINCOUNT_PROGRAMS = {"histogram": "histogram_bincount",
                      "spectra": "spectra_bin"}


def _bincount_fn(decomp, outer_shape, num_bins, weighted,
                 lattice_names=None, owner="histogram"):
    """Jitted wrapper of :func:`bincount_core` (cached), named after
    its ``owner`` so a trace tells the histogram's binning from the
    spectra's."""
    per_decomp = _bincount_cache.setdefault(decomp, {})
    key = ("jit", outer_shape, num_bins, weighted,
           None if lattice_names is None else tuple(lattice_names), owner)
    cached = per_decomp.get(key)
    if cached is None:
        cached = _obs_memory.instrument_jit(
            bincount_core(decomp, outer_shape, num_bins, weighted,
                          lattice_names),
            label=f"histogram.{_BINCOUNT_PROGRAMS[owner]}")
        per_decomp[key] = cached
    return cached


def fetch_partials(partials):
    """Per-device bincount partials as a host array: a plain device_get
    on one controller; under multi-controller ``jax.distributed`` the
    device axis spans non-addressable shards, so every process
    allgathers the global value instead (the multihost analog of the
    reference's host-side MPI allreduce, histogram.py:199-206)."""
    import jax
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        return multihost_utils.process_allgather(partials, tiled=True)
    return np.asarray(partials)


def weighted_bincount(decomp, bins, weights, num_bins, lattice_names=None,
                      owner="histogram"):
    """Distributed histogram: chunked per-device ``jnp.bincount``s with
    host-side wide-precision finalization (see module docstring). ``bins``
    (int32) has shape ``outer + lattice``; ``weights`` shares it, or is
    ``None`` for an exact integer count histogram. ``lattice_names``
    optionally overrides the assumed input layout (see
    :func:`_bincount_fn`); ``owner`` (``"histogram"`` or ``"spectra"``)
    names the program and the two host spans, the enqueue and the wait
    for the partials. Returns a **host** ``np.ndarray`` of shape
    ``outer + (num_bins,)`` (float64, or int64 for counts). The shared
    primitive behind :class:`Histogrammer` and
    :class:`~pystella_tpu.PowerSpectra`."""
    outer_shape = tuple(bins.shape[:-3])
    num_bins = int(num_bins)
    args = (bins,) if weights is None else (bins, weights)
    with host_span(owner + "_dispatch"):
        partials = _bincount_fn(decomp, outer_shape, num_bins,
                                weights is not None, lattice_names,
                                owner)(*args)
    with host_span(owner + "_fetch"):
        partials = fetch_partials(partials)
    h = partials.astype(np.int64 if weights is None
                        else np.float64).sum(axis=0)
    return h.reshape(outer_shape + (num_bins,))


class Histogrammer:
    """Computes weighted histograms of expressions.

    :arg decomp: a :class:`~pystella_tpu.DomainDecomposition`.
    :arg histograms: dict mapping names to ``(bin_expr, weight_expr)``; the
        bin index is ``floor(bin_expr)`` clipped to ``[0, num_bins)``
        (reference histogram.py:62-70).
    :arg num_bins: number of bins.
    :arg dtype: dtype of the output histogram.
    """

    def __init__(self, decomp, histograms, num_bins, dtype=np.float64,
                 **kwargs):
        self.decomp = decomp
        self.histograms = dict(histograms)
        self.num_bins = int(num_bins)
        self.dtype = dtype

        num_bins_ = self.num_bins

        def is_unit(expr):
            if isinstance(expr, _field.Constant):
                expr = expr.value
            return isinstance(expr, (int, float)) and expr == 1

        #: histograms with a constant unit weight take the exact integer
        #: count path (no f32 rounding at any lattice size)
        self._count_names = {name for name, (_, w)
                             in self.histograms.items() if is_unit(w)}

        def prepare(env):
            out = {}
            for name, (bin_expr, weight_expr) in self.histograms.items():
                b = _field.evaluate(bin_expr, env)
                b = jnp.clip(jnp.floor(b), 0, num_bins_ - 1).astype(jnp.int32)
                if name in self._count_names:
                    out[name] = (b, None)
                    continue
                w = _field.evaluate(weight_expr, env)
                acc = jnp.zeros((), self.dtype).dtype  # canonicalized
                out[name] = (b, jnp.broadcast_to(w, b.shape).astype(acc))
            return out

        self._prepare = _obs_memory.instrument_jit(
            prepare, label="histogram.histogram_prepare")

    def __call__(self, allocator=None, **env):
        with host_span("histogram"):
            return self._histograms(env)

    def _histograms(self, env):
        with host_span("histogram_dispatch"):
            prepared = self._prepare(env)
        return {name: weighted_bincount(
                    self.decomp, b, w, self.num_bins).astype(self.dtype)
                for name, (b, w) in prepared.items()}


class FieldHistogrammer(Histogrammer):
    """Linear- and log-binned histograms of a field, with automatic bin
    bounds (reference histogram.py:210-350).

    Returns ``{"linear", "linear_bins", "log", "log_bins"}``, each with shape
    ``f.shape[:-3] + (num_bins[+1],)``.
    """

    def __init__(self, decomp, num_bins, dtype=np.float64, **kwargs):
        f = _field.Field("f")
        max_f, min_f = _field.Var("max_f"), _field.Var("min_f")
        max_log_f = _field.Var("max_log_f")
        min_log_f = _field.Var("min_log_f")

        linear_bin = (f - min_f) / (max_f - min_f)
        log_bin = ((_field.log(_field.fabs(f)) - min_log_f)
                   / (max_log_f - min_log_f))
        histograms = {
            "linear": (linear_bin * num_bins, 1),
            "log": (log_bin * num_bins, 1),
        }
        super().__init__(decomp, histograms, num_bins, dtype, **kwargs)
        self._jit_bounds = {}  # outer ndim -> jitted bounds reductions

        self.get_min_max = Reduction(decomp, {
            "max_f": [(f, "max")],
            "min_f": [(f, "min")],
            "max_log_f": [(_field.log(_field.fabs(f)), "max")],
            "min_log_f": [(_field.log(_field.fabs(f)), "min")],
        })

    def _auto_bounds(self, f):
        """Per-outer-slice min/max of ``f`` and ``log|f|`` as ONE jitted
        dispatch + one host transfer (XLA fuses the log/abs into the
        reductions — no materialized full-field temporary)."""
        fn = self._jit_bounds.get(f.ndim)
        if fn is None:
            def impl(fa):
                lat = (-3, -2, -1)
                log_absf = jnp.log(jnp.abs(fa))
                return (jnp.max(fa, axis=lat), jnp.min(fa, axis=lat),
                        jnp.max(log_absf, axis=lat),
                        jnp.min(log_absf, axis=lat))
            fn = _obs_memory.instrument_jit(
                impl, label="histogram.histogram_bounds")
            self._jit_bounds[f.ndim] = fn
        with host_span("histogram_dispatch"):
            res = fn(f)
        with host_span("histogram_fetch"):
            mx, mn, mxl, mnl = jax.device_get(res)
        return {"max_f": mx, "min_f": mn,
                "max_log_f": mxl, "min_log_f": mnl}

    @staticmethod
    def _widen(lo, hi):
        """``hi`` strictly above ``lo`` by at least a representable step
        at ``lo``'s scale (a +1.0 widening rounds away for |lo| above
        the dtype's integer range)."""
        bump = np.maximum(np.asarray(1.0, lo.dtype),
                          4 * np.spacing(np.abs(lo)))
        return np.where(lo == hi, lo + bump, hi)

    def _sanitize_bounds(self, bounds, dtype=None):
        """Keep bin bounds finite and non-degenerate (elementwise over
        any outer shape), IN THE DTYPE THE BIN EXPRESSIONS RUN IN — a
        field with zeros gives ``log|f| = -inf`` and an
        identically-zero field degenerate bounds, which would turn the
        bin expressions into nan; sanitizing before the cast could be
        undone by rounding (bounds closer than one target-dtype ulp)."""
        dt = np.dtype(dtype if dtype is not None else self.dtype)
        out = {k: np.asarray(v, dt) for k, v in bounds.items()}
        tiny_log = dt.type(np.log(np.finfo(dt).tiny))
        lo, hi = out["min_log_f"], out["max_log_f"]
        hi = np.where(np.isfinite(hi), hi, tiny_log)
        lo = np.where(np.isfinite(lo), lo, np.minimum(tiny_log, hi))
        out["min_log_f"], out["max_log_f"] = lo, self._widen(lo, hi)
        out["max_f"] = self._widen(out["min_f"], out["max_f"])
        return out

    def __call__(self, f, allocator=None, **kwargs):
        """Histogram every outer slice of ``f`` in ONE pass: per-slice
        bounds broadcast into the bin expressions and the offset
        bincount batches all slices through a single device dispatch
        (the reference loops components host-side, histogram.py:313-350;
        so did rounds 1-3 here)."""
        with host_span("histogram"):
            return self._field_histograms(f, kwargs)

    def _field_histograms(self, f, kwargs):
        min_max_keys = set(self.get_min_max.reducers.keys())
        bounds_passed = min_max_keys.issubset(set(kwargs.keys()))

        if not bounds_passed:
            bounds = self._auto_bounds(f)
        else:
            bounds = {key: np.asarray(kwargs[key]) for key in min_max_keys}
        # sanitize in the dtype the bin expressions evaluate in, so the
        # degeneracy-widening survives
        bounds = self._sanitize_bounds(bounds, np.dtype(f.dtype))
        # broadcast per-slice bounds against the lattice axes
        env_bounds = {k: jnp.asarray(np.reshape(v, v.shape + (1, 1, 1)))
                      for k, v in bounds.items()}

        out = self._histograms(dict(env_bounds, f=f))
        out["linear_bins"] = np.linspace(
            bounds["min_f"], bounds["max_f"], self.num_bins + 1,
            axis=-1).astype(self.dtype)
        out["log_bins"] = np.exp(np.linspace(
            bounds["min_log_f"].astype(np.float64),
            bounds["max_log_f"].astype(np.float64), self.num_bins + 1,
            axis=-1)).astype(self.dtype)
        return out
