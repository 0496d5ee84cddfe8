"""Weighted histograms over the lattice.

TPU-native counterpart of /root/reference/pystella/histogram.py:33-350. The
reference uses a two-level atomic scatter kernel (workgroup-local atomics,
barrier, global atomic flush) followed by an MPI allreduce of the host copy.
A TPU has no atomics, and a scatter there is serialised; a bin sum is
instead a contraction: with the bin index split as ``bin = hi * 128 + lo``,

    ``H[hi, lo] = sum_n (w_n * [hi_n == hi]) * [lo_n == lo]``

is a matrix product over the lattice, ``(Hi x n) . (128 x n)^T``, which
the MXU does. One Pallas kernel (:func:`_onehot_bincount`, scope
``pallas_bincount``) builds both one-hot operands tile by tile in VMEM
(they never reach HBM), accumulates ``(Hi, 128)`` in float32 and writes
one partial per run of grid steps, inside ``shard_map`` over each device's
shard: deterministic by construction (no write-race silencing needed, cf.
histogram.py:111-112). There is one path; what it chose from the shapes
goes out as a ``bincount_plan`` event per built program.

Accumulation precision (production lattices exceed f32's 2**24 integer
range: a 512**3 grid has 1.3e8 sites, so a single bin can overflow exact
f32 counting even though TPUs have no native f64): one-hot entries are
exact in bfloat16; a float32 weight is not, so it is split into three
bfloat16 pieces stacked along ``Hi`` (one pass of the MXU, every product
exact to 24 bits; a float64 weight, under ``jax_enable_x64`` on a CPU,
takes one float64 product). A partial of unit-weight counts covers at
most 2**22 elements (0/1 products summed in f32, exact, written as int32),
a weighted partial at most 2**18 (so the f32 accumulation inside one is
short); the per-device partials are returned without any device-side
reduction, and the final sum over partials and devices happens on the host
in int64/float64. Counts are therefore exact at any scale regardless of
``jax_enable_x64`` (matching the reference's f64 device accumulation,
histogram.py:199-206); weighted sums carry f32 rounding within one
2**18-element partial only.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import weakref

from pystella_tpu import field as _field
from pystella_tpu.obs import events as _events
from pystella_tpu.obs import memory as _obs_memory
from pystella_tpu.obs.scope import host_span, trace_scope
from pystella_tpu.ops.reduction import Reduction

__all__ = ["Histogrammer", "FieldHistogrammer", "weighted_bincount",
           "bincount_core", "fetch_partials"]

# cache keyed weakly on the decomp so discarded decompositions (and their
# compiled executables) remain collectable
_bincount_cache = weakref.WeakKeyDictionary()


#: the low factor of a bin index: one lane tile, the width of the MXU
_LO = 128
#: elements in a row of the flattened shard (the contraction length of
#: one product) and rows in a sublane tile
_LANES, _SUB = 512, 8
#: most rows of one grid step: 2**16 elements
_TILE_ROWS = 128
#: most elements of one partial, by ``weighted``: unit-weight counts stay
#: exact in f32 far beyond it, a weighted partial is kept short
_PARTIAL = {False: 1 << 22, True: 1 << 18}


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


def _plan(num_bins, n, dtype):
    """What the kernel takes from the shapes: ``n`` elements a slice into
    ``num_bins`` bins, weights of ``dtype`` (``None``: counts)."""
    hi = -(-num_bins // _LO)
    # a float32 weight goes as three bfloat16 pieces stacked along hi
    passes = 3 if dtype == jnp.float32 else 1
    rows = -(-n // _LANES)
    tile = min(_TILE_ROWS, -(-rows // _SUB) * _SUB)
    blocks = -(-rows // tile)
    steps = max(1, min(_PARTIAL[dtype is not None] // (tile * _LANES),
                       blocks))
    return {"hi": hi, "lo": _LO, "passes": passes,
            # the stacked operand's rows, whole bfloat16 sublane tiles
            "stack": -(-passes * hi // 16) * 16,
            "rows": rows, "tile": (tile, _LANES), "blocks": blocks,
            "steps": steps, "partials": -(-blocks // steps)}


def _onehot_bincount(b, w, num_bins, interpret):
    """Partial histograms of ``b`` (int32, ``(nouter, n)``, bins in
    ``[0, num_bins)`` of each outer slice; anything negative is counted
    nowhere) with weights ``w`` (same shape, float32 or float64; any
    other dtype is taken as float32) or unit weights (``None``):
    ``(partials, nouter * num_bins)``, int32 for counts. The
    contraction of the module docstring as one ``pallas_call`` over
    ``(slice, partial, step)``."""
    nouter, n = b.shape
    if w is not None and w.dtype not in (jnp.float32, jnp.float64):
        w = w.astype(jnp.float32)
    dtype = None if w is None else w.dtype
    plan = _plan(num_bins, n, dtype)
    hi, passes, stack = plan["hi"], plan["passes"], plan["stack"]
    rows, (R, C) = plan["rows"], plan["tile"]
    blocks, steps, partials = plan["blocks"], plan["steps"], plan["partials"]
    _events.emit("bincount_plan", num_bins=num_bins, nouter=nouter,
                 elements=n, weights=None if w is None else str(dtype),
                 **plan)
    # operands: bfloat16 where every entry is exact in it
    op_dtype = dtype if dtype == jnp.float64 else jnp.bfloat16
    acc_dtype = dtype if dtype == jnp.float64 else jnp.float32
    precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float64
                 else None)
    one, zero = np.ones((), acc_dtype), np.zeros((), acc_dtype)

    def rows_of(a, fill):
        pad = rows * C - n
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)), constant_values=fill)
        return a.reshape(nouter, rows, C)

    args = [rows_of(b, -1)] + ([] if w is None else [rows_of(w, 0)])

    def kernel(*refs):
        b_ref, out_ref = refs[0], refs[-1]
        q, s = pl.program_id(1), pl.program_id(2)

        @pl.when(s == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        # row j of the stacked operand holds piece j // hi of bin row
        # j % hi; rows past the last piece match nothing
        j = jax.lax.broadcasted_iota(jnp.int32, (stack, C), 0)
        piece = jax.lax.div(j, np.int32(hi))  # i32 under x64 too
        stack_hi = jnp.where(j < passes * hi, j - piece * hi, np.int32(-2))
        first, second = piece == 0, piece == 1
        lo_id = jax.lax.broadcasted_iota(jnp.int32, (_LO, C), 0)
        # rows of this block that lie inside the array (the last block
        # is ragged; one past it is read again and counted nowhere)
        left = rows - (q * steps + s) * R
        row_id = jax.lax.broadcasted_iota(jnp.int32, (_SUB, C), 0)

        def eight_rows(r8, acc):
            r0 = pl.multiple_of(r8 * _SUB, _SUB)
            b8 = b_ref[pl.ds(r0, _SUB), :]
            hi8 = jnp.where(row_id + r0 < left, b8 >> 7, np.int32(-1))
            lo8 = b8 & (_LO - 1)
            if w is not None:
                w8 = refs[1][pl.ds(r0, _SUB), :]
            if passes == 3:
                # w8 = w0 + w1 + w2 to its 24 bits, each exact in bf16
                w0 = w8.astype(jnp.bfloat16).astype(jnp.float32)
                w1 = (w8 - w0).astype(jnp.bfloat16).astype(jnp.float32)
                w2 = ((w8 - w0) - w1).astype(jnp.bfloat16).astype(
                    jnp.float32)
            for i in range(_SUB):
                def along(a, nrows):
                    return jnp.broadcast_to(a[i:i + 1], (nrows, C))
                sel = along(hi8, stack) == stack_hi
                if w is None:
                    a = jnp.where(sel, one, zero)
                elif passes == 3:
                    a = jnp.where(sel, jnp.where(
                        first, along(w0, stack), jnp.where(
                            second, along(w1, stack), along(w2, stack))),
                        zero)
                else:
                    a = jnp.where(sel, along(w8, stack), zero)
                onehot = jnp.where(along(lo8, _LO) == lo_id, one, zero)
                acc = acc + jax.lax.dot_general(
                    a.astype(op_dtype), onehot.astype(op_dtype),
                    (((1,), (1,)), ((), ())), precision=precision,
                    preferred_element_type=acc_dtype)
            return acc

        out_ref[...] += jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(R // _SUB), eight_rows,
            jnp.zeros((stack, _LO), acc_dtype))

    in_spec = pl.BlockSpec(
        (None, R, C),
        lambda o, q, s: (o, jnp.minimum(q * steps + s, blocks - 1), 0))
    with trace_scope("pallas_bincount"):
        out = pl.pallas_call(
            kernel,
            grid=(nouter, partials, steps),
            in_specs=[in_spec] * len(args),
            out_specs=pl.BlockSpec((None, None, stack, _LO),
                                   lambda o, q, s: (o, q, 0, 0)),
            out_shape=jax.ShapeDtypeStruct(
                (nouter, partials, stack, _LO), acc_dtype),
            interpret=interpret,
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
        )(*args)
    # the pieces' sums, smallest first; then bins in order, slices in order
    out = out[:, :, :passes * hi].reshape(nouter, partials, passes, hi * _LO)
    out = sum(out[:, :, p] for p in reversed(range(passes)))[..., :num_bins]
    if w is None:
        out = out.astype(jnp.int32)
    return jnp.moveaxis(out, 1, 0).reshape(partials, nouter * num_bins)


def bincount_core(decomp, outer_shape, num_bins, weighted,
                  lattice_names=None):
    """The UNJITTED shard_map-wrapped local binning (cached): callers
    that fuse binning into a larger jitted program (the pencil-tier
    spectra path) compose this; :func:`_bincount_fn` wraps it in its
    own jit for standalone dispatch. Takes ``bins`` (int32, ``outer +
    lattice``, each in ``[0, num_bins)``) and, if ``weighted``, weights
    of the same shape; returns per-device partial histograms of
    ``num_bins * prod(outer_shape)`` bins stacked along axis 0 (the
    host finalizes in wide precision). ``lattice_names`` are the
    per-lattice-axis mesh axis names of the input layout (default: the
    decomposition's position-space layout; k-space callers pass their
    own — entries may be combined-axis tuples). On CPU devices the
    kernel runs in Pallas interpret mode."""
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
    spec = P(*((None,) * len(outer_shape) + lattice_names))
    # partials stay sharded along the stacked axis — no device-side
    # reduction, so no precision-losing f32/int32 cross-device sums;
    # stacking covers only the axes the input is actually sharded over
    # (mesh axes the input is replicated across would double count)
    stack = _flat_names(lattice_names)
    out_spec = P(stack or None, None)
    interpret = decomp.mesh.devices.flat[0].platform == "cpu"

    def local(b, w=None):
        return _onehot_bincount(
            b.reshape(nouter, -1),
            None if w is None else w.reshape(nouter, -1),
            num_bins, interpret)

    fn = decomp.shard_map(local, (spec,) * (1 + weighted), out_spec,
                          check_vma=False)
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
    """Distributed histogram: per-device partials of the one-hot
    contraction with host-side wide-precision finalization (see module
    docstring). ``bins`` (int32, each in ``[0, num_bins)``) has shape
    ``outer + lattice``; ``weights`` shares it, or is ``None`` for an
    exact integer count histogram. ``lattice_names``
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
                acc = jax.dtypes.canonicalize_dtype(self.dtype)
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
