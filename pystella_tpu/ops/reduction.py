"""Lattice-wide reductions and field statistics.

TPU-native counterpart of /root/reference/pystella/reduction.py:80-343. The
reference generates a multi-statement loopy kernel producing per-(j,k)
partial sums, finishes on-device with pyopencl array reductions, and
``MPI.allreduce``s the scalars. Here each reduction is a plain ``jnp``
reduction over the global sharded array inside jit — XLA emits the
tree-reduce plus the cross-device ``all-reduce`` over ICI automatically.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from pystella_tpu import field as _field
from pystella_tpu.obs import memory as _obs_memory
from pystella_tpu.obs.scope import host_span

__all__ = ["Reduction", "FieldStatistics"]

_OPS = {
    "avg": jnp.sum,  # divided by grid_size afterwards, like the reference
    "sum": jnp.sum,
    "prod": jnp.prod,
    "max": jnp.max,
    "min": jnp.min,
}


def _normalize_input(input):
    """Accept a dict, a Sector (uses ``.reducers``), or a list of Sectors
    (reference reduction.py:125-135)."""
    if hasattr(input, "reducers"):
        return dict(input.reducers)
    if isinstance(input, (list, tuple)):
        merged = {}
        for sector in input:
            merged.update(sector.reducers)
        return merged
    return dict(input)


class Reduction:
    """Reduces symbolic expressions over the lattice.

    :arg decomp: a :class:`~pystella_tpu.DomainDecomposition` (kept for API
        parity; collectives are implicit in XLA).
    :arg input: dict mapping names to an expression, an ``(expr, op)``
        tuple, or a list of either; or a Sector / list of Sectors whose
        ``reducers`` are used. Default op is ``"avg"`` (mean over the grid).
    :arg callback: post-processes the result dict (reference
        reduction.py:139, used by ``get_rho_and_p``).
    """

    def __init__(self, decomp, input, grid_size=None, callback=None,
                 **kwargs):
        self.decomp = decomp
        self.callback = callback
        self.grid_size = grid_size

        self.reducers = {}
        for name, val in _normalize_input(input).items():
            if not isinstance(val, list):
                val = [val]
            entries = []
            for item in val:
                if isinstance(item, tuple):
                    expr, op = item
                else:
                    expr, op = item, "avg"
                if op not in _OPS:
                    raise ValueError(f"unknown reduction op {op}")
                entries.append((expr, op))
            self.reducers[name] = entries

        def run(env, grid_size):
            out = {}
            for name, entries in self.reducers.items():
                vals = []
                for expr, op in entries:
                    arr = _field.evaluate(expr, env) if isinstance(
                        expr, _field.Expr) else (
                            expr(env) if callable(expr) else expr)
                    red = _OPS[op](arr)
                    if op == "avg":
                        red = red / grid_size
                    vals.append(red)
                out[name] = jnp.stack(vals) if len(vals) > 1 else vals[0]
            return out

        # a sector's reducers are its energy components: that program is
        # the run's energy reduction, and is named so in a trace
        self._run = _obs_memory.instrument_jit(
            run, label="reduction." + (
                "reduce" if isinstance(input, dict) else "energy_reduce"))

    def __call__(self, allocator=None, **env):
        first = next((a for a in env.values() if hasattr(a, "ndim")
                      and getattr(a, "ndim", 0) >= 3), None)
        if first is None:
            raise ValueError(
                "Reduction needs at least one lattice (>= 3-D) array "
                f"argument to infer the grid size; got only scalars/"
                f"low-rank values for {sorted(env)}; pass grid_size= at "
                "construction or include a lattice array")
        grid_size = self.grid_size or int(np.prod(first.shape[-3:]))
        return self._fetch(env, grid_size)

    def _fetch(self, env, grid_size):
        """Enqueue the reduction, then wait for its numbers: the one
        host sync of a call, each half under its own span."""
        with host_span("reduce_dispatch"):
            result = self._run(env, grid_size)
        with host_span("reduce_fetch"):
            result = {k: np.asarray(v) for k, v in result.items()}
        if self.callback is not None:
            result = self.callback(result)
        return result


class FieldStatistics(Reduction):
    """Mean and variance (plus optional extrema) of a field, per outer-axis
    component (reference reduction.py:258-343).

    Call with ``stats(f=array)``; returns a dict with keys ``mean``,
    ``variance`` and, if requested, ``max``, ``min``, ``abs_max``,
    ``abs_min``, each an array over the outer axes.
    """

    def __init__(self, decomp, max_min=False, **kwargs):
        self.decomp = decomp
        self.max_min = max_min
        self.callback = None
        self.grid_size = kwargs.pop("grid_size", None)

        def run(env, grid_size):
            f = env["f"]
            lat_axes = tuple(range(f.ndim - 3, f.ndim))
            mean = jnp.sum(f, axis=lat_axes) / grid_size
            mean_sq = jnp.sum(f * f, axis=lat_axes) / grid_size
            out = {"mean": mean, "variance": mean_sq - mean * mean}
            if self.max_min:
                out["max"] = jnp.max(f, axis=lat_axes)
                out["min"] = jnp.min(f, axis=lat_axes)
                out["abs_max"] = jnp.max(jnp.abs(f), axis=lat_axes)
                out["abs_min"] = jnp.min(jnp.abs(f), axis=lat_axes)
            return out

        self._run = _obs_memory.instrument_jit(
            run, label="reduction.field_statistics")

    def __call__(self, f=None, allocator=None, **kwargs):
        if f is None:
            f = kwargs.pop("f")
        grid_size = self.grid_size or int(np.prod(f.shape[-3:]))
        with host_span("statistics"):
            return self._fetch({"f": f}, grid_size)
