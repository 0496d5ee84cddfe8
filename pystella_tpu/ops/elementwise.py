"""Elementwise map: compile symbolic assignment dicts to jitted functions.

TPU-native stand-in for the reference's loopy-based ``ElementWiseMap``
(/root/reference/pystella/elementwise.py:81-361). There, every elementwise
operation becomes a generated OpenCL kernel with tuned workgroup sizes; here
the "kernel generator" is XLA itself: expressions are traced via
:func:`pystella_tpu.field.evaluate` into one jit-compiled (and fused)
computation over the sharded lattice. There is no parallelization metadata
to manage — layout and fusion are the compiler's job.
"""

from __future__ import annotations

from pystella_tpu import field as _field
from pystella_tpu.obs import memory as _obs_memory

__all__ = ["ElementWiseMap"]


def _assignee_name(key):
    if isinstance(key, _field.Field):
        return key.name
    if isinstance(key, str):
        return key
    raise TypeError(f"assignees must be Field or str, got {type(key)}")


class ElementWiseMap:
    """Maps a dict of ``{assignee: expression}`` over the lattice.

    :arg map_instructions: dict whose keys are :class:`~pystella_tpu.Field`s
        (or strings) naming outputs and whose values are symbolic
        expressions (or callables ``env -> array``).
    :arg tmp_instructions: like ``map_instructions`` but for intermediate
        quantities usable by later expressions (the reference's temporaries,
        elementwise.py:173-193).

    Calling the map with keyword arrays/scalars evaluates all instructions
    and returns a dict of the outputs. The whole evaluation happens inside a
    single ``jax.jit``.
    """

    def __init__(self, map_instructions, tmp_instructions=None, **kwargs):
        self.map_instructions = [(_assignee_name(k), v)
                                 for k, v in dict(map_instructions).items()]
        self.tmp_instructions = [(_assignee_name(k), v)
                                 for k, v in dict(tmp_instructions or {}).items()]

        def run(env):
            env = dict(env)
            for name, expr in self.tmp_instructions:
                env[name] = self._eval(expr, env)
            return {name: self._eval(expr, env)
                    for name, expr in self.map_instructions}

        # the program is named after what it writes (``map_rho``), so a
        # trace tells one map from another
        outs = [name for name, _ in self.map_instructions]
        self._run = _obs_memory.instrument_jit(
            run, label="elementwise.map_" + "_".join(outs[:3])
            + ("_etc" if len(outs) > 3 else ""))

    @staticmethod
    def _eval(expr, env):
        if callable(expr) and not isinstance(expr, _field.Expr):
            return expr(env)
        return _field.evaluate(expr, env)

    def __call__(self, **kwargs):
        return self._run(kwargs)
