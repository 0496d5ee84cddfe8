"""FLRW scale-factor evolution in conformal time.

TPU-native counterpart of /root/reference/pystella/expansion.py:28-176. The
reference integrates the two-variable scale-factor ODE on the host CPU with
a loopy C-target kernel; here the same Stepper classes run the scalar system
directly on host floats (no device round-trips), and the Friedmann
right-hand sides are plain functions usable inside a fused jitted
simulation step as well.
"""

from __future__ import annotations

import numpy as np

from pystella_tpu.obs.scope import host_span

__all__ = ["Expansion"]


class Expansion:
    """Scale-factor stepping for conformal FLRW spacetime.

    :arg energy: initial energy density (initializes ``adot`` via
        Friedmann 1).
    :arg Stepper: a :class:`~pystella_tpu.Stepper` subclass.
    :arg mpl: unreduced Planck mass; sets units (reference expansion.py:55-61).
    """

    def __init__(self, energy, Stepper, mpl=1.0, dtype=np.float64):
        self.mpl = mpl
        self.dtype = np.dtype(dtype)
        self.a = self.dtype.type(1.0)
        self.adot = self.adot_friedmann_1(self.a, energy)
        self.hubble = self.adot / self.a

        def rhs(state, t, energy=0.0, pressure=0.0):
            return {"a": state["adot"],
                    "adot": self.addot_friedmann_2(state["a"], energy,
                                                   pressure)}

        self.stepper = Stepper(rhs)
        self._carry = None

    def adot_friedmann_1(self, a, energy):
        """``da/dtau`` from Friedmann's first equation,
        ``H² = 8 pi a² rho / (3 mpl²)`` (reference expansion.py:101-117)."""
        return np.sqrt(8 * np.pi * a**2 / 3 / self.mpl**2 * energy) * a

    def addot_friedmann_2(self, a, energy, pressure):
        """``d²a/dtau²`` from Friedmann's second equation
        (reference expansion.py:119-138)."""
        return (4 * np.pi * a**2 / 3 / self.mpl**2
                * (energy - 3 * pressure) * a)

    def step(self, stage, energy, pressure, dt):
        """Execute one stage of the stepper (reference expansion.py:140-157);
        updates ``a``, ``adot``, ``hubble``."""
        with host_span("expansion_step"):
            state_or_carry = ({"a": self.a, "adot": self.adot}
                              if stage == 0 else self._carry)
            result = self.stepper(stage, state_or_carry, 0.0, dt,
                                  energy=energy, pressure=pressure)
            if stage == self.stepper.num_stages - 1:
                self.a = self.dtype.type(result["a"])
                self.adot = self.dtype.type(result["adot"])
                self._carry = None
            else:
                self._carry = result
                current = self.stepper.current(result)
                self.a = self.dtype.type(current["a"])
                self.adot = self.dtype.type(current["adot"])
            self.hubble = self.adot / self.a

    def stage_sequence(self, nsteps, energy, pressure, dt):
        """Advance ``nsteps`` full steps with FROZEN ``(energy, pressure)``,
        recording the per-stage ``(a, hubble)`` a driver loop would have
        passed to each field stage (the value *entering* the stage).

        This is the host-side precompute for chunked hot loops
        (:meth:`FusedScalarStepper.multi_step` ``rhs_seq``): the exact
        driver re-evaluates the field energy every stage and feeds it
        back, while a chunk holds the stage-entry energy for ``nsteps``
        steps — a background-coupling lag of one chunk, acceptable when
        ``nsteps * dt`` is small against the expansion timescale (the
        drift is measured in ``tests/test_examples.py``). ``self`` IS
        advanced to the chunk end. Returns two ``(nsteps * num_stages,)``
        float arrays ``(a_seq, hubble_seq)``."""
        ns = self.stepper.num_stages
        a_seq = np.empty(nsteps * ns, self.dtype)
        hubble_seq = np.empty(nsteps * ns, self.dtype)
        i = 0
        for _ in range(nsteps):
            for s in range(ns):
                a_seq[i], hubble_seq[i] = self.a, self.hubble
                self.step(s, energy, pressure, dt)
                i += 1
        return a_seq, hubble_seq

    def constraint(self, energy):
        """Dimensionless violation of Friedmann 1 as an evolution constraint
        (reference expansion.py:159-176)."""
        return np.abs(self.adot_friedmann_1(self.a, energy) / self.adot - 1)

    def constraint_residual(self, a, adot, energy):
        """The same Friedmann-1 residual as :meth:`constraint`, but
        computed from explicit ``(a, adot, energy)`` using only
        power/abs arithmetic — traceable, so it runs *inside* a jitted
        step as a numerics-sentinel invariant
        (:mod:`pystella_tpu.obs.sentinel`), e.g. against the on-device
        background of an energy-coupled chunk
        (``FusedScalarStepper.coupled_multi_step`` passes ``a``/``adot``
        in the sentinel's ``aux``)."""
        adot_f1 = (8 * np.pi * a**2 / 3 / self.mpl**2 * energy) ** 0.5 * a
        return abs(adot_f1 / adot - 1)
