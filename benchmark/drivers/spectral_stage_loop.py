"""Upstream's shipped loop under ``--halo-shape 0``: ``stage_loop``'s body
(one dispatch per RK stage of the generic stepper, the energy reduced and
the background stepped on the host between stages) with the energy's
Laplacian, a transform pair of both fields, in a span of its own inside
``feedback``: ``spectral_lap``, closed on its result in a traced run."""

import numpy as np

from benchmark.drivers.base import LoopDriver


class Driver(LoopDriver):
    def compute_energy(self, state, a):
        sys = self.sys
        with self.spans.span("spectral_lap") as sp:
            lap_f = sp.close_on(sys.derivs.lap(state["f"]))
        return sys.reduce_energy(f=state["f"], dfdt=state["dfdt"],
                                 lap_f=lap_f, a=np.float64(a))

    def one_step(self):
        sys, e, stepper = self.sys, self.expand, self.sys.stepper
        carry = None
        for s in range(stepper.num_stages):
            with self.spans.span("step_call") as sp:
                carry = sp.close_on(stepper(
                    s, self.state if s == 0 else carry, self.t,
                    a=np.float64(e.a), hubble=np.float64(e.hubble)))
            with self.spans.span("feedback"):
                e.step(s, self.energy["total"], self.energy["pressure"],
                       sys.dt)
                if s == stepper.num_stages - 1:
                    self.state = carry
                    self.energy = self.compute_energy(self.state, e.a)
                else:
                    self.energy = self.compute_energy(
                        stepper.current(carry), e.a)
        self.t += sys.dt
        self.step_count += 1

    def first_steps(self):
        for _ in range(self.first_nsteps):
            self.one_step()
            self.after_advance()

    def block(self):
        for _ in range(self.block_steps):
            self.one_step()
            self.after_advance()
