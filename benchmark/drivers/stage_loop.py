"""Upstream's shipped loop (``--fused`` without ``--chunk-steps``): one
dispatch per RK stage, the energy reduced and the background stepped on
the host between stages."""

import numpy as np

from benchmark.drivers.base import LoopDriver


class Driver(LoopDriver):
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
                    self.energy = sys.compute_energy(self.state, e.a)
                else:
                    self.energy = sys.compute_energy(
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
