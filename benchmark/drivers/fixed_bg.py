"""The kernel ceiling: ``multi_step`` chunks on a fixed background
(``a = 1``, ``hubble`` from the traffic file), dispatched back to back.
No energy feedback, no statistics, no outputs."""

import numpy as np

from benchmark.drivers.base import LoopDriver


class Driver(LoopDriver):
    def __init__(self, system, traffic, spans):
        super().__init__(system, traffic, spans)
        dtype = system.dtype.type
        self.args = {"a": dtype(traffic["a"]),
                     "hubble": dtype(traffic["hubble"])}

    def background(self):
        return {"mode": "fixed", "a": float(self.args["a"]),
                "hubble": float(self.args["hubble"])}

    def advance(self, n):
        sys = self.sys
        with self.spans.span("step_call") as sp:
            self.state = sp.close_on(sys.stepper.multi_step(
                self.state, n, np.float32(self.t), sys.dtype.type(sys.dt),
                self.args))
        self.t += n * sys.dt
        self.step_count += n

    def first_steps(self):
        self.advance(self.first_nsteps)

    def block(self):
        for _ in range(self.block_steps // self.chunk_steps):
            self.advance(self.chunk_steps)
