"""The run as a TPU user runs it (``--fused --chunk-steps N``, coupled
mode): ``coupled_multi_step`` chunks with the Friedmann background
integrated on the device from in-kernel energy sums, each followed by the
energy reduction, the statistics rows, the health event and the
sentinel."""

from benchmark.drivers.base import LoopDriver


class Driver(LoopDriver):
    def advance(self, n):
        sys = self.sys
        with self.spans.span("step_call") as sp:
            self.state = sp.close_on(sys.stepper.coupled_multi_step(
                self.state, n, self.expand, self.t, sys.dt,
                grid_size=sys.grid_size))
        with self.spans.span("feedback"):
            self.energy = sys.compute_energy(self.state, self.expand.a)
        self.t += n * sys.dt
        self.step_count += n

    def first_steps(self):
        self.advance(self.first_nsteps)
        self.after_advance()

    def block(self):
        for _ in range(self.block_steps // self.chunk_steps):
            self.advance(self.chunk_steps)
            self.after_advance()
