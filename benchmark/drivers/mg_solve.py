"""Upstream's multigrid benchmark loop: a block is one whole solve,
``cycles_per_solve`` default V-cycles from the seeded unknowns, each one
call of the public ``FullApproximationScheme.__call__`` on the previous
call's unknowns (``test/test_multigrid.py:92-101`` upstream). A step is
one V-cycle. Every block starts from the same seeded unknowns and
sources, which stay on the device; the errors come back once a cycle, as
the call itself fetches them. No statistics, no health rows, no outputs.
"""

from benchmark.drivers.base import LoopDriver


class Driver(LoopDriver):
    def __init__(self, system, traffic, spans):
        super().__init__(system, traffic, spans)
        if self.block_steps != system.cycles_per_solve * self.chunk_steps \
                or self.first_nsteps != self.block_steps:
            raise ValueError(
                "a block and the check are one solve of the "
                f"configuration's {system.cycles_per_solve} cycles")
        self.seeded = self.sources = self.first_host = None
        #: what each cycle of the last solve returned beside the unknowns
        self.errors = []

    def start(self, unknowns, sources, _):
        self.seeded, self.sources = unknowns, sources

    def background(self):
        return {"mode": "multigrid"}

    def advance(self, n):
        sys = self.sys
        for _ in range(n):
            with self.spans.span("step_call") as sp:
                errs, solution = sys.mg(sys.decomp, dx0=sys.dx,
                                        **self.state, **self.sources)
                self.state = sp.close_on(solution)
            self.errors.append(errs)
            self.step_count += 1

    def block(self):
        self.state, self.errors = dict(self.seeded), []
        for _ in range(self.block_steps // self.chunk_steps):
            self.advance(self.chunk_steps)

    first_steps = block

    def end_numbers(self):
        """``repeat_gap``: the solve the window ended on against the
        set-up's, which the check holds to the reference. Every block is
        the same solve of the same arrays, so any difference at all is a
        fault (a buffer reused while still read, an update that depends
        on timing)."""
        import jax.numpy as jnp
        return {"repeat_gap": max(
            float(jnp.max(jnp.abs(self.state[n] - self.first_host[n])))
            for n in self.state)}
