"""What the drivers share: the rows the example's loop writes after each
advance, and the unit bookkeeping. The loop bodies are copies of
``examples/scalar_preheating.py``'s (its loop is not importable), each
call wrapped in a host span of the benchmark's own."""

import numpy as np


class LoopDriver:
    def __init__(self, system, traffic, spans):
        self.sys, self.traffic, self.spans = system, traffic, spans
        self.block_steps = int(traffic["block_steps"])
        self.chunk_steps = int(traffic["chunk_steps"])
        self.stats_every = int(traffic.get("stats_every", 0))
        self.first_nsteps = int(traffic["check_steps"])
        if self.block_steps % max(self.chunk_steps, 1):
            raise ValueError("block_steps must be whole chunks")
        self.t, self.step_count = 0.0, 0
        self.state = self.expand = self.energy = None
        #: what the last statistics row and the last output wrote, for
        #: the check
        self.last_stats = self.last_output = None
        ps = system.ps
        self.monitor = (ps.HealthMonitor(every=int(traffic["health_every"]))
                        if traffic.get("health_every") else None)

    def start(self, state, expand, energy):
        self.state, self.expand, self.energy = state, expand, energy

    def background(self):
        """The background the plain reference starts from."""
        e = self.expand
        return {"mode": "coupled", "a": float(e.a), "adot": float(e.adot),
                "mpl": self.sys.mpl}

    # -- the rows the example writes every ``stats_every`` steps -----------

    def write_stats(self):
        sys, e, energy = self.sys, self.expand, self.energy
        with self.spans.span("stats"):
            f_stats = sys.observables()["statistics"](self.state["f"])
            sys.out.output(
                "energy", t=self.t, a=e.a, adot=e.adot / e.a,
                hubble=e.hubble / e.a,
                **{k: np.asarray(v) for k, v in energy.items()},
                eos=energy["pressure"] / energy["total"],
                constraint=e.constraint(energy["total"]))
            sys.out.output("statistics/f", t=self.t, a=e.a, **f_stats)
        self.last_stats = f_stats

    def after_advance(self):
        sys, e, energy = self.sys, self.expand, self.energy
        if self.stats_every and self.step_count % self.stats_every == 0:
            self.write_stats()
        if self.monitor is not None:
            with self.spans.span("health"):
                sys.ps.obs.emit("health", step=self.step_count, invariants={
                    "constraint": float(e.constraint(energy["total"])),
                    "energy_total": float(np.sum(energy["total"]))})
                self.monitor.observe(self.step_count, self.state)
                self.monitor.poll()

    # -- one spectra + histogram output, on the current state --------------

    def output(self):
        sys, e, st = self.sys, self.expand, self.state
        obs = sys.observables()
        found = {}
        with self.spans.span("output_other") as sp:
            dfdx = sys.derivs.grad(st["f"])
            rho = obs["compute_rho"](
                a=np.float64(e.a), hubble=np.float64(e.hubble),
                f=st["f"], dfdt=st["dfdt"], dfdx=dfdx)["rho"]
            rho_hist = sp.close_on(obs["hist"](rho))
        with self.spans.span("spectra"):
            spec_out = {"scalar": obs["spectra"](st["f"]),
                        "rho": obs["spectra"](rho)}
        with self.spans.span("output_other"):
            sys.out.output("rho_histogram", t=self.t, a=e.a, **rho_hist)
            sys.out.output("spectra", t=self.t, a=e.a, **spec_out)
        found["spectra_nonfinite"] = int(sum(
            np.size(v) - np.count_nonzero(np.isfinite(np.asarray(v)))
            for v in spec_out.values()))
        self.last_output = dict(spec_out, hist=rho_hist)
        return found

    def end_numbers(self):
        """Numbers of the state the window ended on, for the check."""
        if self.background()["mode"] != "coupled":
            return {}
        return {"constraint_per_step": float(
            self.expand.constraint(self.energy["total"])) / self.step_count}

    def finish(self):
        if self.monitor is not None:
            self.monitor.flush()
            self.monitor.check_now(self.state, step=self.step_count)
