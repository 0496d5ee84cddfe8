"""The system under test, built through ``pystella_tpu``'s public API from
a configuration file: the set-up of ``examples/scalar_preheating.py``
(which cannot be imported as a function: its loop lives inside ``main``),
with two departures, both set-up only: the seeded WKB state stays on the
device (the example round-trips 2 GiB through ``np.asarray``), and
nothing is built that the cell's driver does not call.
"""

import numpy as np


class System:
    """Everything a driver drives. ``config`` is the parsed file under
    ``benchmark/configs/``; ``devices`` the chips the cell asks for."""

    def __init__(self, config, devices, outfile=None, stepper=True):
        import jax
        import jax.numpy as jnp
        import pystella_tpu as ps

        self.ps, self.config = ps, config
        p = config
        self.grid_shape = tuple(p["grid_shape"])
        self.proc_shape = tuple(p["proc_shape"])
        self.dtype = np.dtype(p["dtype"])
        self.h = int(p["halo_shape"])
        self.grid_size = float(np.prod(self.grid_shape))
        self.nscalars = int(p["nscalars"])
        self.mpl = float(p["mpl"])
        #: bins of the energy-density histogram (the example's number)
        self.hist_bins = 1000
        if p["stepper"] != "LowStorageRK54":
            raise ValueError(f"stepper {p['stepper']!r}: the plain "
                             "reference knows LowStorageRK54 only")
        self.Stepper = ps.LowStorageRK54

        ndev = int(np.prod(self.proc_shape))
        self.devices = list(devices)[:ndev]
        self.lattice = ps.Lattice(self.grid_shape, tuple(p["box_dim"]),
                                  dtype=self.dtype)
        self.dx = tuple(float(d) for d in self.lattice.dx)
        self.dt = float(p["kappa"]) * min(self.dx)
        self.decomp = ps.DomainDecomposition(self.proc_shape,
                                             devices=self.devices)
        self.local_shape = tuple(
            n // q for n, q in zip(self.grid_shape, self.proc_shape))
        self.fft = ps.DFT(self.decomp, grid_shape=self.grid_shape,
                          dtype=self.dtype)
        self.derivs = ps.FiniteDifferencer(self.decomp, self.h,
                                           self.lattice.dx)

        mphi, mchi = float(p["mphi"]), float(p["mchi"])
        gsq, sigma, lambda4 = (float(p[k])
                               for k in ("gsq", "sigma", "lambda4"))

        def potential(f):
            phi, chi = f[0], f[1]
            unscaled = (mphi**2 / 2 * phi**2 + mchi**2 / 2 * chi**2
                        + gsq / 2 * phi**2 * chi**2
                        + sigma / 2 * phi * chi**2
                        + lambda4 / 4 * chi**4)
            return unscaled / mphi**2

        self.potential = potential
        self.sector = ps.ScalarSector(self.nscalars, potential=potential)
        self.stepper = ps.FusedScalarStepper(
            self.sector, self.decomp, self.grid_shape, self.lattice.dx,
            self.h, tableau=self.Stepper, dtype=self.dtype, dt=self.dt,
            donate=True) if stepper else None
        self.reduce_energy = ps.Reduction(
            self.decomp, self.sector, callback=ps.get_rho_and_p,
            grid_size=self.grid_size)
        self.out = (ps.OutputFile(name=outfile, runfile=__file__)
                    if outfile else None)
        self._jnp, self._jax = jnp, jax
        self._observables = None

    # -- what the example's closures compute -------------------------------

    def compute_energy(self, state, a):
        return self.reduce_energy(
            f=state["f"], dfdt=state["dfdt"],
            lap_f=self.derivs.lap(state["f"]), a=np.float64(a))

    def observables(self):
        """Statistics, spectra, histogram and the ``rho`` map: built on
        first use, so a cell without statistics or outputs never pays
        for them."""
        if self._observables is None:
            ps, p = self.ps, self.config
            hubble = ps.Var("hubble")
            a_sq_rho = 3 * self.mpl**2 * hubble**2 / 8 / np.pi
            self._observables = {
                "statistics": ps.FieldStatistics(
                    self.decomp, grid_size=self.grid_size),
                "spectra": ps.PowerSpectra(
                    self.decomp, self.fft, self.lattice.dk,
                    self.lattice.volume, scheme=p.get("fft_scheme")),
                "hist": ps.FieldHistogrammer(
                    self.decomp, self.hist_bins, self.dtype),
                "compute_rho": ps.ElementWiseMap(
                    {ps.Field("rho"):
                     self.sector.stress_tensor(0, 0) / a_sq_rho}),
            }
        return self._observables

    def new_expansion(self, energy_total):
        return self.ps.Expansion(energy_total, self.Stepper, mpl=self.mpl)

    # -- the seeded initial state ------------------------------------------

    def initial_state(self, seed):
        """Homogeneous background plus WKB vacuum fluctuations drawn from
        ``seed``, as the example initialises them; returns
        ``(state, expansion, energy)``. The same seed gives the same
        state, bit for bit: the check regenerates it after the window."""
        jnp, jax, ps, p = self._jnp, self._jax, self.ps, self.config
        f0 = [float(v) * self.mpl for v in p["f0"]]
        df0 = [float(v) * self.mpl for v in p["df0"]]
        sharding = self.decomp.sharding(1)
        shape = (self.nscalars,) + self.grid_shape

        def homogeneous(vals):
            col = jnp.asarray(vals, self.dtype).reshape((-1, 1, 1, 1))
            return jnp.broadcast_to(col, shape)

        background = jax.jit(
            lambda: {"f": homogeneous(f0), "dfdt": homogeneous(df0)},
            out_shardings={"f": sharding, "dfdt": sharding})
        state = background()
        energy = self.compute_energy(state, 1.0)
        expand = self.new_expansion(energy["total"])
        addot = expand.addot_friedmann_2(expand.a, energy["total"],
                                         energy["pressure"])
        hubble_correction = -addot / expand.a
        fsym = ps.Field("f0_bg", shape=(self.nscalars,))
        eff_mass = [
            float(ps.evaluate(
                ps.diff(self.potential(fsym), fsym[i], fsym[i]),
                {"f0_bg": np.array(f0)})) + hubble_correction
            for i in range(self.nscalars)]
        # any whole number the driver passes becomes a 32-bit key seed
        modes = ps.RayleighGenerator(
            fft=self.fft, dk=self.lattice.dk, volume=self.lattice.volume,
            seed=int(seed) % (2**31 - 1))
        # one field at a time, each added to the state and waited for
        # before the next is drawn: the allocator's peak then does not
        # depend on how far the host runs ahead of the device
        add = jax.jit(
            lambda st, fx, dfx, fld: {
                "f": st["f"].at[fld].add(fx),
                "dfdt": st["dfdt"].at[fld].add(dfx)},
            out_shardings={"f": sharding, "dfdt": sharding},
            donate_argnums=0, static_argnums=3)
        for fld in range(self.nscalars):
            fx, dfx = modes.init_WKB_fields(
                norm=float(p["mphi"])**2,
                omega_k=lambda k, fld=fld: jnp.sqrt(k**2 + eff_mass[fld]),
                hubble=expand.hubble)
            state = jax.block_until_ready(add(state, fx, dfx, fld))
            del fx, dfx
        energy = self.compute_energy(state, expand.a)
        expand = self.new_expansion(energy["total"])
        return state, expand, energy

    def physics(self):
        """The numbers the plain reference needs, and nothing else."""
        p = self.config
        return {k: float(p[k]) for k in
                ("mphi", "mchi", "gsq", "sigma", "lambda4")}

    def close(self):
        if self.out is not None:
            self.out.close()
