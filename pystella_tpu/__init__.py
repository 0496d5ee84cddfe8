"""pystella_tpu: a TPU-native framework for PDE systems on 3-D periodic
lattices.

A ground-up JAX/XLA/Pallas re-design with the capabilities of the reference
pystella (/root/reference): symbolic field expressions, finite-difference and
spectral operators, Runge-Kutta steppers, distributed 3-D lattices over
device meshes, Fourier analysis (spectra, projections, Gaussian random
fields), FLRW expansion, scalar-field / gravitational-wave sectors, and
multigrid solvers.

Where the reference generates OpenCL via loopy and communicates via MPI
(/root/reference/pystella/__init__.py:24-40), here XLA is the kernel
generator and compiler, lattices are ``jax.Array``s sharded over a
``jax.sharding.Mesh``, and communication is XLA collectives over ICI/DCN.
"""

from pystella_tpu import config
from pystella_tpu.field import (
    Field, DynamicField, Expr, Var, Shifted,
    diff, simplify, substitute, evaluate, field_names, shift_fields,
    exp, log, sin, cos, tan, sinh, cosh, tanh, sqrt, fabs, sign,
    t, x, y, z,
)
from pystella_tpu.grid import Lattice
from pystella_tpu.parallel import (
    DomainDecomposition, ensemble_mesh, make_mesh)
from pystella_tpu.ops import (
    ElementWiseMap,
    FirstCenteredDifference, SecondCenteredDifference, FiniteDifferencer,
    expand_stencil, centered_diff,
    Reduction, FieldStatistics,
    Histogrammer, FieldHistogrammer,
)
from pystella_tpu.ops.pallas_stencil import StreamingStencil
from pystella_tpu.ops.fused import FusedScalarStepper, FusedPreheatStepper
from pystella_tpu.fourier import (
    DFT, PencilFFT, make_dft, fftfreq, pfftfreq, make_hermitian,
    Projector, PowerSpectra, RayleighGenerator,
    SpectralCollocator, SpectralPoissonSolver,
)
from pystella_tpu.models import (
    Sector, ScalarSector, TensorPerturbationSector, tensor_index,
    get_rho_and_p, Expansion,
)
from pystella_tpu import obs
from pystella_tpu import ensemble
from pystella_tpu.ensemble import (
    EnsembleDriver, EnsembleMonitor, EnsembleStepper, Scenario)
from pystella_tpu import resilience
from pystella_tpu.resilience import (
    DeviceSubsetFault, FaultInjector, RecoveryFailed, RemeshPlanner,
    RetryPolicy, Supervisor)
from pystella_tpu.utils import (Checkpointer, HealthMonitor,
    SimulationDiverged, OutputFile, ShardedSnapshot, StepTimer, timer,
    trace, advise_shapes)
from pystella_tpu.step import (
    Stepper, RungeKuttaStepper, LowStorageRKStepper, compile_rhs_dict,
    RungeKutta4, RungeKutta3Heun, RungeKutta3Nystrom, RungeKutta3Ralston,
    RungeKutta3SSP, RungeKutta2Midpoint, RungeKutta2Heun, RungeKutta2Ralston,
    LowStorageRK54, LowStorageRK144, LowStorageRK134, LowStorageRK124,
    LowStorageRK3Williamson, LowStorageRK3Inhomogeneous,
    LowStorageRK3Symmetric, LowStorageRK3PredictorCorrector, LowStorageRK3SSP,
    all_steppers,
)

__version__ = "2026.1"


def choose_device_and_make_context(platform=None):
    """Parity shim for the reference device chooser
    (/root/reference/pystella/__init__.py:46-102). With JAX there is no
    context to create; returns ``(None, jax.devices()[0])``."""
    import jax
    devices = jax.devices(platform) if platform else jax.devices()
    return None, devices[0]


class DisableLogging:
    """Context manager silencing logging (reference
    /root/reference/pystella/__init__.py:105-114)."""

    def __enter__(self):
        import logging
        self.previous_level = logging.root.manager.disable
        logging.disable(logging.CRITICAL)

    def __exit__(self, exception_type, exception_value, traceback):
        import logging
        logging.disable(self.previous_level)


__all__ = [
    "Field", "DynamicField", "Expr", "Var", "Shifted", "diff", "simplify",
    "substitute", "evaluate", "field_names", "shift_fields",
    "expand_stencil", "centered_diff",
    "exp", "log", "sin", "cos", "tan", "sinh", "cosh", "tanh", "sqrt",
    "fabs", "sign", "t", "x", "y", "z",
    "Lattice", "DomainDecomposition", "ensemble_mesh", "make_mesh",
    "ensemble", "EnsembleStepper", "EnsembleDriver", "Scenario",
    "EnsembleMonitor",
    "resilience", "Supervisor", "FaultInjector", "RetryPolicy",
    "RecoveryFailed", "RemeshPlanner", "DeviceSubsetFault",
    "ElementWiseMap",
    "FirstCenteredDifference", "SecondCenteredDifference",
    "FiniteDifferencer",
    "Reduction", "FieldStatistics", "Histogrammer", "FieldHistogrammer",
    "StreamingStencil", "FusedScalarStepper", "FusedPreheatStepper",
    "DFT", "PencilFFT", "make_dft", "fftfreq", "pfftfreq",
    "make_hermitian",
    "Projector", "PowerSpectra", "RayleighGenerator",
    "SpectralCollocator", "SpectralPoissonSolver",
    "Sector", "ScalarSector", "TensorPerturbationSector", "tensor_index",
    "get_rho_and_p", "Expansion", "OutputFile", "ShardedSnapshot",
    "timer", "Checkpointer", "obs", "config",
    "HealthMonitor", "SimulationDiverged", "StepTimer", "trace",
    "Stepper", "RungeKuttaStepper", "LowStorageRKStepper", "compile_rhs_dict",
    "RungeKutta4", "RungeKutta3Heun", "RungeKutta3Nystrom",
    "RungeKutta3Ralston", "RungeKutta3SSP", "RungeKutta2Midpoint",
    "RungeKutta2Heun", "RungeKutta2Ralston",
    "LowStorageRK54", "LowStorageRK144", "LowStorageRK134", "LowStorageRK124",
    "LowStorageRK3Williamson", "LowStorageRK3Inhomogeneous",
    "LowStorageRK3Symmetric", "LowStorageRK3PredictorCorrector",
    "LowStorageRK3SSP", "all_steppers",
    "choose_device_and_make_context", "DisableLogging",
]
