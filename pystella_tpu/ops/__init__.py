from pystella_tpu.ops.elementwise import ElementWiseMap
from pystella_tpu.ops.derivs import (
    FirstCenteredDifference, SecondCenteredDifference, FiniteDifferencer,
    expand_stencil, centered_diff,
)
from pystella_tpu.ops.reduction import Reduction, FieldStatistics
from pystella_tpu.ops.histogram import Histogrammer, FieldHistogrammer

__all__ = [
    "ElementWiseMap",
    "FirstCenteredDifference", "SecondCenteredDifference",
    "FiniteDifferencer", "expand_stencil", "centered_diff",
    "Reduction", "FieldStatistics",
    "Histogrammer", "FieldHistogrammer",
]
