from .bench import benchmark_entry
from .kernel import conv_direct_pallas, fits_vmem
from .ops import conv_direct
from .ref import conv_direct_ref

__all__ = ["benchmark_entry", "conv_direct", "conv_direct_pallas", "conv_direct_ref",
           "fits_vmem"]
