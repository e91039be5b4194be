from .bench import benchmark_entry
from .kernel import chw_to_hwc_pallas, hwc_to_chw_pallas
from .ops import chw_to_hwc, convert, hwc_to_chw
from .ref import chw_to_hwc_ref, hwc_to_chw_ref

__all__ = ["benchmark_entry", "chw_to_hwc", "hwc_to_chw", "convert",
           "chw_to_hwc_pallas", "hwc_to_chw_pallas", "chw_to_hwc_ref",
           "hwc_to_chw_ref"]
