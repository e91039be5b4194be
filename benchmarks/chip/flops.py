"""Model operations of a net, from its layer shapes alone.

Two operations (a multiply and an add) per multiply-accumulate of every
conv and fc layer, counted as the direct algorithm does them, whatever
primitive the plan runs: a Winograd or im2col conv does the same model
work.  Pools, LRN, ReLU, the ``concat`` and ``add`` joins and the softmax
are left out.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

from . import reference


def macs(layers: Sequence[Dict], input_chw: Tuple[int, int, int]) -> int:
    """Multiply-accumulates of one image."""
    shp = reference.shapes(layers, input_chw)
    total = 0
    for ly in layers:
        if ly["op"] == "conv":
            c = shp[ly["inputs"][0]][0]
            m, oh, ow = shp[ly["name"]]
            total += m * c * ly["k"] * ly["k"] * oh * ow
        elif ly["op"] == "fc":
            total += int(np.prod(shp[ly["inputs"][0]])) * ly["out"]
    return total


def model_flops(layers: Sequence[Dict], input_chw: Tuple[int, int, int]
                ) -> float:
    """Operations of one image: 2 x :func:`macs`."""
    return 2.0 * macs(layers, input_chw)
