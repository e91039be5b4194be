"""ResNet-50 v1.5 (He et al. 2015, arXiv:1512.03385, Table 1, 50-layer).

A 7x7/2 stem conv and a 3x3/2 max pool; four stages of 3, 4, 6 and 3
bottleneck blocks (1x1, 3x3, 1x1 at four times the width) of widths 64,
128, 256 and 512; global average pool, a 1000-way fc and softmax.  v1.5,
as in MLPerf Inference's image classification, puts a stage's stride on
its first block's 3x3 conv; that block's shortcut is a strided 1x1
projection, every other one the identity.

Each block ends in an ``add`` join of its branch and its shortcut: one
op node with two producers, so the PBQP ties the block's last conv to a
layout its shortcut (decided a whole block earlier) delivers too.  The
branch's last conv carries a folded inference BatchNorm with
gamma/sigma :data:`BRANCH_GAIN`: without it each identity join of the He
draw about doubles the activations' second moment, and the softmax of
16 blocks keeps a single class.  A block adds its convs in the order
``a``, ``b``, ``c``, then the projection, which is the order the seeded
draw follows.

``scale`` shrinks the input resolution (224 -> 224*scale) for fast
CI-scale runs; scale=1.0 is the published 3x224x224.
"""
from __future__ import annotations

from ..core.graph import Net, add, fc, global_avgpool, maxpool, relu, \
    softmax

#: (blocks, width, stride of the first block) per stage
STAGES = {"2": (3, 64, 1), "3": (4, 128, 2), "4": (6, 256, 2),
          "5": (3, 512, 2)}
EXPANSION = 4
BRANCH_GAIN = 0.25


def _bottleneck(net: Net, n: str, x: str, c_in: int, width: int,
                stride: int) -> str:
    c_out = width * EXPANSION
    y = net.conv(f"{n}_a", x, k=1, m=width, pad=0)
    y = net.op(f"{n}_a_relu", [y], relu())
    y = net.conv(f"{n}_b", y, k=3, m=width, stride=stride, pad=1)
    y = net.op(f"{n}_b_relu", [y], relu())
    y = net.conv(f"{n}_c", y, k=1, m=c_out, pad=0, gain=BRANCH_GAIN)
    short = x
    if stride != 1 or c_in != c_out:
        short = net.conv(f"{n}_proj", x, k=1, m=c_out, stride=stride, pad=0)
    y = net.op(f"{n}_add", [y, short], add())
    return net.op(f"{n}_relu", [y], relu())


def resnet50(scale: float = 1.0) -> Net:
    r = max(int(224 * scale), 32)
    net = Net(f"resnet50{'' if scale == 1.0 else f'@{r}'}")
    x = net.input("data", (3, r, r))
    x = net.conv("conv1", x, k=7, m=64, stride=2, pad=3)
    x = net.op("conv1_relu", [x], relu())
    x = net.op("pool1", [x], maxpool(3, 2, pad=1))
    c_in = 64
    for stage, (blocks, width, stride) in STAGES.items():
        for j in range(1, blocks + 1):
            x = _bottleneck(net, f"s{stage}b{j}", x, c_in, width,
                            stride if j == 1 else 1)
            c_in = width * EXPANSION
    x = net.op("gap", [x], global_avgpool())
    x = net.op("fc", [x], fc(1000))
    net.op("prob", [x], softmax())
    return net
