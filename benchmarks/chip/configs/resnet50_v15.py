"""ResNet-50 v1.5 as a reference layer list (see ``reference.py``).

He et al., Deep Residual Learning for Image Recognition (arXiv:1512.03385),
Table 1, 50-layer column: a 7x7/2 stem conv and a 3x3/2 max pool; four
stages of 3, 4, 6 and 3 bottleneck blocks (1x1, 3x3, 1x1 at four times
the width) of widths 64, 128, 256 and 512; global average pool and a
1000-way fc.  v1.5, as in MLPerf Inference's image classification,
puts a stage's stride on its first block's 3x3 conv; that block's
shortcut is a strided 1x1 projection, every other one the identity.
Sizes are read from ``resnet50_v15.json``.

Each branch's last conv carries the configuration's ``branch_gain``: a
folded inference BatchNorm with gamma/sigma 0.25 in every channel (none
where ``branch_gain`` is null).  A block draws its convs in the order
``a``, ``b``, ``c``, then the projection.
"""


def layers(cfg, drop=()):
    """The layer list; a block named in ``drop`` (``"s4b3"``) passes its
    shortcut alone on, with its branch still drawn."""
    out = []

    def add(name, op, inputs, **sizes):
        out.append(dict(name=name, op=op, inputs=list(inputs), **sizes))
        return name

    def conv(name, src, k, m, stride, pad, relu=True, **extra):
        x = add(name, "conv", [src], k=k, m=m, stride=stride, pad=pad,
                **extra)
        return add(f"{name}_relu", "relu", [x]) if relu else x

    k, m, stride, pad = cfg["stem"]["conv1"]
    x = conv("conv1", "data", k, m, stride, pad)
    k, stride, pad = cfg["stem"]["pool1"]
    x = add("pool1", "maxpool", [x], k=k, stride=stride, pad=pad)
    c_in = cfg["stem"]["conv1"][1]
    gain = {} if cfg["branch_gain"] is None else {"gain": cfg["branch_gain"]}
    for stage, (blocks, width, stride) in cfg["stages"].items():
        c_out = width * cfg["expansion"]
        for j in range(1, blocks + 1):
            n = f"s{stage}b{j}"
            s = stride if j == 1 else 1
            y = conv(f"{n}_a", x, 1, width, 1, 0)
            y = conv(f"{n}_b", y, 3, width, s, 1)
            y = conv(f"{n}_c", y, 1, c_out, 1, 0, relu=False, **gain)
            short = x
            if s != 1 or c_in != c_out:
                short = conv(f"{n}_proj", x, 1, c_out, s, 0, relu=False)
            joined = short if n in drop else add(f"{n}_add", "add",
                                                 [y, short])
            x = add(f"{n}_relu", "relu", [joined])
            c_in = c_out
    x = add("gap", "gap", [x])
    add("fc", "fc", [x], out=cfg["classes"], relu=False)
    return out
