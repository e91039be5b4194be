"""GoogLeNet's main tower as a reference layer list (see ``reference.py``).

Table 1 of Szegedy et al. (arXiv:1409.4842): a 7x7/2 stem conv, max pool,
LRN, a 1x1 and a 3x3 conv, LRN, max pool; nine inception blocks with max
pools after 3b and 4e; global average pool and a 1000-way fc.  Sizes are
read from ``googlenet.json``.
"""


def layers(cfg):
    out = []

    def add(name, op, inputs, **sizes):
        out.append(dict(name=name, op=op, inputs=list(inputs), **sizes))
        return name

    def conv(name, src, spec):
        k, m, stride, pad = spec
        x = add(name, "conv", [src], k=k, m=m, stride=stride, pad=pad)
        return add(f"{name}_relu", "relu", [x])

    stem = cfg["stem"]
    x = conv("conv1", "data", stem["conv1"])
    x = add("pool1", "maxpool", [x], k=3, stride=2, pad=1)
    x = add("norm1", "lrn", [x], **cfg["lrn"])
    x = conv("conv2r", x, stem["conv2r"])
    x = conv("conv2", x, stem["conv2"])
    x = add("norm2", "lrn", [x], **cfg["lrn"])
    x = add("pool2", "maxpool", [x], k=3, stride=2, pad=1)
    for blk, (p1, p3r, p3, p5r, p5, pp) in cfg["inception"].items():
        n = f"i{blk}"
        b1 = conv(f"{n}_1x1", x, (1, p1, 1, 0))
        b3 = conv(f"{n}_3x3", conv(f"{n}_3x3r", x, (1, p3r, 1, 0)),
                  (3, p3, 1, 1))
        b5 = conv(f"{n}_5x5", conv(f"{n}_5x5r", x, (1, p5r, 1, 0)),
                  (5, p5, 1, 2))
        bp = add(f"{n}_pool", "maxpool", [x], k=3, stride=1, pad=1)
        bp = conv(f"{n}_poolproj", bp, (1, pp, 1, 0))
        x = add(f"{n}_concat", "concat", [b1, b3, b5, bp])
        if blk in cfg["pool_after"]:
            x = add(f"pool_{blk}", "maxpool", [x], k=3, stride=2, pad=1)
    x = add("gap", "gap", [x])
    add("fc", "fc", [x], out=cfg["classes"], relu=False)
    return out
