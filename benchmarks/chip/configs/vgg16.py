"""VGG-16 (configuration D) as a reference layer list (see ``reference.py``).

Table 1 of Simonyan and Zisserman (arXiv:1409.1556): five stages of 3x3/1
convs with padding 1, each followed by a 2x2/2 max pool, then fc6 and fc7
of 4096 with ReLU and the 1000-way fc8.  Sizes are read from
``vgg16.json``.
"""


def layers(cfg):
    out = []

    def add(name, op, inputs, **sizes):
        out.append(dict(name=name, op=op, inputs=list(inputs), **sizes))
        return name

    k, stride, pad = cfg["conv"]
    pk, ps, pp = cfg["pool"]
    x = "data"
    for si, stage in enumerate(cfg["stages"], start=1):
        for ci, m in enumerate(stage, start=1):
            x = add(f"conv{si}_{ci}", "conv", [x], k=k, m=m, stride=stride,
                    pad=pad)
            x = add(f"relu{si}_{ci}", "relu", [x])
        x = add(f"pool{si}", "maxpool", [x], k=pk, stride=ps, pad=pp)
    for i, width in enumerate(cfg["fc"], start=6):
        x = add(f"fc{i}", "fc", [x], out=width, relu=True)
    add(f"fc{6 + len(cfg['fc'])}", "fc", [x], out=cfg["classes"], relu=False)
    return out
