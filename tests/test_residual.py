"""Residual nets on the normal path: the ``add`` join, a conv's ``gain``,
and ResNet-50 v1.5, served through ``PlanServer`` against the benchmark's
plain reference (``benchmarks/chip/reference.py``) on seeded weights.

Served logits are compared as the chip benchmark's check compares them
(``reference.logit_err``, the largest over the answers).  Both sides are
float32 on XLA:CPU and a plan only reorders sums (Winograd and im2col
transforms, layout changes), so they agree to float32 rounding, about
1e-6; :data:`TOL` leaves a hundred times that, while a dropped join or a
draw that ignores ``gain`` reads 1e-1 or more.
"""
import hashlib
import json

import numpy as np
import pytest

from benchmarks.chip import harness, reference
from repro.convnets import NETWORKS
from repro.core.costs import AnalyticCostModel
from repro.core.graph import (Net, add, fc, global_avgpool, maxpool, relu,
                              softmax)
from repro.core.layouts import LAYOUT_BY_NAME
from repro.core.plan import compile_plan
from repro.core.primitives import convert_layout, registry
from repro.core.selection import select_fixed
from repro.serving import BucketPolicy, PlanServer

TOL = 1e-4
SEED = 2**31 + 11
CM = AnalyticCostModel()
POLICY = BucketPolicy(spatial="exact", channel="exact", batch="pow2",
                      max_n=8)
R50 = json.loads((harness.HERE / "configs" / "resnet50_v15.json").read_text())
r50_list = harness.load_module(harness.HERE / "configs" / "resnet50_v15.py",
                               "test_residual_program_resnet50").layers


def _two_block(gain=0.25, drop=()):
    """A stem, a block with an identity shortcut and a stride-2 block with
    a strided 1x1 projection, as a reference layer list; a block named in
    ``drop`` passes its shortcut alone on."""
    def L(name, op, inputs, **sizes):
        return dict(name=name, op=op, inputs=inputs, **sizes)

    g = {} if gain is None else {"gain": gain}
    layers = [
        L("stem", "conv", ["data"], k=3, m=8, stride=1, pad=1),
        L("stem_relu", "relu", ["stem"]),
        L("b1_a", "conv", ["stem_relu"], k=3, m=8, stride=1, pad=1),
        L("b1_a_relu", "relu", ["b1_a"]),
        L("b1_b", "conv", ["b1_a_relu"], k=3, m=8, stride=1, pad=1, **g),
        L("b1_add", "add", ["b1_b", "stem_relu"]),
        L("b1_relu", "relu", ["b1_add"]),
        L("b2_a", "conv", ["b1_relu"], k=3, m=16, stride=2, pad=1),
        L("b2_a_relu", "relu", ["b2_a"]),
        L("b2_b", "conv", ["b2_a_relu"], k=3, m=16, stride=1, pad=1, **g),
        L("b2_proj", "conv", ["b1_relu"], k=1, m=16, stride=2, pad=0),
        L("b2_add", "add", ["b2_b", "b2_proj"]),
        L("b2_relu", "relu", ["b2_add"]),
        L("gap", "gap", ["b2_relu"]),
        L("fc", "fc", ["gap"], out=10, relu=False),
    ]
    for blk in drop:
        join = next(ly for ly in layers if ly["name"] == f"{blk}_add")
        layers.remove(join)
        layers[layers.index(next(ly for ly in layers
                                 if ly["inputs"] == [join["name"]]))
               ]["inputs"] = [join["inputs"][1]]
    return layers


def _net(layers, chw, name="listed"):
    """The program's net for a reference layer list, ending in softmax."""
    net = Net(name)
    net.input("data", chw)
    for ly in layers:
        op, src = ly["op"], ly["inputs"]
        if op == "conv":
            net.conv(ly["name"], src[0], k=ly["k"], m=ly["m"],
                     stride=ly["stride"], pad=ly["pad"],
                     gain=ly.get("gain", 1.0))
        else:
            opdef = {"relu": relu, "add": add, "gap": global_avgpool,
                     "maxpool": lambda: maxpool(ly["k"], ly["stride"],
                                                ly["pad"]),
                     "fc": lambda: fc(ly["out"])}[op]()
            net.op(ly["name"], src, opdef)
    net.op("prob", [layers[-1]["name"]], softmax())
    return net


TWO_CHW = (3, 12, 12)
R50_CHW = (3, 64, 64)


def _resnet50_list(**kw):
    return r50_list(dict(R50, input_chw=list(R50_CHW)), **kw)


NETS = {
    # name: (program net builder, reference list, chw, a join to drop)
    "two_block": (lambda: _net(_two_block(), TWO_CHW), _two_block,
                  TWO_CHW, "b2"),
    "resnet50": (lambda: NETWORKS["resnet50"](2 / 7), _resnet50_list,
                 R50_CHW, "s4b3"),
}


def _served_err(outs, ref):
    probs = np.concatenate([np.asarray(o["prob"]).reshape(-1, ref.shape[1])
                            for o in outs])
    return float(reference.logit_err(probs, ref).max())


@pytest.fixture(scope="module", params=sorted(NETS))
def served(request):
    """One net served at n=1 (two images) and n=8 against the reference;
    both executables and the batch-1 plan kept."""
    build, layers, chw, drop = NETS[request.param]
    net = build()
    assert tuple(net.nodes["data"].out_shape) == chw
    images = harness.Images(chw, SEED)
    x = np.stack([images.get(i) for i in range(8)])
    ref = reference.logits(layers(), reference.init_params(
        layers(), chw, SEED), x)
    srv = PlanServer(lambda s: build(), CM, policy=POLICY,
                     params_seed=SEED)
    try:
        n1 = [srv.infer(xi) for xi in x[:2]]
        n8 = srv.infer_batch(list(x))
        sel = srv.plan_for(chw, 1)
        cnet = {n: srv.compiled_for(chw, n) for n in (1, 8)}
    finally:
        srv.close()
    return dict(name=request.param, net=net, layers=layers, chw=chw,
                drop=drop, x=x, ref=ref, sel=sel, cnet=cnet,
                err={1: _served_err(n1, ref[:2]), 8: _served_err(n8, ref)})


@pytest.mark.parametrize("n", [1, 8])
def test_served_residual_net_matches_the_reference(served, n):
    assert served["err"][n] < TOL, served["err"]


def test_dropped_join_fails_the_tolerance(served):
    """The net with one block's join replaced by its shortcut, served
    through the normal path, against the whole net's reference."""
    chw = served["chw"]
    dropped = served["layers"](drop=(served["drop"],))
    assert len(dropped) == len(served["layers"]()) - 1
    srv = PlanServer(lambda s: _net(dropped, chw, "dropped"), CM,
                     policy=POLICY, params_seed=SEED)
    try:
        outs = [srv.infer(xi) for xi in served["x"][:2]]
    finally:
        srv.close()
    assert _served_err(outs, served["ref"][:2]) > 10 * TOL


def test_ignored_gain_fails_the_tolerance(served):
    """The served executable run on weights drawn with every ``gain``
    ignored (the plan does not depend on it)."""
    plain = [{k: v for k, v in ly.items() if k != "gain"}
             for ly in served["layers"]()]
    assert plain != served["layers"]()
    raw = reference.init_params(plain, served["chw"], SEED)
    packed = compile_plan(served["sel"], raw, jit=False).params
    cnet = served["cnet"][1]
    outs = [cnet.fn(np.asarray(xi), packed) for xi in served["x"][:2]]
    assert _served_err(outs, served["ref"][:2]) > 10 * TOL


@pytest.mark.parametrize("n", [1, 8])
def test_op_scopes_of_a_residual_net(served, n):
    """Every scope the executable's map names belongs to the plan, a
    stride-2 3x3 conv's among them.  XLA fuses each join with its
    neighbours and the map charges a fusion to one scope, its root's, so
    a join's own scope reaches the compiled module but need not be a
    value of the map."""
    import jax
    cnet, net = served["cnet"][n], served["net"]
    shape = served["chw"] if n == 1 else (n, *served["chw"])
    got = cnet.op_scopes(shape)
    assert got and set(got.values()) <= cnet.scopes()
    assert any(f"node:{c.id}" in got.values() for c in net.conv_nodes()
               if c.scn.stride == 2 and c.scn.k == 3)
    joins = [nid for nid in net.order if net.nodes[nid].kind == "op"
             and net.nodes[nid].op.name == "add"]
    text = cnet.fn.lower(jax.ShapeDtypeStruct(shape, np.float32),
                         cnet.params).compile().as_text()
    assert joins and all(f"node:{nid})" in text or f"node:{nid}/" in text
                         for nid in joins)


# ----------------------------------------------------------------------
# the add join and the gain, at the smallest size
# ----------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["CHW", "HWC", "HCW"])
def test_add_is_layout_polymorphic(layout):
    rng = np.random.default_rng(0)
    xs = [rng.normal(size=(4, 5, 6)).astype(np.float32) for _ in range(3)]
    got = add().fn([convert_layout(x, "CHW", layout) for x in xs],
                   LAYOUT_BY_NAME[layout], None)
    np.testing.assert_array_equal(convert_layout(got, layout, "CHW"),
                                  xs[0] + xs[1] + xs[2])


@pytest.mark.parametrize("srcs", [["b", "s"], ["a"]],
                         ids=["mismatched", "one_input"])
def test_add_needs_two_or_more_inputs_of_one_shape(srcs):
    net = Net("bad")
    x = net.input("data", (4, 8, 8))
    net.conv("a", x, k=3, m=4, pad=1)
    net.conv("b", x, k=3, m=4, stride=2, pad=1)
    net.conv("s", x, k=1, m=4, pad=0)
    with pytest.raises(ValueError, match="add needs two or more inputs"):
        net.op("join", srcs, add())


def test_join_producers_forced_apart_get_one_conversion():
    """A branch conv pinned to HWC output and its shortcut to CHW: the
    join runs in one layout and the legalizer converts the other edge."""
    by_name = {p.name: p for p in registry()}
    net = _net(_two_block(), TWO_CHW)
    pick = {n.id: by_name["direct_lax_chw_chw_oihw"]
            for n in net.conv_nodes()}
    pick["b2_b"] = by_name["direct_lax_chw_hwc_oihw"]
    sel = select_fixed(net, CM, pick, "forced")
    joined = [e for e in sel.conversions if e[1] == "b2_add"]
    assert len(joined) == 1
    assert sel.conversions[joined[0]] in (["HWC", "CHW"], ["CHW", "HWC"])
    x = harness.Images(TWO_CHW, SEED).get(0)
    ref = reference.logits(_two_block(), reference.init_params(
        _two_block(), TWO_CHW, SEED), x[None])
    out = compile_plan(sel, net.init_params(SEED))(x)
    assert _served_err([out], ref) < TOL


def test_gain_scales_only_that_convs_draw():
    plain = _net(_two_block(None), TWO_CHW).init_params(3)
    scaled = _net(_two_block(0.5), TWO_CHW).init_params(3)
    assert list(plain) == list(scaled)
    for nid in plain:
        g = 0.5 if nid in ("b1_b", "b2_b") else 1.0
        # a power of two scales float64 and float32 values exactly
        np.testing.assert_array_equal(scaled[nid]["w"],
                                      np.float32(g) * plain[nid]["w"])
        np.testing.assert_array_equal(scaled[nid]["b"], plain[nid]["b"])


def _sha(params):
    h = hashlib.sha256()
    for nid in sorted(params):
        for k in sorted(params[nid]):
            a = params[nid][k]
            h.update(f"{nid}/{k}/{a.dtype}/{a.shape}".encode())
            h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name, digest", [
    ("googlenet",
     "659ade9cf51708333d27914ff5c7ed6e01cfc55765f2a27cbde8129d39c7a262"),
    ("vgg-d",
     "5cc1d2ce46c5a0389b737996a7cd9057dddf3a47060342d1bfa4991bfac52a8c"),
])
def test_nets_without_gain_draw_the_same_bits(name, digest):
    """The weights of seed 123 at 1/7 scale, as drawn before convs had a
    ``gain``: the benchmark's googlenet and vgg16 cells keep theirs."""
    assert _sha(NETWORKS[name](1 / 7).init_params(123)) == digest


# ----------------------------------------------------------------------
# ResNet-50 v1.5 at its published size
# ----------------------------------------------------------------------
def test_resnet50_is_table1_in_v15_form():
    net = NETWORKS["resnet50"]()
    assert net.nodes["data"].out_shape == (3, 224, 224)
    convs = net.conv_nodes()
    joins = [n for n in net.nodes.values()
             if n.kind == "op" and n.op.name == "add"]
    assert (len(convs), len(joins)) == (53, 16)
    assert sum(n.scn.macs for n in convs) + 2048 * 1000 == 4_089_184_256
    strided = {n.id for n in convs if n.scn.stride == 2}
    assert strided == {"conv1"} | {f"s{s}b1_{c}" for s in "345"
                                   for c in ("b", "proj")}
    assert {n.id for n in convs if n.gain != 1.0} == {
        f"{j.id[:-4]}_c" for j in joins}
    assert all(n.gain == 0.25 for n in convs if n.gain != 1.0)
    assert net.nodes[net.outputs()[0]].out_shape == (1000, 1, 1)


def test_resnet50_draws_the_references_bits():
    layers = r50_list(R50)
    chw = tuple(R50["input_chw"])
    net = NETWORKS["resnet50"]()
    shp = reference.shapes(layers, chw)
    assert [shp[ly["name"]] for ly in layers if ly["op"] == "conv"] == [
        n.out_shape for n in net.conv_nodes()]
    ref = reference.init_params(layers, chw, SEED)
    prog = net.init_params(SEED)
    assert [n for n in net.order if n in prog] == list(ref)
    for nid in ref:
        np.testing.assert_array_equal(prog[nid]["w"], ref[nid]["w"])
        np.testing.assert_array_equal(prog[nid]["b"], ref[nid]["b"])
