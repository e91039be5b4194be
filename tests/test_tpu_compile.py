"""The Pallas kernels compile for a TPU v5e, at the widths they serve.

Interpret mode (every other kernel test) runs a kernel body in Python
and accepts what the chip's compiler refuses: VMEM overflow, strided
vector slices, unsupported shape casts.  Here each kernel is lowered and
compiled for one chip of a described ``v5e:2x2`` topology, with no chip
attached.  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.layouts import LAYOUT_BY_NAME
from repro.core.primitives import registry
from repro.core.scenario import Scenario
from repro.kernels.conv_direct import conv_direct_pallas, fits_vmem
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.layout_transform import (chw_to_hwc_pallas,
                                            hwc_to_chw_pallas)
from repro.kernels.matmul import matmul_pallas
from repro.kernels.winograd_gemm import winograd_bgemm_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compile_for(one_chip):
    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
                for s in shapes]
        return jax.jit(fn).lower(*args).compile()
    return compile_


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# GoogLeNet conv2 in every fusable wire layout, an inception 5x5 and a
# VGG conv4 layer: direct-kernel strips that fit the scoped VMEM
@pytest.mark.parametrize("c,hw,k,m,in_layout,out_layout", [
    (64, 56, 3, 192, "HWC", "HWC"), (64, 56, 3, 192, "CHW", "HWC"),
    (64, 56, 3, 192, "HWC", "CHW"), (64, 56, 3, 192, "CHW", "CHW"),
    (32, 28, 5, 96, "HWC", "HWC"), (512, 28, 3, 512, "HWC", "HWC")])
def test_conv_direct(compile_for, c, hw, k, m, in_layout, out_layout):
    scn = Scenario(c=c, h=hw, w=hw, stride=1, k=k, m=m)
    assert fits_vmem(scn)
    hp = hw + 2 * scn.pad
    x = (c, hp, hp) if in_layout == "CHW" else (hp, hp, c)
    mp = -(-m // 128) * 128

    def f(x, w, b):
        return conv_direct_pallas(x, w, b, bm=128, in_layout=in_layout,
                                  out_layout=out_layout, interpret=False)
    compiled = compile_for(f, x, (k, k, c, mp), (mp,))
    assert _kernel_calls(compiled) == 1


@pytest.fixture
def chip_wrappers(monkeypatch):
    """The kernels' serving wrappers ask ``use_interpret()``, which sees
    the CPU here: compile them as they run on the chip."""
    import repro.kernels.conv_direct.kernel as direct
    import repro.kernels.matmul.kernel as mm
    import repro.kernels.winograd_gemm.kernel as wino
    for mod in (direct, mm, wino):
        monkeypatch.setattr(mod, "use_interpret", lambda: False)
    jax.clear_caches()  # no interpreted trace of a wrapper is reused


def _compile_primitive(compile_for, prim, scn):
    rng = np.random.default_rng(0)
    w = rng.normal(size=scn.weight_shape).astype(np.float32)
    packed = prim.prepare(scn, w, np.zeros((scn.m,), np.float32))
    lay = LAYOUT_BY_NAME[prim.l_in]
    x = tuple(scn.in_shape_chw[i] for i in lay.perm)
    treedef = jax.tree.structure(packed)
    fn = prim.make(scn)

    def f(x, *leaves):
        return fn(x, jax.tree.unflatten(treedef, leaves))
    shapes = [x] + [a.shape for a in jax.tree.leaves(packed)]
    return _kernel_calls(compile_for(f, *shapes))


@pytest.mark.parametrize("kernel", ["conv_direct", "conv_im2col",
                                    "winograd_gemm", "matmul"])
def test_autotuned_variants(compile_for, chip_wrappers, kernel):
    """At a real layer the registry offers only the autotuned variants
    whose blocks tile their axes in whole lanes; the smallest and the
    largest offered compile."""
    from repro.autotune import spaces
    space = spaces()[kernel]
    k = 1 if kernel == "matmul" else 3
    scn = Scenario(c=256, h=28, w=28, stride=1, k=k, m=512)  # VGG conv4
    offered = [p for p in map(space.make_primitive, space.configs())
               if p.supports(scn)]
    assert 0 < len(offered) < len(space.configs()), kernel
    for prim in (offered[0], offered[-1]):
        assert _compile_primitive(compile_for, prim, scn) >= 1, prim.name


def test_conv_direct_vgg_conv3_is_not_offered():
    """VGG conv3 (a padded 58x58x256 strip into 256 outputs) overflows
    the scoped VMEM, so the registry keeps the direct kernel off it."""
    scn = Scenario(c=256, h=56, w=56, stride=1, k=3, m=256)
    assert not fits_vmem(scn)
    prim = next(p for p in registry() if p.name == "pallas_direct_hwc")
    assert not prim.supports(scn)


@pytest.mark.parametrize("m,k,n", [
    (64, 256, 12544),     # GoogLeNet conv1 im2col GEMM (C*K*K = 147)
    (256, 2304, 3200),    # VGG conv3 im2col GEMM (N = 56 * 56, padded)
    (512, 512, 512)])
@pytest.mark.parametrize("lhs_layout,out_layout", [("mk", "mn"),
                                                   ("km", "nm")])
def test_matmul(compile_for, m, k, n, lhs_layout, out_layout):
    x = (k, m) if lhs_layout == "km" else (m, k)

    def f(x, y, b):
        return matmul_pallas(x, y, b, bm=min(m, 128),
                             lhs_layout=lhs_layout, out_layout=out_layout,
                             interpret=False)
    assert _kernel_calls(compile_for(f, x, (k, n), (n,))) == 1


@pytest.mark.parametrize("p,m,c,n,precision", [
    # F(2,3) on GoogLeNet conv2 (28x28 tiles), default MXU passes
    (16, 192, 128, 896, None),
    # F(4,3) on VGG conv3 (14x14 tiles), float32 products
    (36, 256, 256, 256, jax.lax.Precision.HIGHEST),
])
def test_winograd_bgemm(compile_for, p, m, c, n, precision):
    def f(u, v):
        return winograd_bgemm_pallas(u, v, precision=precision,
                                     interpret=False)
    assert _kernel_calls(compile_for(f, (p, m, c), (p, c, n))) == 1


@pytest.mark.parametrize("c,h,w", [(3, 224, 256), (192, 56, 128)])
def test_layout_transforms(compile_for, c, h, w):
    to_hwc = compile_for(
        lambda x: chw_to_hwc_pallas(x, interpret=False), (c, h, w))
    to_chw = compile_for(
        lambda x: hwc_to_chw_pallas(x, interpret=False), (h, w, c))
    assert _kernel_calls(to_hwc) == _kernel_calls(to_chw) == 1


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_tinyllama(compile_for, causal):
    """TinyLlama widths: 32 query heads over 4 KV heads, head_dim 64."""
    def f(q, k, v):
        return flash_attention_pallas(q, k, v, scale=0.125, causal=causal,
                                      interpret=False)
    assert _kernel_calls(compile_for(f, (32, 512, 64), (4, 512, 64),
                                     (4, 512, 64))) == 1


def test_lrn_batched_has_no_reduce_window(compile_for):
    """GoogLeNet's norm2 at batch 8 compiles to elementwise fusion: the
    channel window sum leaves no ``reduce-window`` instruction."""
    from repro.core.graph import lrn
    op, layout = lrn(), LAYOUT_BY_NAME["CHW"]
    compiled = compile_for(jax.vmap(lambda x: op.fn([x], layout, None)),
                           (8, 192, 56, 56))
    assert "reduce-window" not in compiled.as_text()


@pytest.mark.parametrize("net,node", [
    ("googlenet", "conv1"), ("googlenet", "conv2"),
    ("googlenet", "i3a_5x5"), ("googlenet", "i4a_1x1"),
    ("vgg-a", "conv3_2"), ("alexnet", "conv1"),
    # ResNet-50 v1.5: a stage-4 1x1 reduce, a stride-2 3x3
    ("resnet50", "s4b2_a"), ("resnet50", "s3b1_b")])
def test_offered_pallas_primitives(compile_for, chip_wrappers, net, node):
    """Every Pallas primitive the registry offers at a real layer
    compiles through its serving wrapper, as a plan would run it."""
    from repro.convnets import NETWORKS
    scn = NETWORKS[net](1.0).nodes[node].scn
    prims = [p for p in registry()
             if p.family == "pallas" and p.supports(scn)]
    assert prims, f"no Pallas primitive offered at {net}/{node}"
    for prim in prims:
        assert _compile_primitive(compile_for, prim, scn) >= 1, prim.name


@pytest.mark.parametrize("placement,mode", [("dp", "shard_map"),
                                            ("tp", "tp_shard_map")])
def test_mesh_plan_with_pallas_kernels(topo, chip_wrappers, monkeypatch,
                                       placement, mode):
    """A batched plan of Pallas convs over a described 2x2 mesh, every
    node data-parallel (the ``shard_map`` fast path) or every node
    tensor-parallel (the explicit-collective walker)."""
    import dataclasses

    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.costs import TPU_V5E_SPEC, AnalyticCostModel
    from repro.core.plan import compile_plan
    from repro.core.selection import select_pbqp
    from repro.serving import conv_stack
    # described devices hold no arrays: keep the packed weights on host
    monkeypatch.setattr(jax, "device_put", lambda v, s=None: v)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    net = conv_stack((128, 28, 28), depth=2, width=128).with_batch(8)
    sel = select_pbqp(net, AnalyticCostModel(TPU_V5E_SPEC,
                                             include_tpu_only=True),
                      families=["pallas"],
                      mesh_axes={"data": 2, "model": 2})
    sel = dataclasses.replace(sel, choices={
        nid: dataclasses.replace(ch, placement=placement)
        for nid, ch in sel.choices.items()})
    cnet = compile_plan(sel, net.init_params(0), batch=8, mesh=mesh)
    assert cnet.mesh_mode == mode

    def shaped(nid, a):
        tp_slab = (sel.choices[nid].placement == "tp"
                   and net.nodes[nid].kind == "conv")
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(
            mesh, P("model") if tp_slab else P()))
    params = {nid: jax.tree.map(lambda a, nid=nid: shaped(nid, a), v)
              for nid, v in cnet.params.items()}
    x = jax.ShapeDtypeStruct((8, 128, 28, 28), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    compiled = cnet.fn.lower(x, params).compile()
    assert _kernel_calls(compiled) >= 1
