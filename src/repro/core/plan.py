"""Execution of an instantiated DNN: the paper's "simple code generator
which emitted calls to primitive operations" — here it builds a single
jit'd function that walks the DAG in topological order, invoking the
selected primitive per conv layer and the explicit layout-conversion
chains the legalizer inserted on illegal edges.

With ``mesh=`` the generator emits a *mesh-sharded* executable
realizing every node's solved device placement (the
``Choice.placement`` axis of ``select_pbqp(..., mesh_axes=...)``),
one lowering per placement family:

* **dp / rep only** — ``dp`` nodes run batch-sharded over the mesh's
  batch axes (``data`` x ``model``, flattened), ``rep`` replicated.
  All-``dp`` plans take a ``shard_map`` fast path; mixed plans compile
  with one ``NamedSharding`` constraint per node so GSPMD inserts
  exactly the resharding collectives the PBQP edges priced.
* **any tp node** — an explicit-collective ``shard_map`` walker:
  ``tp`` convs run with their output-channel weight slab sharded over
  the ``model`` axis and an intra-group channel ``all_gather`` after
  the call; form changes between dp/tp/rep values are emitted as the
  same gathers and slices the edge costs priced.
* **pp plan** — contiguous stage runs lower onto
  :func:`~repro.runtime.pipeline_parallel.pipeline_apply`
  (the GPipe fill-drain schedule over the ``stage`` axis), with stage
  boundaries wired in logical CHW exactly as the solver priced them.

Runs on real pods and on fake CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) alike; see
docs/distributed.md.
"""
from __future__ import annotations

import math
import re
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import default_registry
from ..obs.trace import get_tracer
from .graph import Net
from .layouts import LAYOUT_BY_NAME
from .primitives import convert_layout
from .selection import Placement, SelectionResult, pp_microbatches

__all__ = ["compile_plan", "CompiledNet", "measure", "xla_compile_stats",
           "mesh_shape_dict", "node_scope", "edge_scope", "packed_shape",
           "unpack_images"]


def mesh_shape_dict(mesh) -> Dict[str, int]:
    """Axis name -> size for a jax Mesh.  Single definition —
    ``launch.mesh`` re-exports it for CLI-side callers."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))

#: JAX's event for an executable built, by compiling or by a load from
#: the persistent compilation cache
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: process-wide: every XLA executable JAX builds, whoever asks for it —
#: the cost the serving LRU and the plan cache exist to amortise
_XLA_COMPILES = default_registry().counter("xla_compiles")
_XLA_COMPILE_S = default_registry().counter("xla_compile_s")


def _on_jax_event(event: str, duration: float, **kw) -> None:
    if event == BACKEND_COMPILE_EVENT:
        _XLA_COMPILES.add()
        _XLA_COMPILE_S.add(float(duration))


jax.monitoring.register_event_duration_secs_listener(_on_jax_event)


def xla_compile_stats() -> Dict[str, float]:
    """Executables XLA has built in this process, and their seconds."""
    return {"xla_compiles": _XLA_COMPILES.value,
            "xla_compile_s": float(_XLA_COMPILE_S.value)}


def packed_shape(chw, batch: int = 1) -> tuple:
    """Shape of the float32 array a single-device executable takes for
    ``batch`` images of shape ``chw``: each image's values in row-major
    order, then zeros, in ``K`` rows of 128 lanes with ``K`` a multiple
    of 8.  A TPU tiles the last two dims of a float32 array by 8 x 128,
    so the tiled layout of this shape is its row-major bytes: nothing
    needs rearranging on the host.  ``(K, 128)`` for one image,
    else ``(batch, K, 128)``."""
    k = -(-math.prod(chw) // 1024) * 8
    return (k, 128) if batch == 1 else (batch, k, 128)


def unpack_images(xp, chw, batch: int = 1):
    """The (C, H, W) image, or (N, C, H, W) images, inside a packed array
    (:func:`packed_shape`): a view of a numpy array, a slice and reshape
    of a traced one."""
    flat = xp.reshape(batch, -1)[:, :math.prod(chw)]
    return flat.reshape(tuple(chw) if batch == 1 else (batch, *chw))


def node_scope(nid: str) -> str:
    """``jax.named_scope`` of a PBQP node's primitive or op call."""
    return f"node:{nid}"


def edge_scope(src: str, dst: str) -> str:
    """``jax.named_scope`` of the layout conversion chain on an edge;
    ``dst`` is ``"out"`` for an output's conversion to logical CHW."""
    return f"edge:{src}->{dst}"


#: a scope in an HLO instruction's ``op_name`` metadata: between path
#: separators, or inside a transform's parentheses (``vmap(node:c1)``)
_SCOPE_RE = re.compile(r"(?:node|edge):[^/()\"]+")
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+)\s*=(.*)$")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_FUSION_CALLS_RE = re.compile(r"\bfusion\(.*?\bcalls=%([^\s,}]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def hlo_op_scopes(hlo_text: str, scopes) -> Dict[str, str]:
    """``{instruction name: scope}`` over an HLO module's text, for the
    instructions a device runs (those outside fusion bodies).

    An instruction's scope is the innermost of ``scopes`` in its
    ``op_name`` metadata; a fusion without one takes the commonest scope
    of its body.  An instruction XLA added with no scope at all — an
    input or weight copy, a prefetch, a bitcast — takes the scope of
    its first consumer that has one: it exists to feed that consumer.
    """
    comps: Dict[str, list] = {}
    body: list = []
    for line in hlo_text.splitlines():
        if line.startswith("%") or line.startswith("ENTRY"):
            name = line.split("%", 1)[1].split()[0]
            body = comps.setdefault(name, [])
            continue
        m = _INSTR_RE.match(line)
        if m is not None:
            meta = _OP_NAME_RE.search(m.group(2))
            own = [t for t in _SCOPE_RE.findall(meta.group(1))
                   if t in scopes] if meta else []
            body.append((m.group(1), m.group(2), own[-1] if own else None))
    fused = {c for _, rhs, _ in (i for b in comps.values() for i in b)
             for c in _FUSION_CALLS_RE.findall(rhs)}

    def body_scope(comp: str) -> Optional[str]:
        found = []
        for _, rhs, own in comps.get(comp, ()):
            inner = _FUSION_CALLS_RE.findall(rhs)
            found.append(own or (body_scope(inner[0]) if inner else None))
        found = [f for f in found if f]
        return max(set(found), key=found.count) if found else None

    out: Dict[str, str] = {}
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        scope: Dict[str, Optional[str]] = {}
        for name, rhs, own in instrs:
            inner = _FUSION_CALLS_RE.findall(rhs)
            scope[name] = own or (body_scope(inner[0]) if inner else None)
        # walking back from the last instruction, every consumer of an
        # unscoped one is settled before it is, and the earliest
        # consumer is the last to write
        unscoped = {n for n, s in scope.items() if s is None}
        for name, rhs, _ in reversed(instrs):
            if scope[name] is None:
                continue
            for op in _OPERAND_RE.findall(rhs):
                if op in unscoped:
                    scope[op] = scope[name]
        out.update((n, s) for n, s in scope.items() if s is not None)
    return out


@dataclass
class CompiledNet:
    sel: SelectionResult
    fn: Callable                      # (x, params) -> outputs dict
    params: Dict[str, Any]            # packed per-node parameters
    build_s: float = 0.0              # wall time of weight packing + wiring
    #: minibatch the executable was compiled for: 1 -> (C, H, W) in/out,
    #: > 1 -> (N, C, H, W) in and a leading N axis on every output
    batch: int = 1
    #: edges executed as fused prologues/epilogues instead of
    #: materialized convert_layout dispatches (observability for tests
    #: and the fusion benchmark)
    fused_edges: int = 0
    #: mesh the executable is sharded over (None: single device)
    mesh: Optional[Any] = None
    #: nodes realized batch-sharded over the mesh's batch axes
    dp_nodes: int = 0
    #: "shard_map" (all-dp fast path) | "gspmd" (per-node constraints)
    #: | "tp_shard_map" (explicit-collective tp walker) | "pipeline"
    #: (GPipe stage schedule) | "" (no mesh)
    mesh_mode: str = ""
    #: nodes realized weight-sharded over the mesh's model axis
    tp_nodes: int = 0
    #: nodes realized as pipeline stages over the mesh's stage axis
    pp_nodes: int = 0
    #: per-conv-node maker callables (fusion-resolved wire layouts) —
    #: kept so obs.drift.InstrumentedNet can rebuild the same walk with
    #: per-node timing.  None only on hand-constructed instances.
    makers: Optional[Dict[str, Callable]] = None
    #: (C, H, W) of one input image
    image_shape: tuple = ()
    #: ``(packed x, params) -> outputs``: ``fn`` behind an unpack of its
    #: input (:func:`packed_shape`), the executable a server runs.  None
    #: on mesh executables, which take ``fn``'s input.
    entry: Optional[Callable] = None

    @property
    def in_shape(self) -> tuple:
        """Shape of the float32 array the executable takes: packed, or
        ``fn``'s (N, C, H, W) on a mesh executable."""
        if self.entry is None:
            return (self.batch, *self.image_shape) if self.batch > 1 \
                else self.image_shape
        return packed_shape(self.image_shape, self.batch)

    def images(self, xin):
        """The (C, H, W) / (N, C, H, W) images inside an ``in_shape``
        array; a view of a numpy one."""
        if self.entry is None:
            return xin
        return unpack_images(xin, self.image_shape, self.batch)

    def pack(self, x) -> np.ndarray:
        """A new ``in_shape`` array holding image(s) ``x``."""
        xin = np.zeros(self.in_shape, np.float32)
        view = self.images(xin)
        if np.shape(x) != view.shape:
            raise ValueError(f"expected input of shape {view.shape}, got "
                             f"{np.shape(x)}")
        view[...] = np.asarray(x)
        return xin

    def __call__(self, x):
        """Run on image(s) ``x``, (C, H, W) or (N, C, H, W), or on an
        ``in_shape`` array that already holds them packed."""
        if self.entry is None:
            return self.fn(jnp.asarray(x), self.params)
        if np.shape(x) != self.in_shape:
            x = self.pack(x)
        return self.entry(x, self.params)

    def scopes(self) -> set:
        """Every ``node:``/``edge:`` scope the plan's executable opens."""
        sel, net = self.sel, self.sel.net
        out = {node_scope(nid) for nid in net.order
               if net.nodes[nid].kind != "input"}
        out |= {edge_scope(src, dst) for (src, dst), chain
                in sel.conversions.items() if chain}
        out |= {edge_scope(nid, "out") for nid in net.outputs()
                if sel.choices[nid].l_out != "CHW"}
        return out

    def op_scopes(self, x_shape) -> Dict[str, str]:
        """``{HLO instruction name: scope}`` of the executable that
        :meth:`__call__` runs for images of shape ``x_shape``: which PBQP
        node (``node:<id>``) or layout conversion edge
        (``edge:<src>-><dst>``) each instruction of the optimized module
        — and so each device op of a profile — belongs to.  Lowers and
        compiles, which for a shape already run is a cache hit.  Needs a
        jitted executable (``jit=True``)."""
        fn, shape = self.fn, tuple(x_shape)
        if self.entry is not None:
            fn, shape = self.entry, packed_shape(shape[-3:], self.batch)
        x = jax.ShapeDtypeStruct(shape, jnp.float32)
        text = fn.lower(x, self.params).compile().as_text()
        return hlo_op_scopes(text, self.scopes())


def compile_plan(sel: SelectionResult, raw_params: Dict[str, Dict],
                 jit: bool = True, fuse_across_layers: bool = False,
                 batch: int = 1, mesh: Optional[Any] = None) -> CompiledNet:
    """``fuse_across_layers=False`` (default) inserts optimization
    barriers between primitive calls: the paper's code generator emits
    *calls into a library of routines*, so no cross-layer fusion exists
    and per-layer profiled costs compose additively.  Letting XLA fuse
    across layers (True) breaks that additivity — useful as an extra
    baseline, but it is a different system than the paper's.

    ``batch > 1`` builds a *batched* executable: the single-image
    program is vmapped over a leading batch axis, so one invocation runs
    the whole tower for N images — per-image dispatch/packing overhead
    is paid once, which is exactly the amortization the batch-aware
    cost model prices (``Scenario.n``).  Input becomes (N, C, H, W) and
    every output gains a leading N axis.

    **Packed input.**  A single-device executable's ``entry`` takes its
    image(s) packed (:func:`packed_shape`), in bytes that already are
    the TPU's tiled layout, and unpacks them on the device inside the
    same program; ``__call__`` packs.  ``fn``, the same walk on
    unpacked input, is compiled only if called.

    **Transform fusion pass.**  Edges the selection realized as fused
    (``sel.fusions``, see :func:`~repro.core.selection.select_pbqp` with
    ``fuse=True``) get no ``convert_layout`` dispatch at all: the
    consumer's maker is built via ``Primitive.make_fused`` to read the
    producer's layout in its prologue (kind ``"in"``), or the producer's
    to emit the consumer's layout in its epilogue (kind ``"out"``).  The
    fused call executes as ONE region — under the default per-layer
    barriers the transform can never be split back out into an HBM
    round trip.  The pass is orthogonal to ``fuse_across_layers`` and
    ``batch``: fused makers are emitted regardless of barrier placement
    and are vmap-safe, so all flag combinations compose.

    **Mesh-sharded executables.**  ``mesh`` (with ``batch > 1``)
    realizes the plan's device placements: nodes whose
    :class:`~repro.core.selection.Choice` carries ``placement="dp"``
    run batch-sharded over the mesh's ``data`` axis, ``"rep"`` nodes
    replicated.  An all-``dp`` plan compiles through ``shard_map`` (one
    per-shard vmapped program per device — the pure data-parallel fast
    path); any plan with a ``rep`` node compiles the batched program
    with one ``NamedSharding`` constraint per node, so GSPMD inserts
    exactly the resharding collectives the selection's edge costs
    priced.  Input is (N, C, H, W) as for any batched executable;
    callers pass host arrays and receive global (gathered-on-read)
    outputs, so a mesh executable is a drop-in for the single-device
    batched one (verified output-identical in tests/test_distributed.py).
    """
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    if mesh is not None and batch < 2:
        raise ValueError("mesh-sharded executables are batched: pass "
                         "batch >= 2 (a single image cannot be sharded "
                         "over the data axis)")
    net = sel.net
    chw = tuple(next(net.nodes[nid].out_shape for nid in net.order
                     if net.nodes[nid].kind == "input"))
    dp_nodes = tp_nodes = pp_nodes = 0
    d_mesh = 1
    batch_axes: tuple = ()
    if mesh is not None:
        mesh_shape = mesh_shape_dict(mesh)
        # dp shards the batch over ALL non-stage axes (data x model),
        # mirroring the solver's pricing (selection._mesh_dims)
        batch_axes = tuple(a for a in ("data", "model")
                           if a in mesh_shape)
        for a in batch_axes:
            d_mesh *= int(mesh_shape[a])
        kinds = {nid: Placement.parse(ch.placement).kind
                 for nid, ch in sel.choices.items()}
        dp_nodes = sum(1 for k in kinds.values() if k == "dp")
        tp_nodes = sum(1 for k in kinds.values() if k == "tp")
        pp_nodes = sum(1 for k in kinds.values() if k == "pp")
        if dp_nodes and (d_mesh <= 1 or batch % d_mesh):
            raise ValueError(
                f"plan has {dp_nodes} dp nodes but mesh {mesh_shape} "
                f"cannot shard batch {batch} over its batch axes "
                f"{batch_axes}")
        if tp_nodes:
            d_tp = int(mesh_shape.get("model", 1))
            d_data = int(mesh_shape.get("data", 1))
            if d_tp <= 1:
                raise ValueError(
                    f"plan has {tp_nodes} tp nodes but mesh "
                    f"{mesh_shape} has no 'model' axis to shard "
                    f"weights over")
            if batch % d_data:
                raise ValueError(
                    f"tp plans keep the batch data-sharded: batch "
                    f"{batch} does not divide over the 'data' axis "
                    f"of {mesh_shape}")
        if pp_nodes:
            if "stage" not in mesh_shape:
                raise ValueError(
                    f"plan has {pp_nodes} pp nodes but mesh "
                    f"{mesh_shape} has no 'stage' axis")
            if pp_nodes != len(net.order):
                raise ValueError(
                    "pipeline plans are all-or-nothing: "
                    f"{pp_nodes}/{len(net.order)} nodes carry a pp "
                    "placement")
    t0 = time.perf_counter()

    # fusion pass: effective wire layouts per conv node.  Kind "in"
    # means the consumer reads the producer's declared l_out; kind
    # "out" means the (single-consumer) producer emits the consumer's
    # l_in.  Selection guarantees an edge is fused or converted, never
    # both, so the two maps cannot conflict.
    fusions = sel.fusions
    eff_in: Dict[str, str] = {}
    eff_out: Dict[str, str] = {}
    for (src, dst), kind in fusions.items():
        if kind == "in":
            eff_in[dst] = sel.choices[src].l_out
        elif kind == "out":
            eff_out[src] = sel.choices[dst].l_in
        else:
            raise ValueError(f"unknown fusion kind {kind!r} on edge "
                             f"({src}, {dst})")

    packed: Dict[str, Any] = {}
    makers: Dict[str, Callable] = {}
    for nid in net.order:
        node = net.nodes[nid]
        ch = sel.choices[nid]
        if node.kind == "conv":
            p = raw_params[nid]
            if mesh is not None and kinds[nid] == "tp":
                # tp conv: slice the raw output-channel slab into d_tp
                # shards, pack each at the shard scenario, and stack —
                # the executor shards the stacked leading axis over the
                # mesh's 'model' axis so each device packs 1/d_tp of
                # the weights.  Fusion is never offered on tp edges,
                # so the maker wires the primitive's own l_in/l_out.
                if node.scn.m % d_tp:
                    raise ValueError(
                        f"tp node {nid}: m={node.scn.m} does not "
                        f"divide over d_tp={d_tp}")
                msh = node.scn.m // d_tp
                scn_tp = node.scn.with_(m=msh)
                shards = [ch.primitive.prepare(
                              scn_tp, p["w"][i * msh:(i + 1) * msh],
                              p["b"][i * msh:(i + 1) * msh])
                          for i in range(d_tp)]
                packed[nid] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *shards)
                makers[nid] = ch.primitive.make_fused(
                    scn_tp, l_in=ch.l_in, l_out=ch.l_out)
                continue
            packed[nid] = ch.primitive.prepare(node.scn, p["w"], p["b"])
            makers[nid] = ch.primitive.make_fused(
                node.scn, l_in=eff_in.get(nid, ch.l_in),
                l_out=eff_out.get(nid, ch.l_out))
        elif node.kind == "op" and nid in raw_params:
            packed[nid] = jax.tree.map(jnp.asarray, raw_params[nid])

    # Batched executables compile without the per-layer barriers: (a)
    # optimization_barrier has no vmap batching rule, and (b) the
    # barriers exist to keep per-layer *profiled* costs additive — a
    # measurement-methodology concern, while the batched path is a
    # throughput path where cross-layer fusion is desirable.
    barrier = (lambda v: v) if fuse_across_layers or batch > 1 else \
        (lambda v: jax.lax.optimization_barrier(v))

    if mesh is not None:
        # place the packed weights on the mesh once: replicated, with tp
        # weight slabs split over 'model'.  Left uncommitted they would
        # sit on one device and be shipped to the others on every call.
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        def placed(nid, v):
            tp_slab = kinds[nid] == "tp" and net.nodes[nid].kind == "conv"
            return jax.device_put(
                v, NamedSharding(mesh, P("model") if tp_slab else P()))
        packed = {nid: placed(nid, v) for nid, v in packed.items()}
        if pp_nodes:
            fn = _build_pipeline_fn(sel, net, makers, mesh, batch, jit)
            mode = "pipeline"
        elif tp_nodes:
            fn = _build_tp_fn(sel, net, makers, packed, mesh, batch,
                              jit)
            mode = "tp_shard_map"
        else:
            fn, mode = _build_mesh_fn(sel, net, makers, mesh,
                                      batch_axes, d_mesh, dp_nodes, jit)
        cnet = CompiledNet(sel, fn, packed,
                           build_s=time.perf_counter() - t0, batch=batch,
                           fused_edges=len(fusions), mesh=mesh,
                           dp_nodes=dp_nodes, mesh_mode=mode,
                           makers=makers, tp_nodes=tp_nodes,
                           pp_nodes=pp_nodes, image_shape=chw)
    else:
        run = _image_walker(sel, net, makers, barrier)
        if batch > 1:
            run = jax.vmap(run, in_axes=(0, None))

        def run_packed(xp, params):
            return run(unpack_images(xp, chw, batch), params)

        cnet = CompiledNet(sel, jax.jit(run) if jit else run, packed,
                           build_s=time.perf_counter() - t0,
                           batch=batch, fused_edges=len(fusions),
                           makers=makers, image_shape=chw,
                           entry=jax.jit(run_packed) if jit else run_packed)
    get_tracer().emit("compile", t0, time.perf_counter(),
                      nodes=len(net.order), batch=batch,
                      fused_edges=cnet.fused_edges,
                      mesh_mode=cnet.mesh_mode, dp_nodes=cnet.dp_nodes,
                      tp_nodes=cnet.tp_nodes, pp_nodes=cnet.pp_nodes)
    return cnet


def _image_walker(sel: SelectionResult, net: Net,
                  makers: Dict[str, Callable],
                  barrier: Callable = lambda v: v) -> Callable:
    """The per-image DAG walk every executable variant shares: invoke
    the selected primitive per conv node, the op function per op node,
    the legalizer's conversion chains per mismatched edge, then convert
    outputs to logical CHW.  ``barrier`` wraps per-layer results (the
    paper's no-cross-layer-fusion discipline; identity for batched and
    mesh executables)."""
    def run(x, params):
        vals: Dict[str, Any] = {}
        for nid in net.order:
            node = net.nodes[nid]
            if node.kind == "input":
                vals[nid] = x  # inputs arrive in logical CHW
                continue
            ins = []
            for src in node.inputs:
                v = vals[src]
                chain = sel.conversions.get((src, nid))
                if chain:
                    with jax.named_scope(edge_scope(src, nid)):
                        for a, b in zip(chain, chain[1:]):
                            v = barrier(convert_layout(v, a, b))
                ins.append(v)
            with jax.named_scope(node_scope(nid)):
                if node.kind == "conv":
                    vals[nid] = barrier(makers[nid](ins[0], params[nid]))
                else:
                    layout = LAYOUT_BY_NAME[sel.choices[nid].l_in]
                    vals[nid] = node.op.fn(ins, layout, params.get(nid))
        outs = {}
        for nid in net.outputs():
            l_out = sel.choices[nid].l_out
            if l_out == "CHW":
                outs[nid] = vals[nid]
                continue
            with jax.named_scope(edge_scope(nid, "out")):
                outs[nid] = convert_layout(vals[nid], l_out, "CHW")
        return outs
    return run


def _build_mesh_fn(sel: SelectionResult, net: Net, makers: Dict[str,
                   Callable], mesh, batch_axes: tuple, d_mesh: int,
                   dp_nodes: int, jit: bool):
    """Emit the mesh-sharded executable for a {dp, rep} plan.

    ``dp`` shards the batch over *all* the mesh's batch axes
    (``batch_axes`` — ``data`` and, when present, ``model`` — exactly
    the flattening the solver priced), so a pure-dp plan costs and runs
    the same on an ``(8,)`` and a ``(2, 4)`` mesh.  Two modes (both
    barrier-free, like every batched executable):

    * ``shard_map`` — every node is ``dp``: split the batch once over
      the batch axes and run the vmapped per-shard program
      (:func:`_image_walker`, the same walk the single-device
      executable runs) on each device.  No partitioner in the loop;
      the pure data-parallel serving fast path.
    * ``gspmd`` — mixed placements: run the batched program with one
      ``NamedSharding`` constraint per node, so GSPMD inserts exactly
      the resharding collectives the selection's edge costs priced
      (``dp -> rep``: all-gather; ``rep -> dp``: a local slice).  This
      walker is the batched per-node-vmap variant of the walk — the
      constraints must land on whole-batch values, so it cannot reuse
      the vmapped per-image program.
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    dp_spec = P(batch_axes) if batch_axes else P()
    if dp_nodes == len(net.order) and d_mesh > 1:
        inner = jax.vmap(_image_walker(sel, net, makers),
                         in_axes=(0, None))
        # no varying-axes check: pallas_call outputs carry no vma type
        fn = jax.shard_map(inner, mesh=mesh, in_specs=(dp_spec, P()),
                           out_specs=dp_spec, check_vma=False)
        return (jax.jit(fn) if jit else fn), "shard_map"

    def spec_of(nid: str) -> "NamedSharding":
        pl = sel.choices[nid].placement
        return NamedSharding(mesh, dp_spec if pl == "dp" else P())

    def run_batched(x, params):
        vals: Dict[str, Any] = {}
        for nid in net.order:
            node = net.nodes[nid]
            ch = sel.choices[nid]
            if node.kind == "input":
                v = x
            else:
                ins = []
                for src in node.inputs:
                    vi = vals[src]
                    chain = sel.conversions.get((src, nid))
                    if chain:
                        for a, b in zip(chain, chain[1:]):
                            vi = jax.vmap(
                                lambda t, a=a, b=b:
                                convert_layout(t, a, b))(vi)
                    ins.append(vi)
                if node.kind == "conv":
                    v = jax.vmap(makers[nid], in_axes=(0, None))(
                        ins[0], params[nid])
                else:
                    layout = LAYOUT_BY_NAME[ch.l_in]
                    p = params.get(nid)
                    v = jax.vmap(
                        lambda *xs, op=node.op, lay=layout, p=p:
                        op.fn(list(xs), lay, p))(*ins)
            vals[nid] = jax.lax.with_sharding_constraint(v, spec_of(nid))
        return {nid: jax.vmap(
                    lambda t, lo=sel.choices[nid].l_out:
                    convert_layout(t, lo, "CHW"))(vals[nid])
                for nid in net.outputs()}

    return (jax.jit(run_batched) if jit else run_batched), "gspmd"


def _build_tp_fn(sel: SelectionResult, net: Net,
                 makers: Dict[str, Callable], packed: Dict[str, Any],
                 mesh, batch: int, jit: bool):
    """Explicit-collective ``shard_map`` walker for plans with tp nodes.

    Every value inside the walker carries one of three *forms* — how its
    leading batch axis is laid out across the mesh:

    * ``dp``  — ``batch / (d_data * d_tp)`` rows per device (sharded
      over all batch axes);
    * ``ds``  — ``batch / d_data`` rows per device (sharded over
      ``data`` only, replicated across ``model``) — the working form of
      tp nodes, whose parallelism lives in the weight shards;
    * ``rep`` — the full batch everywhere.

    Form changes are emitted as exactly the collectives the solver's
    edge costs priced (``dp -> rep``/``dp -> ds``/``ds -> rep``:
    tiled all-gathers; the reverse directions: local slices).  A tp
    conv runs its maker on the device's weight shard (1/d_tp of the
    output channels), converts to logical CHW, all-gathers the channel
    axis across ``model``, and converts back — the intra-group
    collective the node's setup cost carried.
    """
    from jax.sharding import PartitionSpec as P

    mesh_shape = mesh_shape_dict(mesh)
    d_data = int(mesh_shape.get("data", 1))
    d_tp = int(mesh_shape["model"])
    batch_axes = tuple(a for a in ("data", "model") if a in mesh_shape)

    kind_of = {nid: Placement.parse(sel.choices[nid].placement).kind
               for nid in net.order}
    FORM = {"dp": "dp", "tp": "ds", "rep": "rep"}
    form_of = {nid: FORM[kind_of[nid]] for nid in net.order}
    rows = {"dp": batch // (d_data * d_tp), "ds": batch // d_data,
            "rep": batch}

    def _reform(v, src, dst):
        if src == dst or rows[src] == rows[dst]:
            return v
        if src == "dp" and dst == "rep":
            return jax.lax.all_gather(v, batch_axes, axis=0, tiled=True)
        if src == "dp" and dst == "ds":
            return jax.lax.all_gather(v, "model", axis=0, tiled=True)
        if src == "ds" and dst == "rep":
            return jax.lax.all_gather(v, "data", axis=0, tiled=True)
        # remaining directions drop rows: purely local slices
        i = jax.lax.axis_index("data") if d_data > 1 else 0
        j = jax.lax.axis_index("model")
        if src == "rep" and dst == "dp":
            start = (i * d_tp + j) * rows["dp"]
        elif src == "rep" and dst == "ds":
            start = i * rows["ds"]
        elif src == "ds" and dst == "dp":
            start = j * rows["dp"]
        else:
            raise AssertionError(f"unreachable reform {src}->{dst}")
        return jax.lax.dynamic_slice_in_dim(v, start, rows[dst], axis=0)

    def _convert(v, chain):
        if chain:
            for a, b in zip(chain, chain[1:]):
                v = jax.vmap(
                    lambda t, a=a, b=b: convert_layout(t, a, b))(v)
        return v

    def _bring(v, src, dst, chain):
        # convert layouts on whichever side holds fewer rows — the
        # same min-rows discount the edge's transform cost applied
        if rows[dst] <= rows[src]:
            return _convert(_reform(v, src, dst), chain)
        return _reform(_convert(v, chain), src, dst)

    in_forms = {form_of[nid] for nid in net.order
                if net.nodes[nid].kind == "input"}
    x_form = in_forms.pop() if len(in_forms) == 1 else "rep"

    def walker(x, params):
        vals: Dict[str, Any] = {}
        for nid in net.order:
            node = net.nodes[nid]
            ch = sel.choices[nid]
            form = form_of[nid]
            if node.kind == "input":
                vals[nid] = _reform(x, x_form, form)
                continue
            ins = [_bring(vals[src], form_of[src], form,
                          sel.conversions.get((src, nid)))
                   for src in node.inputs]
            if node.kind == "conv":
                if kind_of[nid] == "tp":
                    # local leading axis of the stacked shard slab is
                    # size 1 under P("model"): [0] is this device's cut
                    p_local = jax.tree.map(lambda a: a[0], params[nid])
                    y = jax.vmap(makers[nid], in_axes=(0, None))(
                        ins[0], p_local)
                    lo = ch.l_out
                    y = jax.vmap(
                        lambda t: convert_layout(t, lo, "CHW"))(y)
                    y = jax.lax.all_gather(y, "model", axis=1,
                                           tiled=True)
                    vals[nid] = jax.vmap(
                        lambda t: convert_layout(t, "CHW", lo))(y)
                else:
                    vals[nid] = jax.vmap(makers[nid], in_axes=(0, None))(
                        ins[0], params[nid])
            else:
                layout = LAYOUT_BY_NAME[ch.l_in]
                p = params.get(nid)
                vals[nid] = jax.vmap(
                    lambda *xs, op=node.op, lay=layout, p=p:
                    op.fn(list(xs), lay, p))(*ins)
        return {nid: jax.vmap(
                    lambda t, lo=sel.choices[nid].l_out:
                    convert_layout(t, lo, "CHW"))(vals[nid])
                for nid in net.outputs()}

    def spec(form):
        if form == "dp":
            return P(batch_axes)
        if form == "ds" and d_data > 1:
            return P("data")
        return P()

    p_specs = {nid: (P("model") if (net.nodes[nid].kind == "conv"
                                    and kind_of[nid] == "tp") else P())
               for nid in packed}
    fn = jax.shard_map(
        walker, mesh=mesh,
        in_specs=(spec(x_form), p_specs),
        out_specs={nid: spec(form_of[nid]) for nid in net.outputs()},
        check_vma=False)
    return jax.jit(fn) if jit else fn


def _build_pipeline_fn(sel: SelectionResult, net: Net,
                       makers: Dict[str, Callable], mesh, batch: int,
                       jit: bool):
    """Lower a pp-placed plan onto the GPipe fill-drain schedule.

    The solver only offers pp placements on :func:`~repro.core.
    selection.pp_chain` nets — a linear, shape-preserving chain — and
    its infinite backward-hop edge costs guarantee stages are monotone
    along the chain.  Each mesh stage therefore owns one contiguous run
    of nodes; this builder turns each run into a branch of a
    ``lax.switch`` on ``axis_index("stage")`` and streams
    ``pp_microbatches(batch, S)`` microbatches through
    :func:`~repro.runtime.pipeline_parallel.pipeline_apply`.

    Stage boundaries are wired in logical CHW: the legalizer recorded
    each cross-stage edge's conversion chain *through* CHW, so the
    producing branch applies the ``l_out -> CHW`` prefix and the
    consuming branch the ``CHW -> l_in`` suffix — the carry that
    ``ppermute`` rotates between stages is always the CHW activation
    the edge cost priced.
    """
    from ..runtime.pipeline_parallel import pipeline_apply

    mesh_shape = mesh_shape_dict(mesh)
    s = int(mesh_shape["stage"])
    n_micro = pp_microbatches(batch, s)
    mb = batch // n_micro
    order = net.order
    stage_of = {nid: Placement.parse(sel.choices[nid].placement).stage
                for nid in order}

    def _convert(v, hops):
        for a, b in zip(hops, hops[1:]):
            v = jax.vmap(lambda t, a=a, b=b: convert_layout(t, a, b))(v)
        return v

    def make_branch(s_idx):
        """One stage's program: (params dict, (mb, C, H, W) CHW carry)
        -> (mb, C, H, W) CHW carry.  Stages that own no nodes (more
        stages than layers) are identity relays."""
        def br(p, v):
            for pos, nid in enumerate(order):
                if stage_of[nid] != s_idx:
                    continue
                node = net.nodes[nid]
                ch = sel.choices[nid]
                if node.kind != "input":
                    prev = order[pos - 1]
                    chain = sel.conversions.get((prev, nid))
                    if chain:
                        hops = chain
                        if stage_of[prev] != s_idx:
                            # cross-stage edge: the wire arrived in
                            # CHW; apply only the CHW -> l_in suffix
                            hops = chain[chain.index("CHW"):]
                        v = _convert(v, hops)
                    if node.kind == "conv":
                        v = jax.vmap(makers[nid], in_axes=(0, None))(
                            v, p[nid])
                    else:
                        layout = LAYOUT_BY_NAME[ch.l_in]
                        q = p.get(nid)
                        v = jax.vmap(
                            lambda t, op=node.op, lay=layout, q=q:
                            op.fn([t], lay, q))(v)
                # exit wire: if the chain leaves this stage after nid,
                # park the carry in CHW for the boundary transfer
                nxt = order[pos + 1] if pos + 1 < len(order) else None
                if nxt is None or stage_of[nxt] != s_idx:
                    nchain = (sel.conversions.get((nid, nxt))
                              if nxt is not None else None)
                    if nchain:
                        v = _convert(
                            v, nchain[:nchain.index("CHW") + 1])
                    elif ch.l_out != "CHW":
                        v = jax.vmap(
                            lambda t, lo=ch.l_out:
                            convert_layout(t, lo, "CHW"))(v)
            return v
        return br

    branches = [make_branch(i) for i in range(s)]
    out_nid = net.outputs()[0]
    c, h, w = net.nodes[order[0]].out_shape

    def run(x, params):
        xm = x.reshape(n_micro, mb, c, h, w)
        # pipeline_apply shards stage_params' leading axis over the
        # stage axis; per-stage params are heterogeneous pytrees, so
        # ship the whole dict to every stage (leading axis = S copies)
        # and let each branch pick out its own nodes' entries
        sp = jax.tree.map(
            lambda a: jnp.stack([a] * s), params)

        def stage_fn(p, xmi):
            return jax.lax.switch(
                jax.lax.axis_index("stage"),
                [lambda t, b=b, p=p: b(p, t) for b in branches], xmi)

        y = pipeline_apply(mesh, stage_fn, sp, xm, n_micro=n_micro)
        return {out_nid: y.reshape(batch, c, h, w)}

    return jax.jit(run) if jit else run


def measure(cnet: CompiledNet, x_chw: np.ndarray, *, reps: int = 5,
            warmup: int = 1) -> Dict[str, float]:
    """Wall-time one forward pass (the paper's whole-network benchmark:
    mean of ``reps`` iterations after warmup)."""
    x = jnp.asarray(x_chw)
    for _ in range(warmup):
        jax.block_until_ready(cnet.fn(x, cnet.params))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(cnet.fn(x, cnet.params))
        times.append(time.perf_counter() - t0)
    return {"mean_s": float(np.mean(times)),
            "min_s": float(np.min(times)),
            "std_s": float(np.std(times))}
