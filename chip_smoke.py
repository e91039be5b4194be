#!/usr/bin/env python3
"""Run the main serving path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: vision, Pallas and LM phases
    python chip_smoke.py --chips 4   # the mesh phase alone, on a 2x2 host

One chip:

* vision -- ``PlanServer`` serves GoogLeNet at 3x224x224 (exact buckets,
  no padding), priced by the analytic model of this chip.  A few single
  images, then one batch of 8, each compared with the same net under
  the solver-free reference plan run at the highest matmul precision.
  Every ladder, quarantine and kernel-failure counter must read 0.
* pallas -- every (Pallas primitive, K, stride) the registry offers
  among GoogLeNet's layers, run once and compared with the numpy
  reference convolution.
* lm -- ``ServeLoop`` serves TinyLlama-1.1B at its published widths
  (float32 weights, random from a seed): 4 short requests, every second
  one with an image encoded through the vision server.  One request's
  prefill logits are compared with a jitted full forward pass.

Four chips: ``PlanServer`` on a ``data=2, model=2`` mesh serves a batch
of 8 and is compared with the single-device batched plan; the same
selection with its last inception block placed tensor-parallel runs
through the mesh lowering and is compared too.

Needs a TPU: on any other platform it exits non-zero before doing
anything.  The last line printed is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# Tolerances on max|got - ref| / max|ref| against float32 references run
# at the highest matmul precision.  The served path runs at the default
# precision, where the MXU rounds float32 operands to bfloat16 (8-bit
# mantissa, relative step 2**-8): one layer then lands within ~1e-2 of
# the reference, and the error grows with depth.
KERNEL_TOL = 2e-2   # one conv layer
NET_TOL = 5e-2      # GoogLeNet's softmax, 22 layers deep
#: TinyLlama's prefill against its jitted forward, both at the highest
#: precision: the same float32 math summed in another order.  (At the
#: served precision 22 random-weight blocks amplify the bfloat16
#: rounding to ~0.2 of max|logit| on a v5e, so that gap is reported,
#: not checked.)
LM_TOL = 1e-3
#: the dp+tp executable against the solver's mesh executable: the same
#: primitives on differently partitioned data (the mesh plan against
#: the single-device plan may pick other primitives: NET_TOL)
MESH_TOL = 1e-3

#: counters of the serve path's fallbacks: any non-zero one means the
#: chip path was routed around, so the smoke fails
ZERO_COUNTERS = ("compile_fallbacks", "compile_retries", "ladder_greedy",
                 "ladder_reference", "ladder_anytime", "kernel_failures",
                 "quarantines")


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


class Checks:
    """Collects pass/fail lines; any failure fails the run."""

    def __init__(self):
        self.failed = []

    def expect(self, ok: bool, what: str) -> None:
        log(f"  [{'ok' if ok else 'FAIL'}] {what}")
        if not ok:
            self.failed.append(what)

    def close(self, got, ref, tol: float, what: str) -> None:
        finite = bool(np.isfinite(np.asarray(got)).all())
        err = rel_err(got, ref) if finite else float("inf")
        self.expect(finite and err <= tol,
                    f"{what}: max|d|/max|ref| = {err:.3e} (tol {tol:g})")


def exact_policy():
    from repro.serving import BucketPolicy
    return BucketPolicy(spatial="exact", channel="exact", batch="exact")


def print_plan(sel, title: str) -> None:
    log(f"  plan {title}: strategy={sel.strategy} "
        f"predicted={sel.predicted_cost * 1e3:.3f} ms")
    for nid in sel.net.order:
        ch = sel.choices[nid]
        if ch.primitive is not None:
            log(f"    {nid:14s} {ch.primitive.name:26s} "
                f"{ch.l_in}->{ch.l_out} {ch.placement}")


def check_counters(checks: Checks, stats, title: str) -> None:
    vals = {k: stats[k] for k in ZERO_COUNTERS}
    log(f"  counters {title}: " + " ".join(
        f"{k}={stats[k]}" for k in ("requests", "solves", "compiles",
                                    "batch_calls")) +
        f" solve={stats['solve_s']:.3f}s compile={stats['compile_s']:.3f}s"
        f" execute={stats['execute_s']:.3f}s")
    checks.expect(not any(vals.values()),
                  "fallback counters all 0: " +
                  " ".join(f"{k}={v}" for k, v in vals.items()))


def reference_outputs(net, images):
    """Outputs of ``net`` under the solver-free reference plan (textbook
    sum2d in CHW everywhere) at the highest matmul precision."""
    import jax

    from repro.core.plan import compile_plan
    from repro.reliability import reference_selection
    with jax.default_matmul_precision("highest"):
        ref = compile_plan(reference_selection(net), net.init_params(0))
        t0 = time.perf_counter()
        outs = [jax.device_get(ref(x)) for x in images[:1]]
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        outs += [jax.device_get(ref(x)) for x in images[1:]]
    log(f"  reference: first call (compile+run) {t_first:.2f}s, "
        f"{len(images) - 1} more in {time.perf_counter() - t0:.2f}s")
    return outs


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------
def vision_phase(checks: Checks, build_net, n_single: int = 3,
                 n_batch: int = 8, seed: int = 0):
    """Serve singles and one batch; returns the (open) server."""
    from repro.core.costs import AnalyticCostModel
    from repro.serving import PlanServer

    net = build_net()
    shape = tuple(net.nodes["data"].out_shape)
    cost = AnalyticCostModel()
    log(f"  net {net.name} input {shape}, cost model {cost.spec.name} "
        f"(tpu-only primitives priced: {cost.include_tpu_only})")
    server = PlanServer(lambda s: build_net(), cost, policy=exact_policy())
    rng = np.random.default_rng(seed)
    images = rng.normal(size=(n_single + n_batch, *shape)).astype(np.float32)

    singles = []
    for i, x in enumerate(images[:n_single]):
        t0 = time.perf_counter()
        singles.append(server.infer(x))
        log(f"  infer #{i}: {time.perf_counter() - t0:.3f}s"
            + (" (solve+compile+run)" if i == 0 else ""))
    t0 = time.perf_counter()
    server.infer_batch(list(images[n_single:]))
    log(f"  infer_batch({n_batch}) first call (solve+compile+run): "
        f"{time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    batch = server.infer_batch(list(images[n_single:]))
    log(f"  infer_batch({n_batch}) warm: {time.perf_counter() - t0:.3f}s")
    print_plan(server.plan_for(shape, 1), "n=1")
    print_plan(server.plan_for(shape, n_batch), f"n={n_batch}")

    refs = reference_outputs(net, images)
    out_id = net.outputs()[0]
    for i, (got, ref) in enumerate(zip(singles + batch, refs)):
        kind = "single" if i < n_single else "batch"
        checks.close(got[out_id], ref[out_id], NET_TOL,
                     f"{kind} image {i} vs reference")
    check_counters(checks, server.stats(), "vision")
    return server


def pallas_phase(checks: Checks, net, seed: int = 0) -> None:
    """Each offered (Pallas primitive, K, stride) once, vs ``ref_conv``."""
    import jax
    import jax.numpy as jnp

    from repro.core.layouts import LAYOUT_BY_NAME
    from repro.core.primitives import convert_layout, registry
    from repro.core.scenario import ref_conv

    cases = {}
    for node in net.conv_nodes():
        for p in registry():
            if p.family == "pallas" and p.supports(node.scn):
                cases.setdefault((p.name, node.scn.k, node.scn.stride),
                                 (p, node))
    checks.expect(bool(cases), f"{len(cases)} (primitive, K, stride) "
                               f"cases offered in {net.name}")
    rng = np.random.default_rng(seed)
    for (name, k, stride), (prim, node) in sorted(cases.items()):
        scn = node.scn
        x = rng.normal(size=scn.in_shape_chw).astype(np.float32)
        w = rng.normal(0, np.sqrt(2.0 / (scn.c * k * k)),
                       size=scn.weight_shape).astype(np.float32)
        b = rng.normal(0, 0.01, size=(scn.m,)).astype(np.float32)
        packed = prim.prepare(scn, w, b)
        x_mem = jnp.asarray(LAYOUT_BY_NAME[prim.l_in].to_memory(x))
        fn = jax.jit(prim.make(scn))
        lowered = fn.lower(x_mem, packed).as_text()
        t0 = time.perf_counter()
        y = jax.block_until_ready(fn(x_mem, packed))
        dt = time.perf_counter() - t0
        y = np.asarray(convert_layout(y, prim.l_out, "CHW"))
        checks.expect("tpu_custom_call" in lowered,
                      f"{name} K={k} s={stride} at {node.id} "
                      f"({scn.key()}) runs as a Mosaic kernel, "
                      f"compile+run {dt:.2f}s")
        checks.close(y, ref_conv(x, w, b, scn.stride, scn.pad),
                     KERNEL_TOL, f"{name} K={k} s={stride} vs ref_conv")


def lm_phase(checks: Checks, cfg, server, image_shape, n_requests: int = 4,
             prompt_len: int = 12, max_new: int = 4, max_seq: int = 64,
             seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import ShardingPlan, forward_train, init_params, \
        prefill
    from repro.runtime import Request, ServeLoop

    t0 = time.perf_counter()
    params = init_params(cfg, jax.random.key(seed), jnp.float32)
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    log(f"  {cfg.name}: {n_params / 1e9:.3f}B float32 params "
        f"(d_model={cfg.d_model}, layers={cfg.n_layers}, "
        f"vocab={cfg.vocab}) initialised in "
        f"{time.perf_counter() - t0:.2f}s")
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, size=prompt_len)
                    .astype(np.int32),
                    max_new_tokens=max_new,
                    pixels=(rng.normal(size=image_shape).astype(np.float32)
                            if i % 2 == 0 else None))
            for i in range(n_requests)]
    vision_before = server.stats()["requests"]
    loop = ServeLoop(cfg, params, max_batch=2, max_seq=max_seq,
                     plan_server=server, image_tokens=4)
    try:
        t0 = time.perf_counter()
        loop.run(reqs)
        dt = time.perf_counter() - t0
    finally:
        loop.close()
    tokens = sum(len(r.tokens) for r in reqs)
    log(f"  served {len(reqs)} requests, {tokens} tokens in {dt:.2f}s "
        f"(compiles included)")
    for r in reqs:
        log(f"    req {r.rid}: prompt[{len(r.prompt)}] -> {r.tokens} "
            f"({r.latency_s:.2f}s)")
    checks.expect(all(len(r.tokens) == max_new and
                      all(0 <= t < cfg.vocab for t in r.tokens)
                      for r in reqs),
                  f"every request got {max_new} in-vocab tokens")
    n_img = sum(1 for i in range(n_requests) if i % 2 == 0)
    checks.expect(server.stats()["requests"] - vision_before == n_img,
                  f"{n_img} images went through the vision server")

    req = reqs[0]  # carries the image prefix
    toks = jnp.asarray(req.prompt[None])
    plan = ShardingPlan(mesh=None)
    forward = jax.jit(lambda p, t: forward_train(cfg, p, {"tokens": t},
                                                 plan))

    def prefill_logits():
        logits, _ = prefill(cfg, params, {"tokens": toks}, plan,
                            max_seq=max_seq)
        return np.asarray(logits)[0, -1]

    served = prefill_logits()
    checks.expect(int(np.argmax(served)) == req.tokens[0],
                  "req 0's first served token is its prefill argmax")
    with jax.default_matmul_precision("highest"):
        exact = prefill_logits()
        ref = np.asarray(forward(params, toks))[0, -1]
    checks.close(exact, ref, LM_TOL,
                 f"req 0 prefill logits ({len(req.prompt)} tokens) vs "
                 f"jitted forward, both at highest precision")
    log(f"  served precision vs highest: max|d|/max|ref| = "
        f"{rel_err(served, ref):.3e} (reported, not checked)")
    check_counters(checks, server.stats(), "after lm")


def mesh_phase(checks: Checks, build_net, n_batch: int = 8,
               seed: int = 0) -> None:
    import dataclasses

    import jax

    from repro.core.costs import AnalyticCostModel
    from repro.core.plan import compile_plan
    from repro.launch.mesh import make_mesh_compat
    from repro.serving import PlanServer

    mesh = make_mesh_compat((2, 2), ("data", "model"))
    log(f"  mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} over "
        f"devices {[d.id for d in mesh.devices.flat]}")
    net = build_net()
    shape = tuple(net.nodes["data"].out_shape)
    cost = AnalyticCostModel()
    rng = np.random.default_rng(seed)
    images = list(rng.normal(size=(n_batch, *shape)).astype(np.float32))
    xb = np.stack(images)
    out_id = net.outputs()[0]

    one = PlanServer(lambda s: build_net(), cost, policy=exact_policy())
    meshed = PlanServer(lambda s: build_net(), cost, policy=exact_policy(),
                        mesh=mesh)
    try:
        t0 = time.perf_counter()
        want = one.infer_batch(images)
        log(f"  single-device batch {n_batch}: {time.perf_counter() - t0:.2f}s"
            f" (solve+compile+run)")
        t0 = time.perf_counter()
        got = meshed.infer_batch(images)
        log(f"  mesh batch {n_batch}: {time.perf_counter() - t0:.2f}s "
            f"(solve+compile+run)")
        cnet = meshed.compiled_for(shape, n_batch)
        sel = cnet.sel
        print_plan(sel, f"mesh n={n_batch}")
        log(f"  mesh executable: mode={cnet.mesh_mode} dp={cnet.dp_nodes} "
            f"tp={cnet.tp_nodes}")
        for i, (g, w) in enumerate(zip(got, want)):
            checks.close(g[out_id], w[out_id], NET_TOL,
                         f"mesh image {i} vs single-device batched plan")
        out = cnet(xb)[out_id]
        checks.expect(len(out.sharding.device_set) == 4,
                      f"solver plan's output spans "
                      f"{len(out.sharding.device_set)} devices")
        solver_out = np.asarray(out)
        check_counters(checks, meshed.stats(), "mesh")

        # the solver's plan above; now the same selection with the last
        # inception block tensor-parallel over 'model', the rest dp
        choices = {}
        for nid, ch in sel.choices.items():
            node = net.nodes[nid]
            tp = nid.startswith("i5b_") and (
                node.kind != "conv" or ch.primitive.supports(
                    node.scn.with_(m=node.scn.m // 2, n=n_batch // 2)))
            choices[nid] = dataclasses.replace(
                ch, placement="tp" if tp else "dp")
        mixed = dataclasses.replace(sel, choices=choices)
        t0 = time.perf_counter()
        cmix = compile_plan(mixed, sel.net.init_params(0), batch=n_batch,
                            mesh=mesh)
        out = jax.block_until_ready(cmix(xb))[out_id]
        log(f"  dp+tp executable: mode={cmix.mesh_mode} "
            f"dp={cmix.dp_nodes} tp={cmix.tp_nodes}, compile+run "
            f"{time.perf_counter() - t0:.2f}s")
        checks.expect(cmix.dp_nodes > 0 and cmix.tp_nodes > 0,
                      "plan has dp and tp nodes")
        checks.expect(len(out.sharding.device_set) == 4,
                      f"dp+tp output spans {len(out.sharding.device_set)} "
                      f"devices")
        out = np.asarray(out)
        for i, w in enumerate(want):
            checks.close(out[i], w[out_id], NET_TOL,
                         f"dp+tp image {i} vs single-device batched plan")
        checks.close(out, solver_out, MESH_TOL,
                     "dp+tp batch vs the solver's mesh plan")
    finally:
        one.close()
        meshed.close()


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: vision, Pallas and LM phases on one chip; "
                         "4: the mesh phase on a 2x2 host")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    from repro.convnets import googlenet
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")

    checks = Checks()
    build = lambda: googlenet(1.0)  # noqa: E731
    served = {}  # the vision phase's server, which the LM phase reuses
    phases = []
    t_all = time.perf_counter()
    if args.chips == 4:
        phases.append(("mesh", lambda: mesh_phase(checks, build)))
    else:
        from repro.configs import get_config

        def vision():
            served["server"] = vision_phase(checks, build)

        def lm():
            shape = tuple(build().nodes["data"].out_shape)
            lm_phase(checks, get_config("tinyllama-1.1b"), served["server"],
                     shape)

        phases += [("vision", vision),
                   ("pallas", lambda: pallas_phase(checks, build())),
                   ("lm", lm)]
    try:
        for name, run in phases:
            log(f"== phase {name}")
            t0 = time.perf_counter()
            run()
            log(f"== phase {name}: {time.perf_counter() - t0:.2f}s wall")
    finally:
        if "server" in served:
            served["server"].close()
    log(f"== all phases: {time.perf_counter() - t_all:.2f}s wall")
    if checks.failed:
        log(f"FAILED {len(checks.failed)} checks:")
        for what in checks.failed:
            log(f"  {what}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
