"""Multi-device tests: run in subprocesses with fake CPU devices so the
main pytest process keeps a single device (per the dry-run contract —
XLA_FLAGS must not leak globally)."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
    return proc.stdout


class TestShardedModel:
    def test_model_lowers_and_runs_on_4x2_mesh(self):
        out = run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_config
            from repro.models import (init_params, loss_fn, ShardingPlan,
                                      MEGATRON_RULES, ModelRuntime)
            cfg = get_config('tinyllama-1.1b').scaled_down(
                n_layers=2, d_model=64, d_ff=128, vocab=512,
                n_heads=4, n_kv_heads=2, head_dim=16)
            from repro.launch.mesh import make_mesh_compat
            mesh = make_mesh_compat((4, 2), ('data', 'model'))
            rules = MEGATRON_RULES.restrict(mesh.axis_names)
            plan = ShardingPlan(mesh=mesh, rules=rules)
            params = init_params(cfg, jax.random.key(0), jnp.float32)
            rng = np.random.default_rng(0)
            batch = {'tokens': jnp.asarray(rng.integers(0, 512, (8, 16)),
                                           jnp.int32),
                     'labels': jnp.asarray(rng.integers(0, 512, (8, 16)),
                                           jnp.int32)}
            with mesh:
                loss = jax.jit(lambda p, b: loss_fn(cfg, p, b, plan,
                                                    ModelRuntime()))(
                    params, batch)
            assert jnp.isfinite(loss), loss
            # single-device reference must match the sharded result
            plan0 = ShardingPlan(mesh=None)
            loss0 = loss_fn(cfg, params, batch, plan0, ModelRuntime())
            assert abs(float(loss) - float(loss0)) < 1e-3, (loss, loss0)
            print('OK', float(loss))
        """)
        assert "OK" in out

    def test_sharded_matches_unsharded_moe(self):
        out = run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_config
            from repro.models import (init_params, forward_train,
                                      ShardingPlan, MEGATRON_RULES,
                                      ModelRuntime)
            cfg = get_config('grok-1-314b').scaled_down(
                n_layers=2, d_model=64, d_ff=128, vocab=512,
                n_heads=4, n_kv_heads=2, head_dim=16, n_experts=4,
                top_k=2)
            from repro.launch.mesh import make_mesh_compat
            mesh = make_mesh_compat((2, 4), ('data', 'model'))
            plan = ShardingPlan(mesh=mesh,
                                rules=MEGATRON_RULES.restrict(
                                    mesh.axis_names))
            params = init_params(cfg, jax.random.key(1), jnp.float32)
            rng = np.random.default_rng(0)
            batch = {'tokens': jnp.asarray(rng.integers(0, 512, (4, 16)),
                                           jnp.int32)}
            with mesh:
                lg = jax.jit(lambda p, b: forward_train(
                    cfg, p, b, plan, ModelRuntime()))(params, batch)
            lg0 = forward_train(cfg, params, batch, ShardingPlan(None),
                                ModelRuntime())
            err = float(jnp.max(jnp.abs(lg - lg0)))
            assert err < 2e-2, err
            print('OK', err)
        """)
        assert "OK" in out


class TestPipelineParallel:
    def test_pipeline_matches_sequential(self):
        out = run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.runtime import pipeline_apply
            S, n_micro, mb, d = 4, 8, 2, 16
            from repro.launch.mesh import make_mesh_compat
            mesh = make_mesh_compat((S,), ('stage',))
            rng = np.random.default_rng(0)
            w = jnp.asarray(rng.normal(size=(S, d, d)) * 0.3, jnp.float32)
            x = jnp.asarray(rng.normal(size=(n_micro, mb, d)), jnp.float32)
            def stage_fn(params, xm):
                return jnp.tanh(xm @ params['w'])
            y = pipeline_apply(mesh, stage_fn, {'w': w}, x,
                               n_micro=n_micro, axis='stage')
            # sequential reference
            ref = x
            for s in range(S):
                ref = jnp.tanh(ref @ w[s])
            err = float(jnp.max(jnp.abs(y - ref)))
            assert err < 1e-5, err
            print('OK', err)
        """)
        assert "OK" in out


    def test_pipeline_ticks_formula(self):
        """Fill-drain schedule length: T = n_micro + S - 1."""
        from repro.runtime.pipeline_parallel import pipeline_ticks
        assert pipeline_ticks(1, 1) == 1
        assert pipeline_ticks(4, 8) == 11
        assert pipeline_ticks(2, 1) == 2
        with pytest.raises(ValueError):
            pipeline_ticks(0, 4)
        with pytest.raises(ValueError):
            pipeline_ticks(4, 0)

    def test_degenerate_single_stage(self):
        """S=1: the pipeline IS the stage function (one tick per
        microbatch, no boundary transfers)."""
        out = run_with_devices("""
            import jax.numpy as jnp, numpy as np
            from repro.runtime import pipeline_apply
            from repro.launch.mesh import make_mesh_compat
            mesh = make_mesh_compat((1,), ('stage',))
            rng = np.random.default_rng(0)
            w = jnp.asarray(rng.normal(size=(1, 8, 8)) * 0.3, jnp.float32)
            x = jnp.asarray(rng.normal(size=(4, 2, 8)), jnp.float32)
            y = pipeline_apply(mesh, lambda p, xm: jnp.tanh(xm @ p['w']),
                               {'w': w}, x, n_micro=4)
            ref = jnp.tanh(x @ w[0])
            err = float(jnp.max(jnp.abs(y - ref)))
            assert err < 1e-6, err
            print('OK', err)
        """, n_devices=1)
        assert "OK" in out

    def test_degenerate_single_microbatch(self):
        """n_micro=1: pure fill-drain bubble (T = S ticks), still
        correct."""
        out = run_with_devices("""
            import jax.numpy as jnp, numpy as np
            from repro.runtime import pipeline_apply
            from repro.launch.mesh import make_mesh_compat
            S = 4
            mesh = make_mesh_compat((S,), ('stage',))
            rng = np.random.default_rng(1)
            w = jnp.asarray(rng.normal(size=(S, 8, 8)) * 0.3, jnp.float32)
            x = jnp.asarray(rng.normal(size=(1, 3, 8)), jnp.float32)
            y = pipeline_apply(mesh, lambda p, xm: jnp.tanh(xm @ p['w']),
                               {'w': w}, x, n_micro=1)
            ref = x
            for s in range(S):
                ref = jnp.tanh(ref @ w[s])
            err = float(jnp.max(jnp.abs(y - ref)))
            assert err < 1e-5, err
            print('OK', err)
        """, n_devices=4)
        assert "OK" in out


class TestCompression:
    def test_quantized_psum_close_to_exact(self):
        out = run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.optim import compressed_psum_tree
            from repro.launch.mesh import make_mesh_compat
            mesh = make_mesh_compat((8,), ('pod',))
            rng = np.random.default_rng(0)
            g = jnp.asarray(rng.normal(size=(8, 64, 32)), jnp.float32)
            def f(gl):
                return compressed_psum_tree({'g': gl[0]}, 'pod')['g']
            out = jax.shard_map(f, mesh=mesh, in_specs=P('pod'),
                                out_specs=P())(g)
            exact = jnp.mean(g, axis=0)
            rel = float(jnp.linalg.norm(out - exact) /
                        jnp.linalg.norm(exact))
            assert rel < 0.05, rel
            print('OK', rel)
        """)
        assert "OK" in out

    def test_quantize_roundtrip_unbiased(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.optim import dequantize_int8, quantize_int8
        x = jnp.asarray(np.random.default_rng(0).normal(size=(1000,)),
                        jnp.float32)
        deq = []
        for i in range(20):
            q, s = quantize_int8(x, jax.random.key(i))
            deq.append(np.asarray(dequantize_int8(q, s)))
        err = np.abs(np.mean(deq, axis=0) - np.asarray(x)).max()
        assert err < 0.02  # stochastic rounding averages out


class TestShardedPlan:
    """The unified choice-space pipeline: solve (placement axis) ->
    compile (mesh executables) -> serve, on an 8-fake-device CPU mesh.
    Acceptance: mesh-sharded outputs identical to the unsharded plan."""

    def test_sharded_tower_matches_unsharded(self):
        out = run_with_devices("""
            import numpy as np
            from repro.core.costs import AnalyticCostModel
            from repro.core.plan import compile_plan
            from repro.core.selection import select_pbqp
            from repro.launch.mesh import make_mesh_compat
            from repro.serving.towers import conv_stack, conv_tower

            mesh = make_mesh_compat((8,), ('data',))
            cm = AnalyticCostModel()
            rng = np.random.default_rng(0)
            modes = set()
            for builder in (conv_stack, conv_tower):
                net = builder((4, 32, 32), depth=3, width=8).with_batch(8)
                sel = select_pbqp(net, cm, mesh_axes={'data': 8})
                assert sel.optimal
                assert any(c.placement == 'dp'
                           for c in sel.choices.values()), 'no dp chosen'
                sel0 = select_pbqp(net, cm)
                assert all(c.placement == 'rep'
                           for c in sel0.choices.values())
                params = net.init_params(0)
                x = rng.normal(size=(8, 4, 32, 32)).astype(np.float32)
                cn = compile_plan(sel, params, batch=8, mesh=mesh)
                cn0 = compile_plan(sel0, params, batch=8)
                modes.add(cn.mesh_mode)
                out, out0 = cn(x), cn0(x)
                assert set(out) == set(out0)
                for k in out:
                    np.testing.assert_allclose(
                        np.asarray(out[k]), np.asarray(out0[k]),
                        rtol=2e-3, atol=2e-3)
            # both executable modes exercised: the all-dp shard_map
            # fast path and the mixed-placement GSPMD path
            assert modes == {'shard_map', 'gspmd'}, modes
            print('OK', sorted(modes))
        """)
        assert "OK" in out

    def test_mesh_plan_server_matches_plain(self):
        out = run_with_devices("""
            import numpy as np
            from repro.core.costs import AnalyticCostModel
            from repro.launch.mesh import make_mesh_compat
            from repro.serving import BucketPolicy, PlanServer, conv_stack

            mesh = make_mesh_compat((8,), ('data',))
            policy = BucketPolicy(min_hw=8, max_hw=64)
            build = lambda s: conv_stack(s, depth=2, width=8)
            rng = np.random.default_rng(0)
            stream = [rng.normal(size=(
                4, int(rng.integers(12, 17)), int(rng.integers(12, 17))
                )).astype(np.float32) for _ in range(16)]
            srv_m = PlanServer(build, AnalyticCostModel(), policy=policy,
                               mesh=mesh)
            srv_0 = PlanServer(build, AnalyticCostModel(), policy=policy)
            # the mesh topology is part of every cache key
            assert srv_m.cost_version != srv_0.cost_version
            out_m = srv_m.infer_batch(stream)
            out_0 = srv_0.infer_batch(stream)
            for i in range(len(stream)):
                assert set(out_m[i]) == set(out_0[i])
                for k in out_m[i]:
                    assert out_m[i][k].shape == out_0[i][k].shape
                    np.testing.assert_allclose(out_m[i][k], out_0[i][k],
                                               rtol=2e-3, atol=2e-3)
            s = srv_m.stats()
            assert s['mesh_compiles'] >= 1, s
            # single-image latency path stays mesh-free but must agree
            one_m = srv_m.infer(stream[0])
            one_0 = srv_0.infer(stream[0])
            for k in one_m:
                np.testing.assert_allclose(one_m[k], one_0[k],
                                           rtol=2e-3, atol=2e-3)
            srv_m.close(); srv_0.close()
            print('OK', int(s['mesh_compiles']))
        """)
        assert "OK" in out

    def test_mesh_plan_roundtrips_through_disk_cache(self):
        out = run_with_devices("""
            import numpy as np, tempfile
            from repro.core.costs import AnalyticCostModel
            from repro.launch.mesh import make_mesh_compat
            from repro.serving import BucketPolicy, PlanServer, conv_stack

            mesh = make_mesh_compat((8,), ('data',))
            policy = BucketPolicy(min_hw=8, max_hw=64)
            build = lambda s: conv_stack(s, depth=2, width=8)
            xs = [np.ones((4, 16, 16), np.float32)] * 8
            with tempfile.TemporaryDirectory() as d:
                srv = PlanServer(build, AnalyticCostModel(),
                                 policy=policy, mesh=mesh, cache_dir=d)
                out1 = srv.infer_batch(xs)
                assert srv.stats()['solves'] == 1
                srv.close()
                # new server, same dir: placements come back from disk
                srv2 = PlanServer(build, AnalyticCostModel(),
                                  policy=policy, mesh=mesh, cache_dir=d)
                out2 = srv2.infer_batch(xs)
                s = srv2.stats()
                assert s['solves'] == 0 and s['plan_disk_hits'] == 1, s
                assert s['mesh_compiles'] >= 1, s
                for k in out1[0]:
                    np.testing.assert_allclose(out1[0][k], out2[0][k],
                                               rtol=2e-3, atol=2e-3)
                srv2.close()
            print('OK')
        """)
        assert "OK" in out


class TestFullParallelismPlans:
    """The enlarged placement space {rep, dp, tp, pp} end to end:
    solve -> compile -> execute, verified output-identical to the
    unsharded executable (docs/distributed.md)."""

    def test_mixed_tp_dp_plan_matches_unsharded(self):
        out = run_with_devices("""
            import numpy as np
            from repro.core.costs import AnalyticCostModel
            from repro.core.plan import compile_plan
            from repro.core.selection import Placement, select_pbqp
            from repro.launch.mesh import make_mesh_compat
            from repro.serving.towers import bottleneck_tower

            mesh = make_mesh_compat((2, 4), ('data', 'model'))
            net = bottleneck_tower((4, 16, 16)).with_batch(8)
            cm = AnalyticCostModel()
            sel = select_pbqp(net, cm,
                              mesh_axes={'data': 2, 'model': 4})
            kinds = {Placement.parse(c.placement).kind
                     for c in sel.choices.values()}
            # the fat 1x1-spatial body is weight-bandwidth bound: the
            # solver must shard its weights (tp), not its batch
            assert 'tp' in kinds and 'dp' in kinds, kinds
            params = net.init_params(0)
            x = np.random.default_rng(0).normal(
                size=(8, 4, 16, 16)).astype(np.float32)
            cn = compile_plan(sel, params, batch=8, mesh=mesh)
            assert cn.mesh_mode == 'tp_shard_map', cn.mesh_mode
            assert cn.tp_nodes > 0 and cn.dp_nodes > 0
            cn0 = compile_plan(select_pbqp(net, cm), params, batch=8)
            out, out0 = cn(x), cn0(x)
            assert set(out) == set(out0)
            for k in out:
                np.testing.assert_allclose(
                    np.asarray(out[k]), np.asarray(out0[k]),
                    rtol=2e-3, atol=2e-3)
            print('OK', sorted(kinds))
        """)
        assert "OK" in out

    def test_solved_pipeline_matches_unsharded(self):
        out = run_with_devices("""
            import numpy as np
            from repro.core.costs import AnalyticCostModel
            from repro.core.plan import compile_plan
            from repro.core.selection import Placement, select_pbqp
            from repro.launch.mesh import make_mesh_compat
            from repro.serving.towers import uniform_stack

            mesh = make_mesh_compat((4,), ('stage',))
            net = uniform_stack((8, 8, 8), depth=6).with_batch(8)
            cm = AnalyticCostModel()
            sel = select_pbqp(net, cm, mesh_axes={'stage': 4})
            assert all(Placement.parse(c.placement).kind == 'pp'
                       for c in sel.choices.values())
            params = net.init_params(0)
            x = np.random.default_rng(0).normal(
                size=(8, 8, 8, 8)).astype(np.float32)
            cn = compile_plan(sel, params, batch=8, mesh=mesh)
            assert cn.mesh_mode == 'pipeline', cn.mesh_mode
            assert cn.pp_nodes == len(net.order)
            cn0 = compile_plan(select_pbqp(net, cm), params, batch=8)
            out, out0 = cn(x), cn0(x)
            for k in out:
                np.testing.assert_allclose(
                    np.asarray(out[k]), np.asarray(out0[k]),
                    rtol=2e-3, atol=2e-3)
            print('OK')
        """, n_devices=4)
        assert "OK" in out

    def test_pure_dp_flattens_over_both_batch_axes(self):
        """A pure-dp plan prices and runs identically on an (8,) and a
        (2, 4) mesh — dp shards over ALL non-stage axes."""
        out = run_with_devices("""
            import numpy as np
            from repro.core.costs import AnalyticCostModel
            from repro.core.plan import compile_plan
            from repro.core.selection import select_pbqp
            from repro.launch.mesh import make_mesh_compat
            from repro.serving.towers import conv_stack

            cm = AnalyticCostModel()
            net = conv_stack((4, 32, 32), depth=3, width=8).with_batch(8)
            sel_24 = select_pbqp(net, cm,
                                 mesh_axes={'data': 2, 'model': 4})
            sel_8 = select_pbqp(net, cm, mesh_axes={'data': 8})
            assert sel_24.predicted_cost == sel_8.predicted_cost
            assert all(c.placement == 'dp'
                       for c in sel_24.choices.values())
            mesh = make_mesh_compat((2, 4), ('data', 'model'))
            params = net.init_params(0)
            x = np.random.default_rng(0).normal(
                size=(8, 4, 32, 32)).astype(np.float32)
            cn = compile_plan(sel_24, params, batch=8, mesh=mesh)
            assert cn.mesh_mode == 'shard_map', cn.mesh_mode
            cn0 = compile_plan(select_pbqp(net, cm), params, batch=8)
            out, out0 = cn(x), cn0(x)
            for k in out:
                np.testing.assert_allclose(
                    np.asarray(out[k]), np.asarray(out0[k]),
                    rtol=2e-3, atol=2e-3)
            print('OK')
        """)
        assert "OK" in out


class TestForceHostDevices:
    """XLA_FLAGS mangling for fake-device meshes (single home:
    launch/mesh.py::force_host_devices — serve CLI and the sharding
    benchmark both route through it)."""

    def test_appends_when_absent(self, monkeypatch):
        from repro.launch.mesh import force_host_devices
        monkeypatch.setenv("XLA_FLAGS", "--some_other_flag")
        force_host_devices(8)
        assert os.environ["XLA_FLAGS"] == \
            "--some_other_flag --xla_force_host_platform_device_count=8"

    def test_replaces_smaller_keeps_larger(self, monkeypatch):
        from repro.launch.mesh import force_host_devices
        monkeypatch.setenv(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
        force_host_devices(8)  # a 4-device flag cannot carry an 8-mesh
        assert "--xla_force_host_platform_device_count=8" in \
            os.environ["XLA_FLAGS"]
        force_host_devices(2)  # but a larger pre-set count is kept
        assert "--xla_force_host_platform_device_count=8" in \
            os.environ["XLA_FLAGS"]


class TestElastic:
    def test_remesh_on_device_change(self):
        out = run_with_devices("""
            import jax
            from repro.runtime import ElasticController
            from repro.models.sharding import MEGATRON_RULES

            def make_mesh(n):
                from repro.launch.mesh import make_mesh_compat
                d = max(n // 2, 1)
                return make_mesh_compat((d, 2 if n >= 2 else 1),
                                        ('data', 'model'))

            ec = ElasticController(make_mesh, lambda shape: MEGATRON_RULES)
            mesh1, plan1, ch1 = ec.current()
            assert not ch1
            mesh2, plan2, ch2 = ec.current()
            assert not ch2 and ec.generation == 0
            print('OK', mesh1.devices.shape)
        """)
        assert "OK" in out
