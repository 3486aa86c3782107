"""Launch-layer integration: mesh construction + SPMD lowering on forced
host devices (subprocess: the device-count flag must precede jax init)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_mini_mesh_sync_lowering_compiles():
    out = run_py("""
        import jax, jax.numpy as jnp, json
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro import configs
        from repro.launch import specs as S
        from repro.launch.mesh import auto_mesh
        from repro.roofline import hlo
        from repro.train import trainer
        from repro.optim.sgd import sgd

        cfg = configs.reduced(configs.get('minitron-4b'), seq_shard=True)
        mesh = auto_mesh((4, 2), ('data', 'model'))
        p_shapes, p_specs = S.param_shapes_and_specs(cfg)
        b_shapes, b_specs = S.batch_specs(cfg, 'train', 16, 8)
        opt = sgd(1e-2)
        o_shapes = jax.eval_shape(opt.init, p_shapes)
        o_specs = trainer.opt_state_specs(o_shapes, p_specs)
        step = trainer.make_sync_step(cfg, mesh, opt, p_specs)
        sh = lambda s: trainer.resolve_tree(s, mesh, cfg)
        with mesh:
            lowered = jax.jit(step,
                in_shardings=(sh(p_specs), sh(o_specs), sh(b_specs)),
                out_shardings=(sh(p_specs), sh(o_specs),
                               NamedSharding(mesh, P()))).lower(
                p_shapes, o_shapes, b_shapes)
            compiled = lowered.compile()
        ca = hlo.cost_analysis_dict(compiled)
        print(json.dumps({'flops': ca.get('flops', -1),
                          'ok': True}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["ok"] and res["flops"] > 0


def test_mini_mesh_decode_lowering_compiles():
    out = run_py("""
        import jax, jax.numpy as jnp, json
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro import configs
        from repro.launch import specs as S
        from repro.launch.mesh import auto_mesh
        from repro.train import trainer

        cfg = configs.reduced(configs.get('zamba2-1.2b'))
        mesh = auto_mesh((2, 2, 2), ('pod', 'data', 'model'))
        p_shapes, p_specs = S.param_shapes_and_specs(cfg)
        c_shapes, c_specs = S.cache_shapes_and_specs(cfg, 8, 32)
        b_shapes, b_specs = S.batch_specs(cfg, 'decode', 32, 8)
        step = trainer.make_decode_step(cfg, mesh)
        sh = lambda s: trainer.resolve_tree(s, mesh, cfg)
        with mesh:
            compiled = jax.jit(step,
                in_shardings=(sh(p_specs), sh(c_specs), sh(b_specs),
                              NamedSharding(mesh, P())),
                out_shardings=(NamedSharding(mesh, P()), sh(c_specs))
                ).lower(p_shapes, c_shapes, b_shapes,
                        jax.ShapeDtypeStruct((), jnp.int32)).compile()
        print(json.dumps({'ok': True,
                          'mem': compiled.memory_analysis() is not None}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["ok"]


def test_production_mesh_shapes():
    out = run_py("""
        import jax, json
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        print(json.dumps({'single': dict(m1.shape), 'multi': dict(m2.shape)}))
    """, devices=512)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["single"] == {"data": 16, "model": 16}
    assert res["multi"] == {"pod": 2, "data": 16, "model": 16}
