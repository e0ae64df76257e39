"""Multi-device integration tests (subprocess-isolated: jax fixes its device
count at first import, and the assignment requires smoke tests to see ONE
device — so each test spawns a fresh interpreter with forced host devices).
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_param_shardings_all_archs_divisible():
    """Every arch's full-size params get valid shardings on a 4x2 mesh."""
    _run("""
        import jax
        from jax.sharding import AxisType
        from repro.configs import ARCHS
        from repro.launch import sharding as shr, specs
        from repro.models import common
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        common.set_mesh(mesh)
        for name in ARCHS:
            params, _ = specs.state_specs(name)
            sh = shr.params_shardings(params, mesh)
            # shard_shape raises if any dim is not divisible
            for (path, leaf), (_, s) in zip(
                    jax.tree_util.tree_flatten_with_path(params)[0][:9999],
                    jax.tree_util.tree_flatten_with_path(sh)[0]):
                s.shard_shape(leaf.shape)
            print(name, "ok")
    """)


def test_train_cell_compiles_on_debug_mesh():
    """End-to-end dry-run plumbing (specs -> shardings -> jit lower+compile)
    on a 2x2 mesh with a reduced arch."""
    _run("""
        import dataclasses, jax
        from jax.sharding import AxisType
        import repro.configs.registry as reg
        from repro.configs import get
        from repro.configs.registry import ShapeConfig
        from repro.launch import sharding as shr, specs
        from repro.models import common, get_model
        from repro.optim import adamw
        from repro.train.train_step import make_train_step

        cfg = dataclasses.replace(get("qwen3-8b").reduced(), name="dbg")
        reg.ARCHS["dbg"] = cfg
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        common.set_mesh(mesh)
        shape = ShapeConfig("t", 32, 4, "train")
        sp = specs.input_specs("dbg", shape)
        psh = shr.params_shardings(sp["params"], mesh)
        bsh = shr.batch_shardings(sp["batch"], mesh, "train")
        osh = shr.opt_shardings(sp["opt"], psh, mesh)
        step = make_train_step(cfg, adamw.OptConfig(), microbatches=2)
        fn = jax.jit(step, in_shardings=(psh, osh, None, bsh),
                     out_shardings=(psh, osh, None, None))
        c = fn.lower(sp["params"], sp["opt"], None, sp["batch"]).compile()
        assert c.cost_analysis() is not None
        print("compiled ok")
    """, devices=4)


def test_decode_cell_compiles_on_debug_mesh():
    _run("""
        import dataclasses, jax
        from jax.sharding import AxisType
        import repro.configs.registry as reg
        from repro.configs import get
        from repro.configs.registry import ShapeConfig
        from repro.launch import sharding as shr, specs
        from repro.models import common, get_model

        cfg = dataclasses.replace(get("granite-8b").reduced(), name="dbg")
        reg.ARCHS["dbg"] = cfg
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        common.set_mesh(mesh)
        shape = ShapeConfig("d", 64, 4, "decode")
        sp = specs.input_specs("dbg", shape)
        psh = shr.params_shardings(sp["params"], mesh)
        bsh = shr.batch_shardings(sp["batch"], mesh, "decode")
        csh = shr.cache_shardings(sp["cache"], mesh)
        model = get_model(cfg)
        fn = jax.jit(lambda p, c, b: model.decode_step(p, c, b),
                     in_shardings=(psh, csh, bsh), out_shardings=(None, csh))
        fn.lower(sp["params"], sp["cache"], sp["batch"]).compile()
        print("compiled ok")
    """, devices=4)


def test_sharded_train_numerics_match_single_device():
    """The same train step computes the same loss sharded vs unsharded."""
    _run("""
        import dataclasses, jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.configs import get
        from repro.launch import sharding as shr
        from repro.models import common
        from repro.optim import adamw
        from repro.train.train_step import make_train_step

        cfg = dataclasses.replace(get("phi3-mini-3.8b").reduced(),
                                  dtype="float32")
        from repro.models import get_model
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw.init_state(params)
        batch = {"tokens": jnp.ones((4, 16), jnp.int32),
                 "targets": jnp.ones((4, 16), jnp.int32),
                 "positions": jnp.broadcast_to(jnp.arange(16)[None], (4, 16))}
        step = make_train_step(cfg, adamw.OptConfig(), 1)
        _, _, _, m_plain = jax.jit(step)(params, opt, None, batch)

        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        common.set_mesh(mesh)
        psh = shr.params_shardings(params, mesh)
        osh = shr.opt_shardings(opt, psh, mesh)
        bsh = shr.batch_shardings(batch, mesh, "train")
        fn = jax.jit(step, in_shardings=(psh, osh, None, bsh),
                     out_shardings=(psh, osh, None, None))
        _, _, _, m_shard = fn(params, opt, None, batch)
        np.testing.assert_allclose(float(m_plain["loss"]),
                                   float(m_shard["loss"]), rtol=1e-5)
        print("losses match:", float(m_plain["loss"]))
    """, devices=4)


def test_elastic_checkpoint_restore_across_meshes():
    """Save under a (2,2) mesh, restore under (4,1) with re-sharding."""
    _run("""
        import jax, numpy as np, tempfile
        import jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.checkpoint import Checkpointer

        mesh_a = jax.make_mesh((2, 2), ("data", "model"),
                               axis_types=(AxisType.Auto,) * 2)
        sh_a = NamedSharding(mesh_a, P("data", "model"))
        x = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                           sh_a)
        d = tempfile.mkdtemp()
        ck = Checkpointer(d)
        ck.save(1, {"x": x}, blocking=True)

        mesh_b = jax.make_mesh((4, 1), ("data", "model"),
                               axis_types=(AxisType.Auto,) * 2)
        sh_b = NamedSharding(mesh_b, P("data", "model"))
        step, restored = ck.restore(
            {"x": x}, shard_fn=lambda k, a: jax.device_put(a, sh_b))
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.arange(64).reshape(8, 8))
        assert restored["x"].sharding == sh_b
        print("elastic restore ok")
    """, devices=4)


def test_multipod_mesh_axes():
    _run("""
        from repro.launch.mesh import make_production_mesh
        m = make_production_mesh(multi_pod=True)
        assert m.axis_names == ("pod", "data", "model")
        assert m.devices.shape == (2, 16, 16)
        s = make_production_mesh()
        assert s.axis_names == ("data", "model")
        print("meshes ok")
    """, devices=512)
