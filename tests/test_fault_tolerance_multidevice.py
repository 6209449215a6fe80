"""Fault-tolerance paths that need real (placeholder) multi-device meshes.

Run in subprocesses so the main pytest process keeps its 1-device view
(dryrun.py device-count contract).

1. Elastic re-mesh: checkpoint written under mesh A (8 devices) restores
   onto mesh B (4 devices, different sharding) bit-exact — the node-failure
   recovery path of runtime/fault_tolerance.py.
2. int8 error-feedback gradient reduction across a `pod` axis inside
   shard_map — the cross-pod DCN compression (optim/compression.py),
   verified unbiased against the exact f32 psum.
"""
import json
import os
import subprocess
import sys
import textwrap
import pytest

pytestmark = pytest.mark.slow  # ~100s: subprocess multi-device trainings

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, cwd=_ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_elastic_remesh_restore(tmp_path):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import Checkpointer
        from repro.runtime import replan_mesh, rescale_grad_accum

        # "Before failure": 8 devices, (4, 2) mesh, params FSDP+TP sharded.
        from repro.launch.mesh import make_host_mesh
        mesh_a = make_host_mesh(8, model=2)
        w = jnp.arange(64.0 * 32).reshape(64, 32)
        sh_a = NamedSharding(mesh_a, P("data", "model"))
        tree = {{"w": jax.device_put(w, sh_a),
                 "step": jnp.asarray(7, jnp.int32)}}
        ck = Checkpointer(r"{tmp_path}")
        ck.save(7, tree, blocking=True)

        # "After failure": 4 survivors -> replan mesh, restore resharded.
        mesh_b = replan_mesh(4, prefer_model=2)
        assert mesh_b.devices.size == 4
        sh_b = {{"w": NamedSharding(mesh_b, P("data", "model")),
                 "step": NamedSharding(mesh_b, P())}}
        out, step, _ = ck.restore(tree, shardings=sh_b)
        assert step == 7
        assert out["w"].sharding == sh_b["w"]
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(w))
        assert rescale_grad_accum(2, old_data=4, new_data=2) == 4
        print(json.dumps({{"ok": True}}))
    """)
    assert '"ok": true' in _run(code)


def test_int8_crosspod_gradient_reduction():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json, functools
        import numpy as np, jax, jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.optim import compression

        mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
        rng = np.random.default_rng(0)
        # per-pod gradients (leading axis = pod shard)
        g_all = jnp.asarray(rng.normal(size=(4, 256)) * 1e-3, jnp.float32)

        def body(g, e):
            grads = {"w": g[0]}
            err = {"w": e[0]}
            reduced, new_err = compression.cross_pod_psum_int8(
                grads, err, axis_name="pod")
            return reduced["w"][None], new_err["w"][None]

        fn = shard_map(body, mesh=mesh,
                       in_specs=(P("pod", None), P("pod", None)),
                       out_specs=(P("pod", None), P("pod", None)))
        err0 = jnp.zeros((4, 256), jnp.bfloat16)

        exact = np.asarray(g_all).sum(0)
        # error feedback: averaged over repeats, quantized reduction -> exact
        total = np.zeros(256)
        err = err0
        for _ in range(30):
            red, err = fn(g_all, err)
            total += np.asarray(red[0])
        np.testing.assert_allclose(total / 30, exact, rtol=0.05, atol=2e-5)
        print(json.dumps({"ok": True}))
    """)
    assert '"ok": true' in _run(code)


def test_preemption_checkpoint_loss_bounded(tmp_path):
    """Preempt mid-training (simulated), resume: at most one step lost."""
    code = textwrap.dedent(f"""
        import json
        from repro.launch import train
        ck = r"{tmp_path}/ck"
        losses = train.main(["--arch", "qwen2-0.5b", "--reduced", "--steps",
                             "6", "--batch", "2", "--seq", "32",
                             "--ckpt-dir", ck, "--ckpt-every", "2",
                             "--log-every", "100"])
        # simulate crash: just restart with --resume for more steps
        more = train.main(["--arch", "qwen2-0.5b", "--reduced", "--steps",
                           "8", "--batch", "2", "--seq", "32",
                           "--ckpt-dir", ck, "--resume",
                           "--log-every", "100"])
        assert len(more) == 2, f"resume should run exactly steps 6..7: {{len(more)}}"
        print(json.dumps({{"ok": True}}))
    """)
    assert '"ok": true' in _run(code)
