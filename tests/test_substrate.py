"""Substrate tests: checkpointing, data pipeline, optimizer, fault runtime."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.data import DataConfig, DataIterator
from repro.optim import adam, compression, schedule
from repro.runtime import (StragglerMonitor, replan_mesh, rescale_grad_accum)


# -- checkpoint ---------------------------------------------------------------

def _tree():
    return {"w": jnp.arange(12.0).reshape(3, 4),
            "nested": {"b": jnp.ones((5,), jnp.bfloat16)},
            "step": jnp.asarray(7, jnp.int32)}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path)
    tree = _tree()
    ck.save(3, tree, extra={"data": {"step": 3}}, blocking=True)
    out, step, extra = ck.restore(tree)
    assert step == 3 and extra["data"]["step"] == 3
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_checkpoint_atomic_and_gc(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    tree = _tree()
    for s in (1, 2, 3, 4):
        ck.save(s, tree, blocking=True)
    steps = sorted(ck.all_steps())
    assert steps == [3, 4]          # gc kept the last two
    assert ck.latest_step() == 4
    # a stale .tmp dir must not be visible as a checkpoint
    (tmp_path / "step_0000000099.tmp").mkdir()
    assert ck.latest_step() == 4


def test_checkpoint_corruption_detected(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(1, _tree(), blocking=True)
    d = next((pathlib.Path(tmp_path)).glob("step_*/leaf_00000.npy"))
    d.write_bytes(b"corrupt!" + d.read_bytes()[8:])
    with pytest.raises(IOError):
        ck.restore(_tree())


def test_checkpoint_async(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(5, _tree(), blocking=False)
    ck.wait()
    assert ck.latest_step() == 5


def test_elastic_restore_resharded(tmp_path):
    """Save, then restore with explicit (new-mesh) shardings."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    ck = Checkpointer(tmp_path)
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    ck.save(1, tree, blocking=True)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    out, _, _ = ck.restore(tree, shardings=sh)
    assert out["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))


# -- data pipeline --------------------------------------------------------------

def test_data_deterministic_and_resumable():
    cfg = DataConfig(seq_len=32, global_batch=4, vocab=1000, seed=5)
    it1 = DataIterator(cfg)
    batches = [next(it1) for _ in range(5)]
    # resume from step 3
    it2 = DataIterator(cfg)
    it2.load_state_dict({"step": 3, "seed": 5})
    b3 = next(it2)
    np.testing.assert_array_equal(np.asarray(batches[3]["tokens"]),
                                  np.asarray(b3["tokens"]))


def test_data_labels_shifted():
    cfg = DataConfig(seq_len=16, global_batch=2, vocab=100, seed=1)
    b = next(DataIterator(cfg))
    t, l = np.asarray(b["tokens"]), np.asarray(b["labels"])
    np.testing.assert_array_equal(t[:, 1:], l[:, :-1])


def test_data_multiprocess_disjoint():
    cfg = DataConfig(seq_len=16, global_batch=4, vocab=100, seed=1)
    a = DataIterator(cfg, process_index=0, process_count=2)._host_batch(0)
    b = DataIterator(cfg, process_index=1, process_count=2)._host_batch(0)
    assert not np.array_equal(a["tokens"], b["tokens"])


# -- optimizer -------------------------------------------------------------------

def test_adam_converges_quadratic():
    params = {"x": jnp.asarray([5.0, -3.0])}
    cfg = adam.AdamConfig(lr=0.2, weight_decay=0.0, moment_dtype="float32",
                          grad_clip=0.0)
    state = adam.init(params, cfg)
    for _ in range(150):
        g = jax.grad(lambda p: jnp.sum(p["x"] ** 2))(params)
        params, state, _ = adam.update(g, state, params, cfg)
    assert float(jnp.abs(params["x"]).max()) < 1e-2


def test_adam_bf16_moments_shapes():
    params = {"w": jnp.zeros((8, 8))}
    state = adam.init(params)
    assert state["m"]["w"].dtype == jnp.bfloat16
    assert adam.opt_state_axes({"w": "d_model|d_ff"})["m"]["w"] == "d_model|d_ff"


def test_grad_clip():
    params = {"x": jnp.asarray([1.0])}
    cfg = adam.AdamConfig(lr=0.0, grad_clip=1.0)
    state = adam.init(params, cfg)
    _, _, m = adam.update({"x": jnp.asarray([100.0])}, state, params, cfg)
    assert float(m["grad_norm"]) == pytest.approx(100.0)


def test_schedule_warmup_cosine():
    import numpy as np
    lr0 = float(schedule.warmup_cosine(jnp.asarray(0), warmup=10, total=100))
    lrw = float(schedule.warmup_cosine(jnp.asarray(10), warmup=10, total=100))
    lre = float(schedule.warmup_cosine(jnp.asarray(100), warmup=10, total=100))
    assert lr0 == 0.0 and lrw == pytest.approx(1.0) and lre == pytest.approx(0.1)


def test_compression_error_feedback_unbiased():
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64,)) * 1e-3,
                          jnp.float32)}
    err = compression.init_error_state(g)
    total = np.zeros(64)
    for _ in range(50):
        payload, err = compression.compress_with_feedback(g, err)
        q, s = payload["w"]
        total += np.asarray(compression.dequantize(q, s))
    # error feedback: accumulated dequantized sum ~ accumulated true sum
    np.testing.assert_allclose(total / 50, np.asarray(g["w"]), rtol=0.05,
                               atol=1e-5)


# -- fault runtime ---------------------------------------------------------------

def test_straggler_monitor_flags():
    m = StragglerMonitor(threshold=2.0)
    for i in range(20):
        m.record(i, 0.1)
    assert m.record(20, 0.5) is True
    assert m.flagged


def test_replan_mesh_and_accum():
    mesh = replan_mesh(1, prefer_model=16)
    assert mesh.devices.size == 1
    assert rescale_grad_accum(4, old_data=16, new_data=8) == 8
    assert rescale_grad_accum(1, old_data=16, new_data=16) == 1
