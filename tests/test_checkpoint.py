"""Checkpointing + fault tolerance: atomic save/restore, kill-resume,
elastic re-mesh."""

import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.runtime.fault_tolerance import Heartbeat, TrainController


def tree_eq(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def test_save_restore_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path)
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": [jnp.ones(4), {"c": jnp.zeros(2)}]}
    ck.save(10, tree, extra={"step": 10})
    restored, extra = ck.restore(tree)
    assert extra["step"] == 10
    assert tree_eq(tree, restored)


def test_latest_and_retention(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    tree = {"w": jnp.ones(3)}
    for s in (1, 2, 3, 4):
        ck.save(s, tree)
    assert ck.latest() == 4
    assert ck.steps() == [3, 4]  # older GC'd


def test_atomicity_partial_write_invisible(tmp_path):
    ck = Checkpointer(tmp_path)
    tree = {"w": jnp.ones(3)}
    ck.save(1, tree)
    # simulate a crash mid-write: stray .tmp dir must be ignored
    (tmp_path / "step_0000000002.tmp").mkdir()
    (tmp_path / "step_0000000002.tmp" / "leaf_00000.npy").write_bytes(b"garbage")
    assert ck.latest() == 1
    restored, _ = ck.restore(tree)
    assert tree_eq(tree, restored)


def test_controller_resumes(tmp_path):
    calls = {"n": 0}

    def init_state():
        calls["n"] += 1
        return {"w": jnp.zeros(2)}, {"m": jnp.zeros(2)}

    def step(params, opt, batch):
        return (
            jax.tree.map(lambda w: w + 1, params),
            opt,
            {"loss": jnp.asarray(1.0)},
        )

    c1 = TrainController(tmp_path, step, init_state, save_every=2)
    c1.run(iter([None] * 5), n_steps=5)
    assert c1.step == 5

    c2 = TrainController(tmp_path, step, init_state, save_every=2)
    assert c2.resumed and c2.step == 5
    assert float(c2.params["w"][0]) == 5.0
    c2.run(iter([None] * 3), n_steps=8)
    assert c2.step == 8


_KILL_SCRIPT = r"""
import sys, time
sys.path.insert(0, "SRC")
import jax, jax.numpy as jnp
from repro.runtime.fault_tolerance import TrainController

def init_state():
    return {"w": jnp.zeros(2)}, {"m": jnp.zeros(2)}

def step(params, opt, batch):
    time.sleep(0.05)
    return jax.tree.map(lambda w: w + 1, params), opt, {"loss": jnp.asarray(0.0)}

c = TrainController("CKPT", step, init_state, save_every=5)
print(f"START {c.step}", flush=True)
c.run(iter([None] * 1000), n_steps=1000)
"""


def test_kill_and_resume(tmp_path):
    """SIGKILL a training process mid-run; the restart must resume from the
    last committed checkpoint (the paper-scale failure model)."""
    script = _KILL_SCRIPT.replace("SRC", str(Path("src").resolve())).replace(
        "CKPT", str(tmp_path)
    )
    env = dict(os.environ)
    proc = subprocess.Popen([sys.executable, "-c", script], env=env,
                            stdout=subprocess.PIPE, text=True)
    time.sleep(12)  # let it commit a few checkpoints
    proc.kill()
    proc.wait()

    ck = Checkpointer(tmp_path)
    committed = ck.latest()
    assert committed is not None and committed >= 5

    # restart: must resume exactly at the committed step
    def init_state():
        return {"w": jnp.zeros(2)}, {"m": jnp.zeros(2)}

    def step(params, opt, batch):
        return jax.tree.map(lambda w: w + 1, params), opt, {"loss": jnp.asarray(0.0)}

    c = TrainController(tmp_path, step, init_state, save_every=5)
    assert c.resumed and c.step == committed
    assert float(c.params["w"][0]) == committed


def test_heartbeat(tmp_path):
    hb = Heartbeat(tmp_path / "hb", interval_s=0.0)
    hb.beat(3)
    assert Heartbeat.is_alive(tmp_path / "hb", timeout_s=10.0)
    assert not Heartbeat.is_alive(tmp_path / "missing", timeout_s=10.0)


def test_elastic_remesh_roundtrip(tmp_path):
    """Checkpoint from one topology restores onto another (here 1-device
    meshes of different shapes; the multi-device path is exercised in
    test_distributed.py)."""
    from repro.runtime.elastic import available_mesh, remesh

    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    axes = {"w": ("embed", "mlp")}
    mesh = available_mesh(model_parallel=1)
    out = remesh(tree, axes, mesh)
    assert tree_eq(tree, out)


def test_controller_stats_count_steps_and_sync(tmp_path):
    """``TrainController.stats`` counts the steps run and the time turning
    their metrics into floats; the history is what it was without them."""

    def init_state():
        return {"w": jnp.zeros(2)}, {"m": jnp.zeros(2)}

    def step(params, opt, batch):
        params = jax.tree.map(lambda w: w + batch, params)
        return params, opt, {"loss": params["w"][0] * 2.0, "grad_norm": jnp.asarray(0.5)}

    c = TrainController(tmp_path, step, init_state, save_every=100)
    history = c.run(iter([1.0, 2.0, 3.0]), n_steps=10)
    assert history == [
        {"step": 1, "loss": 2.0, "grad_norm": 0.5},
        {"step": 2, "loss": 6.0, "grad_norm": 0.5},
        {"step": 3, "loss": 12.0, "grad_norm": 0.5},
    ]
    assert c.stats["steps"] == 3
    assert c.stats["sync_s"] > 0.0
