"""Fault-tolerance: atomic checkpoints, integrity (I3 analogue), retention,
resume, elastic re-shard."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.train.checkpoint import CheckpointManager


def _tree(seed=0):
    k = jax.random.key(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16)),
                   "layers": {"stack": jnp.arange(24.0).reshape(2, 3, 4)}},
        "opt": {"m": jnp.zeros((8, 16)), "step": jnp.int32(7)},
    }


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(5, tree, extra={"loss": 1.25})
    out, manifest = mgr.restore(tree)
    assert manifest["step"] == 5
    assert manifest["extra"]["loss"] == 1.25
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    assert mgr.latest_step() == 4
    kept = sorted(p.name for p in pathlib.Path(tmp_path).iterdir())
    assert kept == ["step_00000003", "step_00000004"]


def test_corruption_detected(tmp_path):
    """I3: a tampered shard must fail verification on restore."""
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    path = pathlib.Path(mgr.save(3, tree))
    manifest = json.loads((path / "manifest.json").read_text())
    victim = path / next(iter(manifest["leaves"].values()))["file"]
    raw = bytearray(victim.read_bytes())
    raw[-1] ^= 0xFF
    victim.write_bytes(bytes(raw))
    with pytest.raises(IOError, match="integrity"):
        mgr.restore(tree)


def test_restore_without_verify_skips_hashing(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(1, tree)
    out, _ = mgr.restore(tree, verify=False)
    assert jax.tree.structure(out) == jax.tree.structure(tree)


def test_atomic_publish_no_tmp_left(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(9, _tree())
    assert not any(p.name.endswith(".tmp")
                   for p in pathlib.Path(tmp_path).iterdir())


def test_elastic_reshard_subprocess(tmp_path):
    """Save on an 8-device (4,2) mesh, restore onto a 4-device (2,2) mesh —
    the device-loss recovery path."""
    import subprocess
    import sys
    script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" + os.environ["NDEV"]
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.train.checkpoint import CheckpointManager
ndev = len(jax.devices())
mesh = make_mesh((ndev // 2, 2), ("data", "model"))
sh = NamedSharding(mesh, P("data", "model"))
mgr = CheckpointManager({str(tmp_path)!r})
tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
if os.environ["MODE"] == "save":
    tree = {{"w": jax.device_put(tree["w"], sh)}}
    mgr.save(1, tree)
else:
    out, _ = mgr.restore(tree, shardings={{"w": sh}})
    assert out["w"].sharding.mesh.shape["data"] == ndev // 2
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.arange(64.0).reshape(8, 8))
    print("RESHARD_OK")
"""
    env = dict(NDEV="8", MODE="save")
    import os
    env = {**os.environ, "PYTHONPATH": "src", **env}
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    env["NDEV"], env["MODE"] = "4", "restore"
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "RESHARD_OK" in r.stdout


# --- property-based: arbitrary pytrees roundtrip --------------------------


def _roundtrip(tree):
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, tree)
        out, _ = mgr.restore(tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert np.asarray(a).dtype == np.asarray(b).dtype


_LEAVES = [
    jnp.arange(6.0).reshape(2, 3),
    jnp.ones((4,), jnp.int32),
    jnp.zeros((1, 2, 2), jnp.float16),
    jnp.float32(3.5),
]

settings.register_profile("ci", max_examples=15, deadline=None)
settings.load_profile("ci")

_leaf = st.sampled_from(_LEAVES)
_tree_st = st.recursive(
    _leaf, lambda kids: st.dictionaries(
        st.sampled_from(["a", "b", "c", "w"]), kids, min_size=1, max_size=3),
    max_leaves=6)


@given(tree=_tree_st)
def test_roundtrip_arbitrary_pytrees(tree):
    _roundtrip(tree)
