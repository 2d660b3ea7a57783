"""I2 compression-aware gradient sync: QDQ error bounds, error feedback,
int8 ring all-reduce correctness + payload accounting."""

import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.train import compression as comp


def test_qdq_error_bounded():
    g = jax.random.normal(jax.random.key(0), (1000,)) * 0.01
    ghat, err = comp.compress_decompress({"g": g})
    diff = np.abs(np.asarray(ghat["g"] - g))
    # int8 grid: error ≤ scale/2 per block; scale ≈ absmax/127
    assert diff.max() <= float(jnp.max(jnp.abs(g))) / 127.0 * 0.51 + 1e-8
    np.testing.assert_allclose(np.asarray(err["g"]),
                               np.asarray(g - ghat["g"]), atol=1e-7)


def test_error_feedback_unbiased_over_time():
    """With error feedback, the累 accumulated compressed signal tracks the true
    accumulated gradient (1-bit-Adam-style guarantee)."""
    key = jax.random.key(1)
    err = {"g": jnp.zeros((512,), jnp.float32)}
    total_true = jnp.zeros((512,))
    total_sent = jnp.zeros((512,))
    for i in range(20):
        key, k = jax.random.split(key)
        g = jax.random.normal(k, (512,)) * 0.1
        ghat, err = comp.compress_decompress({"g": g}, err)
        total_true += g
        total_sent += ghat["g"]
    resid = np.abs(np.asarray(total_sent + err["g"] - total_true))
    assert resid.max() < 1e-4  # exact up to float round-off


def test_error_feedback_sgd_converges():
    """Toy quadratic: compressed-with-feedback SGD reaches the same loss."""
    w_true = jnp.linspace(-1, 1, 64)

    def loss(w, x):
        return jnp.mean((x @ (w - w_true)) ** 2)

    def run(compressed: bool):
        w = jnp.zeros((64,))
        err = {"w": jnp.zeros((64,))} if compressed else None
        key = jax.random.key(2)
        for i in range(150):
            key, k = jax.random.split(key)
            x = jax.random.normal(k, (16, 64))
            g = jax.grad(loss)(w, x)
            if compressed:
                ghat, err = comp.compress_decompress({"w": g}, err)
                g = ghat["w"]
            w = w - 0.1 * g
        return float(loss(w, jnp.eye(64)))

    assert run(True) < 1e-3
    assert abs(run(True) - run(False)) < 1e-3


def test_payload_ratio():
    r = comp.payload_ratio((1024, 1024), block=256)
    assert 0.25 < r < 0.27  # int8 + f32/block ≈ 3.94× reduction


def test_compressed_ring_allreduce_multidevice():
    """shard_map int8 ring all-reduce ≈ psum on 8 fake devices, and its HLO
    moves int8 (not f32) over the wire."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.train.compression import compressed_ring_allreduce

mesh = make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.key(0), (8, 1024), jnp.float32) * 0.1

def f(xs):
    return compressed_ring_allreduce(xs[0], "data")[None]

y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data", None),
                              out_specs=P("data", None), check_vma=False))(x)
want = jnp.sum(x, axis=0)
got = np.asarray(y[0])
scale = float(jnp.max(jnp.abs(x)))
assert np.abs(got - np.asarray(want)).max() < scale / 127.0 * 8 * 1.5, \
    np.abs(got - np.asarray(want)).max()
txt = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data", None),
                                out_specs=P("data", None), check_vma=False)).lower(x).compile().as_text()
import re
perms = re.findall(r"(s8|f32|bf16)\[([0-9,]+)\][^\n]*collective-permute", txt)
assert any(dt == "s8" for dt, _ in perms), perms
print("RING_OK")
"""
    env = {**os.environ, "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, cwd="/root/repo")
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "RING_OK" in r.stdout
