"""Pipeline parallelism: pipelined forward/backward == sequential reference."""
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code, devices=4, timeout=900):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_gpipe_matches_sequential():
    r = run_py("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.train.pipeline import pipelined_apply

mesh = jax.make_mesh((2, 2), ("pipe", "data"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
L, B, S, D = 4, 8, 4, 16
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (L, D, D)) / jnp.sqrt(D)
x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, D))

def layer_fn(w, h):
    return jnp.tanh(h @ w) + h

def seq(ws, x):
    for i in range(L):
        x = layer_fn(ws[i], x)
    return x

y_ref = seq(ws, x)
y_pipe = jax.jit(lambda w_, x_: pipelined_apply(
    layer_fn, w_, x_, mesh, n_microbatch=4))(ws, x)
np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_ref),
                           atol=1e-5, rtol=1e-5)

# gradients flow through the reverse pipeline identically
g_ref = jax.grad(lambda w_: seq(w_, x).sum())(ws)
g_pipe = jax.grad(lambda w_: pipelined_apply(
    layer_fn, w_, x, mesh, n_microbatch=4).sum())(ws)
np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_ref),
                           atol=1e-4, rtol=1e-4)
print("OK")
""")
    assert "OK" in r.stdout, r.stdout + r.stderr
