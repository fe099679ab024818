"""Distribution-layer tests that need multiple devices / the 512-device
dry-run path — run in subprocesses so the test session keeps 1 device."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code, devices=4, timeout=900):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_des_identical_across_member_counts():
    r = run_py("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core.cloudsim import SimulationConfig, run_simulation
devs = jax.devices()
for broker in ("round_robin", "matchmaking"):
    cfg = SimulationConfig(n_vms=40, n_cloudlets=80, broker=broker, is_loaded=True,
                           workload_iters_per_gmi=0.05)
    r1 = run_simulation(cfg, Mesh(np.array(devs[:1]), ("data",)))
    r4 = run_simulation(cfg, Mesh(np.array(devs), ("data",)))
    assert np.array_equal(r1.vm_assign, r4.vm_assign), broker
    np.testing.assert_allclose(r1.finish_times, r4.finish_times, rtol=1e-5)
    np.testing.assert_allclose(r1.workload_checksum, r4.workload_checksum, rtol=1e-4)
print("OK")
""")
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_mapreduce_backends_agree_distributed():
    r = run_py("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.mapreduce import MapReduceEngine, make_corpus, word_count_job
mesh = Mesh(np.array(jax.devices()), ("data",))
corpus = make_corpus(8, 512, vocab=64)
expected = np.bincount(corpus.reshape(-1), minlength=64)
for backend in ("hazelcast", "infinispan"):
    out = MapReduceEngine(mesh, backend=backend).run(word_count_job(64),
                                                     jnp.asarray(corpus))
    assert np.array_equal(np.asarray(out), expected), backend
print("OK")
""")
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_moe_ep_matches_oracle_on_mesh():
    r = run_py("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_config, reduced
from repro.models import moe as moe_mod
from repro.models.shard_ctx import sharding_rules
from repro.models.param import init_params
mesh = jax.make_mesh((2,2), ("data","model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
cfg = dataclasses.replace(reduced(get_config("olmoe-1b-7b"), n_experts=4,
                                  d_ff_expert=64, d_model=64),
                          capacity_factor=8.0)
params = init_params(moe_mod.moe_defs(cfg), jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64), jnp.float32)
ref = moe_mod.moe_block(params, x, cfg, compute_dtype=jnp.float32, moe_impl="dense")
with sharding_rules(cfg.policy, mesh, **{"exp": "model", "moe_ff": None}):
    ep = jax.jit(lambda p, xx: moe_mod.moe_block(
        p, xx, cfg, compute_dtype=jnp.float32, moe_impl="ep"))(params, x)
np.testing.assert_allclose(np.asarray(ep), np.asarray(ref), atol=2e-4, rtol=2e-3)
print("OK")
""")
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_ring_reduce_scatter_distributed():
    r = run_py("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh
from repro.train.compression import ring_reduce_scatter
mesh = Mesh(np.array(jax.devices()), ("data",))
n, k = 4, 8
x = jnp.arange(n * n * k, dtype=jnp.float32).reshape(n, n * k)
out = ring_reduce_scatter(x, mesh)
np.testing.assert_allclose(np.asarray(out), np.asarray(x.sum(0).reshape(n, k)))
print("OK")
""")
    assert "OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_dryrun_cell_compiles_on_512_devices():
    """End-to-end dry-run contract for one cheap cell (the full 66-cell sweep
    artifacts live in experiments/dryrun; see EXPERIMENTS.md §Dry-run)."""
    r = run_py("""
from repro.launch.dryrun import run_cell
from repro.launch import mesh as mesh_lib
mesh = mesh_lib.make_production_mesh(multi_pod=True)
meta = run_cell("mamba2-370m", "long_500k", mesh, "pod2", out_dir=None)
assert meta["peak_gb"] < 16.0, meta
print("OK", meta["peak_gb"])
""", devices=512, timeout=1200)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_elastic_remesh_across_devices():
    r = run_py("""
import jax
from repro.configs import get_config, reduced
from repro.models.model import build_model
from repro.core.health import HealthConfig
from repro.data.pipeline import DataConfig
from repro.train.elastic_runner import run_elastic_training
cfg = reduced(get_config("smollm-360m"), n_layers=2, d_model=32, n_heads=2,
              n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64)
model = build_model(cfg, remat=False, xent_chunk=8)
rep = run_elastic_training(
    model, steps=16, data_cfg=DataConfig(64, 16, 8), start_instances=1,
    health_cfg=HealthConfig(target_step_time=1e6, min_threshold=-1,
                            time_between_scaling=4, window=2,
                            max_threshold=0.0))   # load always 'high' -> scale out
assert rep.scale_events, rep
assert rep.final_n_instances > 1
print("OK", rep.scale_events)
""")
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_distributed_flash_decode_matches_unsharded():
    """Sequence-sharded KV decode (the long_500k SP path): softmax over the
    sharded KV axis must equal the unsharded computation."""
    r = run_py("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.models.attention import _chunked_attn

mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
B, S, H, hd = 1, 256, 2, 16
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)

ref = _chunked_attn(q, k, v, causal=False, window=0, q_offset=0,
                    kv_len=jnp.int32(200), q_chunk=1)

kv_sh = NamedSharding(mesh, P(None, "data", None, None))
k_s = jax.device_put(k, kv_sh)
v_s = jax.device_put(v, kv_sh)
out = jax.jit(lambda q_, k_, v_, n: _chunked_attn(
    q_, k_, v_, causal=False, window=0, q_offset=0, kv_len=n, q_chunk=1),
    in_shardings=(NamedSharding(mesh, P()), kv_sh, kv_sh,
                  NamedSharding(mesh, P())))(q, k_s, v_s, jnp.int32(200))
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                           rtol=1e-5)
# the compiled module must actually reduce over the sharded axis
txt = jax.jit(lambda q_, k_, v_, n: _chunked_attn(
    q_, k_, v_, causal=False, window=0, q_offset=0, kv_len=n, q_chunk=1),
    in_shardings=(NamedSharding(mesh, P()), kv_sh, kv_sh,
                  NamedSharding(mesh, P()))).lower(
        q, k_s, v_s, jnp.int32(200)).compile().as_text()
assert ("all-reduce" in txt) or ("all-gather" in txt)
print("OK")
""")
    assert "OK" in r.stdout, r.stdout + r.stderr
