"""Multi-device tests (8 host CPU devices via a subprocess so the main
pytest process stays single-device, per the dry-run isolation rule)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["REPRO_DRYRUN_DEVICES"] = str(devices)
    out = subprocess.run([sys.executable, "-c", code], env=env, timeout=timeout,
                         capture_output=True, text=True)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_decode_attention_and_topk():
    run_py("""
from repro import compat
import jax, jax.numpy as jnp, numpy as np, jax.random as jr
mesh = compat.make_mesh((2, 4), ('data', 'model'))
from repro.distributed.decode_attention import sharded_decode_attention, sharded_topk_sample
from repro.kernels import ref
from repro import core
B,S,Hq,Hkv,D = 4, 64, 8, 2, 16
ks = jr.split(jr.PRNGKey(0), 4)
q = jr.normal(ks[0], (B,1,Hq,D)); kc = jr.normal(ks[1], (B,S,Hkv,D)); vc = jr.normal(ks[2], (B,S,Hkv,D))
vlen = jnp.array([64, 40, 17, 1], jnp.int32)
with mesh:
    o = sharded_decode_attention(q, kc, vc, vlen, mesh=mesh, seq_axes=('model',), batch_axes=('data',), chunk_size=16, scale=D**-0.5)
np.testing.assert_allclose(np.asarray(o), np.asarray(ref.attention_ref(q, kc, vc, causal=False, kv_valid_len=vlen)), rtol=2e-5, atol=2e-5)
logits = jr.normal(ks[3], (B, 512)) * 4
with mesh:
    tok, probs = sharded_topk_sample(jr.PRNGKey(7), logits, 5, mesh=mesh, batch_axes=('data',))
st = core.softmax_topk(logits, 5)
np.testing.assert_allclose(np.asarray(probs), np.asarray(st.values), rtol=1e-5, atol=1e-6)
print('OK')
""")


def test_int8_allreduce():
    run_py("""
from repro import compat
import jax, jax.numpy as jnp, numpy as np
mesh = compat.make_mesh((8,), ('data',))
from repro.distributed.compression import int8_allreduce
x = jnp.linspace(-2, 2, 1024)
with mesh:
    y = int8_allreduce(x, mesh, 'data')
np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=2e-2)
print('OK')
""")


def test_sharded_train_step_matches_single_device():
    """The pjit'd train step on a 2x4 mesh must produce the same params as
    the unsharded step (same batch, same init)."""
    run_py("""
from repro import compat
import jax, jax.numpy as jnp, numpy as np
import repro.configs as configs
from repro.configs.base import OptimizerConfig, ParallelConfig, RunConfig
from repro.data.synthetic import SyntheticConfig, SyntheticDataset
from repro.distributed import context, sharding
from repro.training.train_step import init_state, make_train_step
cfg = configs.get_smoke('smollm_360m')
run = RunConfig(model=cfg, optimizer=OptimizerConfig(lr=1e-3, warmup_steps=0, schedule='constant'),
                parallel=ParallelConfig(grad_reduce_dtype='float32'))
params, opt, axes = init_state(run, jax.random.PRNGKey(0))
ds = SyntheticDataset(SyntheticConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8))
batch = jax.tree.map(jnp.asarray, ds.batch(0))
# single device
p1, _, m1 = make_train_step(run)(jax.tree.map(jnp.copy, params), jax.tree.map(jnp.copy, opt), batch)
# sharded
mesh = compat.make_mesh((2, 4), ('data', 'model'))
par = sharding.derive_parallel(cfg, mesh, run.parallel)
p_sh = sharding.param_sharding(axes, cfg, par, mesh)
params_s = jax.device_put(params, p_sh)
ctx = context.ShardContext(mesh=mesh, par=par)
with mesh, context.use(ctx):
    step = jax.jit(make_train_step(run))
    p2, _, m2 = step(params_s, opt, batch)
assert abs(float(m1['loss']) - float(m2['loss'])) < 5e-3, (float(m1['loss']), float(m2['loss']))
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=2e-2, atol=3e-3)
print('OK loss', float(m1['loss']))
""")


@pytest.mark.parametrize("arch", ["smollm-360m", "qwen2-moe-a2.7b",
                                  "zamba2-1.2b"])
def test_dryrun_smoke_cells(arch):
    """Every builder path lowers+compiles on a small mesh (smoke configs)."""
    run_py(f"""
from repro import compat
import jax
from repro.launch import dryrun
mesh = compat.make_mesh((2, 4), ('data', 'model'))
for shape in ('train_4k', 'prefill_32k', 'decode_32k'):
    rec = dryrun.run_cell({arch!r}, shape, multi_pod=False, mesh=mesh,
                          smoke=True, verbose=False)
    assert rec['status'] == 'ok', (shape, rec)
    assert rec['hlo_flops_per_device'] > 0
    assert rec['collective_bytes_per_device'] >= 0
print('OK')
""")


def test_dryrun_multipod_smoke():
    run_py("""
from repro import compat
import jax
from repro.launch import dryrun
mesh = compat.make_mesh((2, 2, 2), ('pod', 'data', 'model'))
rec = dryrun.run_cell('smollm-360m', 'train_4k', multi_pod=True, mesh=mesh,
                      smoke=True, verbose=False)
assert rec['status'] == 'ok'
rec = dryrun.run_cell('xlstm-125m', 'long_500k', multi_pod=True, mesh=mesh,
                      smoke=True, verbose=False)
assert rec['status'] == 'ok'
print('OK')
""")


def test_elastic_reshard_restore():
    """Save sharded on a 2x4 mesh, restore onto 4x2 — elastic scaling."""
    run_py("""
from repro import compat
import jax, jax.numpy as jnp, numpy as np
import repro.configs as configs
from repro.checkpoint.manager import CheckpointManager
from repro.distributed import sharding
from repro.training.train_step import init_state
from repro.configs.base import RunConfig
import tempfile
cfg = configs.get_smoke('smollm_360m')
run = RunConfig(model=cfg)
params, _, axes = init_state(run, jax.random.PRNGKey(0))
d = tempfile.mkdtemp()
mgr = CheckpointManager(d)
mesh1 = compat.make_mesh((2, 4), ('data', 'model'))
par1 = sharding.derive_parallel(cfg, mesh1)
sh1 = sharding.param_sharding(axes, cfg, par1, mesh1)
p1 = jax.device_put(params, sh1)
mgr.save(1, {'params': p1}, blocking=True)
mesh2 = compat.make_mesh((4, 2), ('data', 'model'))
par2 = sharding.derive_parallel(cfg, mesh2)
sh2 = sharding.param_sharding(axes, cfg, par2, mesh2)
like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), {'params': params})
restored = mgr.restore(1, like, shardings={'params': sh2})
for a, b in zip(jax.tree.leaves(restored['params']), jax.tree.leaves(params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print('OK')
""")
