"""Sharded-execution tests on 8 fake host devices (subprocess: XLA_FLAGS must
be set before jax initializes, so these run via `python -c` children)."""

import os
import subprocess
import sys


_ENV = {**os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def _run(code: str) -> str:
    out = subprocess.run([sys.executable, "-c", code], env=_ENV,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    """Train step on a (2,4) mesh must produce the same loss as 1 device."""
    print(_run("""
import jax, jax.numpy as jnp, numpy as np
AUTO = (jax.sharding.AxisType.Auto,) * 2
from repro.configs import ARCHS, reduced
from repro.distributed.sharding import Sharder
from repro.launch import steps as steps_lib
from repro.optim.adamw import AdamWConfig
from repro.data.pipeline import synthetic_batches
from repro.configs.base import ShapeSpec

cfg = reduced(ARCHS["qwen3-0.6b"], n_kv_heads=4)
shape = ShapeSpec("t", seq_len=32, global_batch=8, kind="train")
batch = jax.tree.map(jnp.asarray, next(synthetic_batches(cfg, shape, seed=0)))
opt = AdamWConfig(lr=1e-3, total_steps=10, warmup_steps=1)

mesh = jax.make_mesh((2, 4), ("data", "model"), AUTO)
sharder = Sharder(mesh, sequence_parallel=True)
state = steps_lib.init_state(cfg, jax.random.key(0))
st_shard = steps_lib.state_shardings(state["params"], mesh, sharder)
state_sharded = jax.tree.map(lambda x, s: jax.device_put(x, s), state, st_shard)
step = jax.jit(steps_lib.make_train_step(cfg, opt, sharder),
               in_shardings=(st_shard, None),
               out_shardings=(st_shard, None))
new_state, metrics = step(state_sharded, batch)
loss_sharded = float(metrics["loss"])

# Single-device reference.
from repro.models import transformer as tf
loss_ref = float(tf.loss_fn(state["params"], cfg, batch)[1]["loss"])
assert abs(loss_sharded - loss_ref) < 5e-2, (loss_sharded, loss_ref)
# One more step to exercise donated buffers.
new_state, metrics = step(new_state, batch)
assert jnp.isfinite(metrics["loss"])
print("SHARDED_TRAIN_OK", loss_sharded, loss_ref)
"""))


def test_sharded_decode_matches_prefill_consistency():
    """Sharded decode step reproduces unsharded logits."""
    print(_run("""
import jax, jax.numpy as jnp, numpy as np
AUTO = (jax.sharding.AxisType.Auto,) * 2
from repro.configs import ARCHS, reduced
from repro.distributed.sharding import Sharder
from repro.launch import specs as specs_lib, steps as steps_lib
import repro.distributed.sharding as shlib
from repro.models import transformer as tf

cfg = reduced(ARCHS["gemma-2b"], n_kv_heads=1, n_heads=4)
params = tf.init_params(jax.random.key(0), cfg)
tokens = jax.random.randint(jax.random.key(1), (8, 12), 0, cfg.vocab_size)
_, caches = tf.prefill(params, cfg, {"tokens": tokens[:, :11]})
caches = tf.pad_caches(cfg, caches, 16)
want, _ = tf.decode_step(params, cfg, caches, tokens[:, 11],
                         jnp.asarray(11, jnp.int32))

mesh = jax.make_mesh((2, 4), ("data", "model"), AUTO)
sharder = Sharder(mesh, sequence_parallel=False)
p_shard = shlib.named_sharding_tree(shlib.param_specs(params, sharder), mesh)
c_shard = specs_lib.cache_shardings(cfg, sharder, caches)
step = jax.jit(steps_lib.make_decode_step(cfg, sharder),
               in_shardings=(p_shard, c_shard,
                             sharder.sharding(["batch"], (8,)),
                             sharder.sharding([], ())))
params_s = jax.tree.map(lambda x, s: jax.device_put(x, s), params, p_shard)
caches_s = jax.tree.map(lambda x, s: jax.device_put(x, s), caches, c_shard)
nxt, logits, _ = step(params_s, caches_s, tokens[:, 11],
                      jnp.asarray(11, jnp.int32))
np.testing.assert_allclose(np.asarray(logits, np.float32),
                           np.asarray(want, np.float32), rtol=3e-2, atol=3e-2)
print("SHARDED_DECODE_OK")
"""))


def test_elastic_reshard_roundtrip(tmp_path):
    """Checkpoint on a (2,4) mesh, restore onto (4,2) — elastic re-scale."""
    print(_run(f"""
import jax, jax.numpy as jnp, numpy as np
AUTO = (jax.sharding.AxisType.Auto,) * 2
from repro.configs import ARCHS, reduced
from repro.distributed.sharding import Sharder
from repro.launch import steps as steps_lib
import repro.distributed.sharding as shlib
from repro.checkpoint.store import CheckpointStore
from repro.models import transformer as tf

cfg = reduced(ARCHS["qwen3-0.6b"], n_kv_heads=4)
params = tf.init_params(jax.random.key(0), cfg)
store = CheckpointStore({str(tmp_path)!r})

mesh1 = jax.make_mesh((2, 4), ("data", "model"), AUTO)
s1 = Sharder(mesh1)
shard1 = shlib.named_sharding_tree(shlib.param_specs(params, s1), mesh1)
p1 = jax.tree.map(lambda x, s: jax.device_put(x, s), params, shard1)
store.save(7, p1, {{"step": 7}}, blocking=True)

mesh2 = jax.make_mesh((4, 2), ("data", "model"), AUTO)
s2 = Sharder(mesh2)
shard2 = shlib.named_sharding_tree(shlib.param_specs(params, s2), mesh2)
step, restored, meta = store.restore_latest(params, shard2)
assert step == 7 and meta["step"] == 7
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("ELASTIC_RESHARD_OK")
"""))


def test_moe_ep_shard_map_matches_gspmd():
    """The shard_map EP dispatch (§Perf winner) is numerically exact."""
    print(_run("""
import jax, jax.numpy as jnp
AUTO = (jax.sharding.AxisType.Auto,) * 2
from repro.configs import ARCHS, reduced
from repro.distributed.sharding import Sharder, use_sharder
from repro.models import moe as moe_mod, transformer as tf
cfg = reduced(ARCHS["deepseek-v2-lite-16b"], n_experts=8, experts_per_token=2,
              capacity_factor=8.0)
params = tf.init_params(jax.random.key(0), cfg)
tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
batch = {"tokens": tokens, "targets": tokens}
mesh = jax.make_mesh((2, 4), ("data", "model"), AUTO)
sharder = Sharder(mesh, sequence_parallel=False)
def loss(p):
    with use_sharder(sharder):
        return tf.loss_fn(p, cfg, batch)[0]
l_base = float(jax.jit(loss)(params))
moe_mod.set_moe_impl("ep_shard_map")
try:
    l_ep = float(jax.jit(loss)(params))
    g_ep = jax.jit(jax.grad(loss))(params)
finally:
    moe_mod.set_moe_impl("gspmd")
g_base = jax.jit(jax.grad(loss))(params)
gd = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
         for a, b in zip(jax.tree.leaves(g_base), jax.tree.leaves(g_ep)))
assert abs(l_base - l_ep) < 2e-3, (l_base, l_ep)
assert gd < 2e-2, gd
print("MOE_EP_EQUIV_OK", l_base, l_ep, gd)
"""))
