"""Tests for bucketed collectives, hierarchical reduce, and host offload."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.collectives import partition_buckets
from repro.distributed.offload import HostOffloader, plan_offload_chunks

_ENV = {**os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def test_partition_buckets_balanced_and_complete():
    tree = {"a": jnp.zeros((1000,)), "b": jnp.zeros((10,)),
            "c": jnp.zeros((500,)), "d": jnp.zeros((499,))}
    buckets = partition_buckets(tree, 2)
    all_idx = sorted(i for b in buckets for i in b)
    assert all_idx == [0, 1, 2, 3]
    leaves = jax.tree.leaves(tree)
    loads = [sum(leaves[i].size for i in b) for b in buckets]
    assert max(loads) - min(loads) <= 1000  # roughly balanced


def test_bucketed_psum_matches_plain_psum():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import bucketed_psum, hierarchical_grad_reduce
mesh = jax.make_mesh((2, 4), ("pod", "data"))
tree = {"w": jnp.arange(24.0).reshape(2, 12), "b": jnp.ones((7,))}

def f(t):
    def local(t):
        return bucketed_psum(t, "data", group_size=4)
    return jax.shard_map(local, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(t)

got = jax.jit(f)(tree)
want = jax.tree.map(lambda x: x * 4.0, tree)  # psum over data axis (size 4)
for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)

def h(t):
    def local(t):
        return hierarchical_grad_reduce(t, "data", "pod")
    return jax.shard_map(local, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(t)

got2 = jax.jit(h)(tree)
want2 = jax.tree.map(lambda x: x * 8.0, tree)  # full 2x4 reduction
for g, w in zip(jax.tree.leaves(got2), jax.tree.leaves(want2)):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)
print("BUCKETED_PSUM_OK")
"""
    out = subprocess.run([sys.executable, "-c", code], env=_ENV,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "BUCKETED_PSUM_OK" in out.stdout


def test_offload_plan_rounds():
    plan = plan_offload_chunks(1 << 30, staging_budget=256 << 20)
    assert plan.n_chunks == 8  # 1 GiB through 128 MiB double-buffered chunks
    tiny = plan_offload_chunks(1 << 20)
    assert tiny.n_chunks == 1


def test_host_offloader_roundtrip():
    off = HostOffloader(staging_budget=64 << 20)
    tree = {"k": jnp.arange(100.0), "v": {"x": jnp.ones((3, 3), jnp.bfloat16)}}
    h = off.offload(tree)
    back = off.restore(h)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert off.rounds >= 4  # 2 leaves, each offloaded + restored
    assert off.bytes_moved == 2 * (100 * 4 + 9 * 2)
    with pytest.raises(KeyError):
        off.restore(h)  # handle freed
