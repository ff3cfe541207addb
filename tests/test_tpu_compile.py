"""Compile the engine's main-path kernels for a described TPU v5e chip.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached, so these tests need no accelerator.  They catch what
interpret mode cannot: block shapes Mosaic refuses, unsupported primitives in
a kernel body, and programs that overflow VMEM or HBM.  The sizes are the ones
``chip_smoke.py`` drives:

* ``remop_sort`` on the 2**21-key run-formation block EMS passes to the sort
  hook (64 pages of 32,768 keys), and on a 2**18-key merge block with values;
* the partition program (``argsort_by_key`` + ``gather_rows``) on the
  131,072-row block of the Q3 plan's EHJ build phase (7 pages of 16,384
  ``(key, payload)`` rows, padded to a power of two).

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.merge_sort.ops import remop_sort
from repro.remote.backend import _group_by_part

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # libtpu would log under /tmp
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _assert_kernel_program(compiled, n_kernels: int):
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= n_kernels
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES


@pytest.mark.parametrize("n,with_values,n_kernels", [
    (1 << 21, False, 8),  # run formation + 7 merge passes
    (1 << 18, True, 5),
])
def test_sort_compiles_for_v5e(one_chip, n, with_values, n_kernels):
    keys = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    args = (keys, keys) if with_values else (keys,)
    compiled = remop_sort.lower(*args, interpret=False).compile()
    _assert_kernel_program(compiled, n_kernels)


@pytest.mark.parametrize("width", [2, 3])
def test_partition_compiles_for_v5e(one_chip, width):
    n = 1 << 17
    rows = jax.ShapeDtypeStruct((n, width), jnp.int32, sharding=one_chip)
    parts = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    compiled = _group_by_part.lower(rows, parts, max_key=8,
                                    interpret=False).compile()
    _assert_kernel_program(compiled, 5)  # sort: 1 + 3 merges; 1 gather


def test_partition_program_names_its_kernels(one_chip):
    """The device trace tells the gather from the sort by these names, and
    ``chipbench/kernels/partition.py`` finds the module by its own."""
    n = 1 << 14
    rows = jax.ShapeDtypeStruct((n, 2), jnp.int32, sharding=one_chip)
    parts = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    text = _group_by_part.lower(rows, parts, max_key=8, interpret=False).compile().as_text()
    assert text.startswith("HloModule jit__group_by_part,")
    kernels = [line.split(" = ", 1)[0].split()[-1] for line in text.splitlines()
               if "tpu_custom_call" in line and " = " in line]
    assert {k.rsplit(".", 1)[0] for k in kernels} == {"%gather_rows", "%bitonic_block"}
    assert 'op_name="jit(_group_by_part)/argsort/' in text
    assert 'op_name="jit(_group_by_part)/gather_rows/' in text
