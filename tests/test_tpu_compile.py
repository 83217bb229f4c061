"""The device-sync kernels compile for a described TPU v5e chip.

Nothing here runs on a chip: the TPU compiler, which is installed with
JAX, compiles for a v5e that is described, not attached.  That catches
what interpret mode cannot -- block shapes the Mosaic lowering refuses,
more VMEM than a kernel may use, programs larger than the chip's HBM.
The topology is described inside a fixture, so only the worker that runs
these tests loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

PAGE = 4096
HBM_BYTES = 16 << 30  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(nbytes: int, dtype) -> tuple[int, int]:
    """A square-ish 2-D parameter of ``nbytes`` (both sides powers of 2)."""
    elems = nbytes // jnp.dtype(dtype).itemsize
    rows = 1 << ((elems.bit_length() - 1) // 2)
    return rows, elems // rows


CASES = [(nbytes, dtype) for nbytes in (64 << 20, 1 << 30)
         for dtype in (jnp.float32, jnp.bfloat16)]
IDS = [f"{n >> 20}MiB-{jnp.dtype(d).name}" for n, d in CASES]


def _compile(fn, nbytes, dtype, sharding):
    x = jax.ShapeDtypeStruct(_shape(nbytes, dtype), dtype, sharding=sharding)
    return jax.jit(fn).lower(x, x).compile()


def _fits(compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, m


@pytest.mark.parametrize("nbytes,dtype", CASES, ids=IDS)
def test_dirty_diff_compiles_for_v5e(one_chip, nbytes, dtype):
    be = PAGE // jnp.dtype(dtype).itemsize
    c = _compile(lambda a, b: ops.dirty_blocks(a, b, block_elems=be,
                                               impl="pallas"),
                 nbytes, dtype, one_chip)
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


@pytest.mark.parametrize("nbytes,dtype", CASES, ids=IDS)
def test_diff_pack_compiles_for_v5e(one_chip, nbytes, dtype):
    be = PAGE // jnp.dtype(dtype).itemsize
    c = _compile(lambda a, b: ops.dirty_pack(a, b, block_elems=be,
                                             impl="pallas"),
                 nbytes, dtype, one_chip)
    text = c.as_text()
    # both kernels of the pair: the diff and the DMA pack
    assert text.count("tpu_custom_call") >= 2
    _fits(c)
