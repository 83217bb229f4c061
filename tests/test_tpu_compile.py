"""The device-sync kernels compile for a described TPU v5e chip.

Nothing here runs on a chip: the TPU compiler, which is installed with
JAX, compiles for a v5e that is described, not attached.  That catches
what interpret mode cannot -- block shapes the Mosaic lowering refuses,
more VMEM than a kernel may use, programs larger than the chip's HBM.
The topology is described inside a fixture, so only the worker that runs
these tests loads the TPU library.
"""

import os
import re

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


#: 1-D shards of sub-32-bit dtypes: HACC's uint16 ``mask`` field at its
#: particle count, and an int8 field of 64 MiB and three pages
NARROW = [(100_001_792, jnp.uint16), ((64 << 20) + 3 * PAGE, jnp.int8)]
NARROW_IDS = [f"{n}-{jnp.dtype(d).name}" for n, d in NARROW]


def _strided_or_gathered(hlo: str) -> list[str]:
    """Optimized-HLO lines that slice with a stride above 1 or gather: what
    a lane-strided word view of a 1-D array compiles to."""
    strided = re.compile(r"slice=\{\[\d+:\d+:(\d+)\]")
    return [line.strip()[:160] for line in hlo.splitlines()
            if any(int(s) > 1 for s in strided.findall(line))
            or re.search(r"\bgather\(", line)]


@pytest.mark.parametrize("kernel", ["dirty_blocks", "dirty_pack"])
@pytest.mark.parametrize("n,dtype", NARROW, ids=NARROW_IDS)
def test_narrow_1d_shard_compiles_in_native_tiles(one_chip, kernel, n,
                                                  dtype):
    """A 1-D sub-32-bit shard reaches the kernels as a view in its own
    width: it compiles for the v5e, fits, and has no strided slice or
    gather (which a TPU runs as a lane shuffle at a fraction of HBM
    speed)."""
    be = PAGE // jnp.dtype(dtype).itemsize
    fn = getattr(ops, kernel)
    x = jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)
    c = jax.jit(lambda a, b: fn(a, b, block_elems=be, impl="pallas")).lower(
        x, x).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert _strided_or_gathered(text) == []
    _fits(c)
