"""The data path's Pallas kernels compile for a TPU v5e chip.

Each test lowers and compiles one kernel at acquisition size (a 1 mm T1w of
256x256x176) for a described, not attached, ``v5e:2x2`` topology. Interpret
mode accepts kernels the TPU compiler refuses (scalar stores to VMEM,
blocks that break the (8, 128) tiling); these compiles catch that without a
chip. Nothing runs, so results are checked elsewhere (``tests/test_kernels``
and friends in interpret mode, ``chip_smoke.py`` on the chip).
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.stream import DEFAULT_CHUNK_BYTES
from repro.kernels.checksum import checksum as ck

T1W = (256, 256, 176)
N_T1W = T1W[0] * T1W[1] * T1W[2]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiles_to_kernel(fn, *args, **kwargs):
    compiled = fn.lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_device_checksum_compiles(one_chip):
    _compiles_to_kernel(ck._device_checksum,
                        _shape(one_chip, T1W, jnp.float32), interpret=False)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int16])
def test_one_shot_qa_compiles(one_chip, dtype):
    _compiles_to_kernel(ck._qa_checksum_2d,
                        _shape(one_chip, (1, N_T1W), dtype), interpret=False)


def test_batched_qa_compiles(one_chip):
    compiled = _compiles_to_kernel(
        ck._qa_checksum_2d, _shape(one_chip, (4, N_T1W), jnp.float32),
        interpret=False)
    # four T1w volumes and their carries fit one chip's 16 GB
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_chunk_fold_compiles(one_chip):
    """One launch of the streaming fold at the default 4 MiB chunk, the
    shape every full chunk of a float32 stream compiles to."""
    nblocks = DEFAULT_CHUNK_BYTES // (ck.BLK * 4)
    k_blocks, _, w_rows = ck._launch_shape(nblocks, ck.BLK, 4)
    carry = (ck.SUBLANES, ck.LANES)
    _compiles_to_kernel(
        ck._qa_call, _shape(one_chip, (3,), jnp.int32),
        _shape(one_chip, (1, w_rows, ck.LANES), jnp.int32), None,
        _shape(one_chip, (1, 3) + carry, jnp.int32),
        _shape(one_chip, (1, 3) + carry, jnp.float32),
        k_blocks=k_blocks, blk_v=ck.BLK, interpret=False)
