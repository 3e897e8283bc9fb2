"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret-mode tests (``test_kernels.py``) cannot see what the TPU compiler
refuses: blocks not aligned to the (8, 128) tiling, or more fast memory than
a kernel may use.  These tests lower each kernel with ``interpret=False`` at
the shapes the paper-scale scenario (``ScenarioConfig.paper_full``) feeds it
and compile it for one chip of a ``v5e:2x2`` topology that is described, not
attached.  Nothing runs; a kernel passes when the compiler accepts it and
the executable holds the kernel as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never while the
module is imported: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.calibrate.kernel import calibrate_kernel
from repro.kernels.coded_matmul.kernel import coded_matmul_rounds_kernel
from repro.kernels.ssm_scan.kernel import ssm_scan_kernel
from repro.kernels.wkv.kernel import wkv_kernel

# nanogpt-paper: 32,912 parameters per client, M = 20 clients / 4 shards = 5
# clients per shard row, G = 30 rounds, C = 20 coded slices.
P_CLIENT = 32_912
G, C_PAD, S_PAD = 30, 24, 8
P_SHARD_PAD = -(-5 * P_CLIENT // 4096) * 4096          # 167,936
P_CALIB_PAD = -(-P_CLIENT // 8192) * 8192              # 40,960
BATCH, SEQ = 10, 64                                    # generation task


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 topology, with JAX's persistent
    compilation cache off: a compile for a described chip is written to the
    cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_coded_matmul_rounds(one_chip, out_dtype):
    compiled = coded_matmul_rounds_kernel.lower(
        _sds((C_PAD, S_PAD), one_chip),
        _sds((G, S_PAD, P_SHARD_PAD), one_chip),
        block_c=C_PAD, block_p=4096, out_dtype=out_dtype,
        interpret=False).compile()
    _assert_kernel(compiled)


def test_calibrate(one_chip):
    compiled = calibrate_kernel.lower(
        _sds((1, P_CALIB_PAD), one_chip), _sds((8, P_CALIB_PAD), one_chip),
        _sds((1, 8), one_chip), block_p=8192, interpret=False).compile()
    _assert_kernel(compiled)


def test_ssm_scan(one_chip):
    # mamba family: d_inner 64 padded to one 128-wide block, state 8
    d, n = 128, 8
    compiled = ssm_scan_kernel.lower(
        _sds((BATCH, SEQ, d), one_chip), _sds((BATCH, SEQ, n), one_chip),
        _sds((BATCH, SEQ, n), one_chip), _sds((BATCH, SEQ, d), one_chip),
        _sds((d, n), one_chip), _sds((BATCH, d, n), one_chip),
        chunk=SEQ, blk_d=d, interpret=False).compile()
    _assert_kernel(compiled)


def test_wkv(one_chip):
    # rwkv6 family: 2 heads of 16 folded into the grid axis
    bh, n = BATCH * 2, 16
    x = _sds((bh, SEQ, n), one_chip)
    compiled = wkv_kernel.lower(
        x, x, x, x, _sds((bh, 1, n), one_chip), _sds((bh, n, n), one_chip),
        chunk=SEQ, interpret=False).compile()
    _assert_kernel(compiled)
