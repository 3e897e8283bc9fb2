"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret-mode tests (``test_kernels.py``) cannot see what the TPU compiler
refuses: blocks not aligned to the (8, 128) tiling, or more fast memory than
a kernel may use.  These tests lower each kernel with ``interpret=False`` at
the shapes the paper-scale scenario (``ScenarioConfig.paper_full``) feeds it
and compile it for one chip of a ``v5e:2x2`` topology that is described, not
attached.  Nothing runs; a kernel passes when the compiler accepts it and
the executable holds the kernel as a ``tpu_custom_call``.  One more test
compiles the nanogpt-paper stage program, small in rounds and epochs, and
reads in its HLO how the token embedding was lowered and that the program,
metadata aside, is the one it has been since the simulator learnt to train
adapters over a frozen base.

The topology is described inside a module-scoped fixture, never while the
module is imported: only one process at a time may load the TPU library.
"""
import hashlib
import os
import re

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


@pytest.fixture(scope="module")
def nanogpt_stage_text(one_chip):
    """The nanogpt-paper stage program at G=1 and one epoch, compiled."""
    from repro.configs import FLConfig, OptimizerConfig, get_config
    from repro.fl.simulator import FLSimulator
    from repro.models import init_params
    model = get_config("nanogpt-paper")
    shards, clients, samples = 4, 20, 100
    fl = FLConfig(num_clients=clients, clients_per_round=clients,
                  num_shards=shards, local_epochs=1, global_rounds=1)
    sim = FLSimulator(model, fl, {}, task="generation",
                      opt_cfg=OptimizerConfig(name="sgd", lr=0.3,
                                              grad_clip=0.0),
                      local_batch=BATCH)
    w0 = jax.tree.map(lambda a: _sds(a.shape, one_chip, a.dtype),
                      jax.eval_shape(lambda: init_params(model,
                                                         jax.random.key(0))))
    data = _sds((shards, clients // shards, samples, SEQ), one_chip, jnp.int32)
    enc = _sds((clients, shards), one_chip)
    prog = sim._get_stage_program(1, "flat", 1, encode=True)
    return prog.lower(w0, data, data, enc).compile().as_text()


def test_nanogpt_stage_program_reads_embedding_by_matmul(nanogpt_stage_text):
    # The token embedding is a one-hot matmul with a matmul gradient, so the
    # compiled program holds no gather, no scatter-add and no custom fusion
    # of either.
    text = nanogpt_stage_text
    assert "embed.onehot" in text
    for banned in (" gather(", " scatter(", "kind=kCustom", "jit(_take)"):
        assert banned not in text, banned


# sha256 of that program's text with its metadata stripped (op names, source
# locations and the stack-frame tables), as it compiled before the simulator
# took a frozen base for adapter-trained models: without adapters, the
# shared stage path must compile to the very same program.
NANOGPT_STAGE_SHA256 = \
    "cc395509bf8373afb100051ba7b5eca1c6c82004f25981fdab49294d2be8652d"


def strip_metadata(text: str) -> str:
    text = re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n", text,
                  flags=re.S)
    return re.sub(r",?\s*metadata=\{[^}]*\}", "", text)


def test_nanogpt_stage_program_is_unchanged(nanogpt_stage_text):
    stripped = strip_metadata(nanogpt_stage_text)
    assert "op_name" not in stripped and "StackFrames" not in stripped
    assert hashlib.sha256(stripped.encode()).hexdigest() == NANOGPT_STAGE_SHA256
