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
adapters over a frozen base.  Two more compile Moonlight's held-expert
layer (vmapped over shards and clients, with its input gradient) and the
tiny variant's stage program, and read in them that the held experts run
as grouped matmuls over the routed pairs and take no weight gradient; the
latter program, metadata aside, is pinned like nanogpt's.

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


def _grouped_matmuls(text):
    """(output dims, op_name) of each grouped-matmul kernel in ``text``."""
    return [(tuple(int(v) for v in dims.split(",")), name) for dims, name in
            re.findall(r'= \w+\[([\d,]+)\][^\n]*custom_call_target='
                       r'"tpu_custom_call"[^\n]*op_name="([^"]*jit\(gmm\)[^"]*)"',
                       text)]


def _expected(buffers, d, f):
    """The grouped matmuls' output dims for each pair buffer: forward, the
    gate and up projections together (rows, 2f) and the down projection
    (rows, d); backward, the first again, then the down projection's
    transpose (rows, f) and the others' (rows, d)."""
    return sorted(dims for rows in buffers for dims in
                  [(rows, 2 * f)] * 2 + [(rows, f)] + [(rows, d)] * 2)


def test_held_experts_run_as_grouped_matmuls(one_chip, monkeypatch):
    """At Moonlight's widths: two grouped matmuls forward and three backward
    for each size of pair buffer (of all the batch's tokens), each under
    ``moe.experts``; no tensor holds the held-expert axis beside the expert
    or the model width but the weights (the dense layer, or a dense
    fallback of a grouped matmul, would)."""
    from repro.configs import get_config
    from repro.models import moe
    from repro.models.params import ShapeOnly
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    cfg = get_config("moonlight-16b-a3b-fedlora")
    held, d, f = cfg.experts_held, cfg.d_model, cfg.moe_d_ff
    p = jax.tree.map(lambda a: _sds(a.shape, one_chip, a.dtype),
                     moe.init_moe(ShapeOnly(jnp.bfloat16), cfg))
    shards, clients, seq = 2, 3, 128
    x = _sds((shards, clients, 1, seq, d), one_chip, jnp.bfloat16)

    def step(p, x):
        def loss(x):
            y, _ = moe.apply_moe_held(p, x, cfg)
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return jax.vmap(jax.vmap(jax.grad(loss)))(x)
    text = jax.jit(step).lower(p, x).compile().as_text()
    kernels = _grouped_matmuls(text)
    assert sorted(dims for dims, _ in kernels) == _expected(
        moe.pair_buffer_sizes(shards * clients * seq, cfg.experts_per_token,
                              held), d, f)
    assert all("moe.experts" in name for _, name in kernels)
    shapes = {tuple(int(v) for v in s.split(","))
              for s in re.findall(r"\w+\[([\d,]+)\]", text)}
    assert {s for s in shapes if held in s and (f in s or d in s)} <= {
        (held, d, f), (held, f, d), (held, d, 2 * f)}


@pytest.fixture(scope="module")
def moonlight_stage_text(one_chip):
    """The tiny Moonlight variant's stage program, compiled with the held
    experts steered onto their TPU path (grouped matmuls)."""
    from repro.configs import FLConfig, OptimizerConfig
    from repro.fl.families import get_model_family
    from repro.fl.simulator import FLSimulator
    from repro.models import moe
    cfg = get_model_family("moonlight").build(None)
    fl = FLConfig(num_clients=8, clients_per_round=4, num_shards=2,
                  local_epochs=1, global_rounds=2)
    sim = FLSimulator(cfg, fl, {}, task="generation",
                      opt_cfg=OptimizerConfig(name="sgd", lr=0.1,
                                              grad_clip=0.0),
                      local_batch=2, seed=5)
    shape = lambda a: _sds(a.shape, one_chip, a.dtype)  # noqa: E731
    xs = _sds((2, 2, 4, 6), one_chip, jnp.int32)
    prog = sim._get_stage_program(1, "flat", 2, encode=True)
    # lowered without source frames: the kernels' serialized bodies would
    # otherwise carry file paths and line numbers, out of strip_metadata's
    # reach, and the pin below would follow the checkout and every edit
    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(moe, "on_tpu", lambda: True)
            return cfg, prog.func.lower(
                jax.tree.map(shape, sim.base),
                jax.tree.map(shape, sim.init_params(jax.random.key(5))),
                xs, xs, _sds((4, 2), one_chip)).compile().as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)


def test_moonlight_stage_program_trains_no_expert_weight(moonlight_stage_text):
    """The tiny variant's stage program: in each MoE layer two grouped
    matmuls run forward and three backward (the rematerialised forward keeps
    only the gate and up projections), all over pair buffers and under
    ``moe.experts``; none has a weight's shape, so none is a gradient for the
    frozen experts."""
    from repro.models import moe
    cfg, text = moonlight_stage_text
    kernels = _grouped_matmuls(text)
    assert sorted(dims for dims, _ in kernels) == _expected(
        moe.pair_buffer_sizes(2 * 2 * 2 * 6, cfg.experts_per_token,
                              cfg.experts_held), cfg.d_model, cfg.moe_d_ff)
    assert all("moe.experts" in name for _, name in kernels)


# sha256 of that program's text with its metadata stripped, as it compiled
# when stage training still had three round engines to choose from (taken
# on that code, lowered as above): with one training path, the adapter
# path's measured program must be the very same.
MOONLIGHT_STAGE_SHA256 = \
    "d8444ba800601531067a7019d143887a58445556077f4724926c6e376c043cf6"


def test_moonlight_stage_program_is_unchanged(moonlight_stage_text):
    stripped = strip_metadata(moonlight_stage_text[1])
    assert "op_name" not in stripped and "StackFrames" not in stripped
    assert hashlib.sha256(stripped.encode()).hexdigest() == \
        MOONLIGHT_STAGE_SHA256
