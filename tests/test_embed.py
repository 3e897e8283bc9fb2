"""The token embedding's lookup (``repro.models.layers.apply_embed``).

A small real vocabulary reads the table as a one-hot matmul with its own
VJP; a large one gathers.  Each case runs under ``jit`` and under a double
``vmap`` of (4, 5), the stage program's shards and clients."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.nanogpt_paper import CONFIG as NANOGPT
from repro.models.layers import apply_embed, pad_vocab

LEAD = (4, 5)
BATCH, SEQ = 10, 64


def _inputs(cfg, vmapped):
    lead = LEAD if vmapped else ()
    k1, k2, k3, k4 = jax.random.split(jax.random.key(0), 4)
    shape = (pad_vocab(cfg.vocab_size), cfg.d_model)
    # magnitudes over 40 binades, so every part of the bf16 split is used
    table = (jax.random.normal(k1, shape)
             * jnp.exp2(jax.random.randint(k2, shape, -20, 20)))
    tokens = jax.random.randint(k3, lead + (BATCH, SEQ), 0, cfg.vocab_size)
    weight = jax.random.normal(k4, lead + (BATCH, SEQ, cfg.d_model))
    return jnp.broadcast_to(table, lead + shape), tokens, weight


def _lookup(cfg, vmapped, embed=None):
    """``(table, tokens) -> rows`` through ``embed`` (default
    ``apply_embed``), vmapped twice when ``vmapped``."""
    embed = embed or (lambda t, x: apply_embed({"table": t}, x, cfg))
    return jax.vmap(jax.vmap(embed)) if vmapped else embed


def _take(table, tokens):
    return jnp.take(table, tokens, axis=0)


def _hlo(f, *args) -> str:
    return jax.jit(f).lower(*args).compile().as_text()


def _table_grad(lookup, table, tokens, weight):
    return jax.jit(jax.grad(lambda t: jnp.sum(lookup(t, tokens) * weight)))(table)


@pytest.mark.parametrize("vmapped", [False, True], ids=["jit", "vmap2"])
@pytest.mark.parametrize("case", ["forward_exact", "grad_matches_take",
                                  "pad_rows_zero_grad", "large_vocab_gathers"])
def test_embed_lookup(case, vmapped):
    cfg = NANOGPT
    if case == "large_vocab_gathers":
        cfg = dataclasses.replace(cfg, vocab_size=32_000)
    table, tokens, weight = _inputs(cfg, vmapped)
    lookup = _lookup(cfg, vmapped)
    take = _lookup(cfg, vmapped, _take)
    if case == "forward_exact":
        got = jax.jit(lookup)(table, tokens)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(jax.jit(take)(table, tokens)))
        text = _hlo(lookup, table, tokens)
        assert " gather(" not in text and "embed.onehot" in text
    elif case == "grad_matches_take":
        got = np.asarray(_table_grad(lookup, table, tokens, weight))
        want = np.asarray(_table_grad(take, table, tokens, weight))
        assert np.max(np.abs(got - want)) <= 1e-6 * np.max(np.abs(want))
    elif case == "pad_rows_zero_grad":
        got = np.asarray(_table_grad(lookup, table, tokens, weight))
        assert np.all(got[..., cfg.vocab_size:, :] == 0)
    else:
        text = _hlo(lookup, table, tokens)
        assert " gather(" in text and "embed.gather" in text
        assert "embed.onehot" not in text
