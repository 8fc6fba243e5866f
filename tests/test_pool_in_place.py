"""The paged layer loop reads and writes the stacked KV pool in place.

Each attention segment keeps its K and V as one pool stacked over its
layers and stored as the kernels read it, ``[layers, pages, kv_heads,
page_size / f, f * head_dim]`` with ``f`` tokens to a 128-lane row where
head_dim divides 128.  The layer loop carries the pools; each layer
scatters its new rows into ``pool[layer]`` and attends over it through
the layer index.  At the benchmark's CPU test size (batch 4, ``max_len``
256, pages of 16) with the two benchmark configurations' cuts (two
layers, d 64), at head_dim 64 (two tokens to a row), 128 (one) and the
cut's own 16 (eight):

* the paged prefill and decode steps give the dense reference's logits,
  on the XLA route and on the Pallas kernels (interpreted), and the
  engine's join and decode loop emit the dense reference's tokens;
* a join, a decode step and a speculative verify leave every stored
  token outside their write positions bit-for-bit unchanged, in every
  layer, and write their positions in every layer.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.decode_attn import decode_attn_policy
from repro.models import param as pm
from repro.models.model_zoo import Model
from repro.serve.engine import (ServeConfig, jit_paged_decode_loop,
                                jit_paged_join)

B, MAX_LEN, PS = 4, 256, 16
PAGES = B * MAX_LEN // PS
PROMPTS = (5, 17, 32, 40)        # odd and even, inside and across pages
WIDTH = 48
STEPS = 4


def _model(arch: str, d: int):
    cfg = dataclasses.replace(get_config(arch).reduced(), head_dim=d)
    model = Model(cfg)
    return cfg, model, pm.unwrap(model.init(jax.random.key(0)))


def _scfg(mode: str = "auto") -> ServeConfig:
    return ServeConfig(max_len=MAX_LEN, batch=B, dtype=jnp.float32,
                       page_size=PS, sync_every=STEPS, attn_mode=mode)


def _prompts(cfg):
    rng = np.random.default_rng(3)
    toks = np.zeros((B, WIDTH), np.int32)
    for i, n in enumerate(PROMPTS):
        toks[i, :n] = rng.integers(0, cfg.vocab, n)
    return jnp.asarray(toks), jnp.asarray(PROMPTS, jnp.int32)


def _table():
    """Slot i owns a permuted run of pages, so no slot's pages are in
    order."""
    perm = np.random.default_rng(4).permutation(PAGES).astype(np.int32)
    return jnp.asarray(perm.reshape(B, -1))


def _dense(model, params, toks, plens):
    """Prefill then ``STEPS`` greedy decode steps on the dense caches:
    the logits of every step and the tokens fed back."""
    logits, caches = model.prefill(params, {"tokens": toks}, MAX_LEN,
                                   dtype=jnp.float32, last_pos=plens - 1)
    out = [logits[:, -1]]
    lengths = plens
    for _ in range(STEPS):
        tok = jnp.argmax(out[-1], axis=-1).astype(jnp.int32)[:, None]
        logits, caches = model.decode_step(params, tok, caches, lengths,
                                           dtype=jnp.float32)
        out.append(logits[:, -1])
        lengths = lengths + 1
    return out


CASES = [("qwen2-0.5b", 64, "xla"), ("qwen2-0.5b", 128, "xla"),
         ("starcoder2-3b", 64, "xla"), ("starcoder2-3b", 128, "xla"),
         ("qwen2-0.5b", 64, "kernel"), ("qwen2-0.5b", 128, "kernel")]


@pytest.mark.parametrize("arch,d,mode", CASES)
def test_join_then_decode_matches_dense(arch, d, mode):
    """The paged prefill and decode steps through the stacked pool give
    the dense reference's logits at every step; the engine's paged join
    and decode loop emit its greedy tokens."""
    cfg, model, params = _model(arch, d)
    toks, plens = _prompts(cfg)
    want = _dense(model, params, toks, plens)
    table = _table()
    caches = model.init_paged_caches(B, PAGES, PS, jnp.float32)

    @jax.jit
    def paged(caches):
        with decode_attn_policy(mode=mode, interpret=True):
            logits, caches = model.prefill_paged(
                params, {"tokens": toks}, caches, table, dtype=jnp.float32,
                last_pos=plens - 1)
            out = [logits[:, -1]]
            lengths = plens
            for _ in range(STEPS):
                tok = jnp.argmax(out[-1], axis=-1).astype(jnp.int32)[:, None]
                logits, caches = model.decode_step(
                    params, tok, caches, lengths, dtype=jnp.float32,
                    pages=table)
                out.append(logits[:, -1])
                lengths = lengths + 1
        return out
    for step, (w, g) in enumerate(zip(want, paged(caches))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5, err_msg=f"step {step}")

    scfg = _scfg(mode)
    join = jit_paged_join(model, scfg, eos_id=None)
    loop = jit_paged_decode_loop(model, scfg, steps=STEPS, eos_id=None)
    ones = jnp.ones((B,), bool)

    def zeros():                 # one buffer for each donated argument
        return jnp.zeros((B,), jnp.int32)
    caches, tok, lengths, done, remaining, key, first = join(
        params, model.init_paged_caches(B, PAGES, PS, jnp.float32),
        jnp.zeros((B, 1), jnp.int32), zeros(), jnp.ones((B,), bool),
        zeros(), ones, toks, plens, jnp.full((B,), STEPS + 1, jnp.int32),
        jax.random.key(0), table, zeros(), ones)
    (_, _, _, _, _, _), emitted = loop(params, tok, caches, lengths, done,
                                       remaining, key, table)
    got = np.concatenate([np.asarray(first)[None], np.asarray(emitted)])
    greedy = np.stack([np.argmax(np.asarray(w), axis=-1) for w in want])
    np.testing.assert_array_equal(got, greedy)


def _tokens(pool, d):
    """A stored pool as token rows: [layers, pages, kv_heads, ps, d]."""
    return np.asarray(pool).reshape(pool.shape[:3] + (PS, d))


def _written(table, starts, count):
    """[pages, ps] mask of the (page, offset) each slot writes: ``count``
    positions from ``starts[b]``."""
    mask = np.zeros((PAGES, PS), bool)
    table = np.asarray(table)
    for b, s in enumerate(np.asarray(starts)):
        for pos in range(int(s), int(s) + count):
            mask[table[b, pos // PS], pos % PS] = True
    return mask


def _assert_isolated(before, after, d, mask):
    """Outside ``mask`` every layer's tokens are bit-identical; inside it
    every layer's are rewritten."""
    for a, b in zip(before, after):
        ka, kb = _tokens(a, d), _tokens(b, d)
        assert ka.shape == kb.shape
        for layer in range(ka.shape[0]):
            np.testing.assert_array_equal(
                ka[layer].swapaxes(1, 2)[~mask],
                kb[layer].swapaxes(1, 2)[~mask],
                err_msg=f"layer {layer} outside the writes")
            assert (ka[layer].swapaxes(1, 2)[mask]
                    != kb[layer].swapaxes(1, 2)[mask]).all(), layer


@pytest.mark.parametrize("d", [16, 64, 128])
def test_writes_touch_only_their_positions(d):
    """On a pool of random values: a decode step at odd and even depths,
    a three-token verify across a row and a page boundary, and a join of
    every slot each rewrite exactly their positions, in every layer."""
    cfg, model, params = _model("qwen2-0.5b", d)
    scfg = _scfg()
    table = _table()
    rng = np.random.default_rng(d)
    pools = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        model.init_paged_caches(B, PAGES, PS, jnp.float32))
    leaves = jax.tree_util.tree_leaves

    def fresh():
        """A copy of the pools for a program that donates them."""
        return jax.tree_util.tree_map(jnp.copy, pools)

    lengths = jnp.asarray([5, 16, 31, 100], jnp.int32)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)
    loop = jit_paged_decode_loop(model, scfg, steps=1, eos_id=None)
    (_, after, *_), _ = loop(params, tok, fresh(), lengths,
                             jnp.zeros((B,), bool),
                             jnp.full((B,), 9, jnp.int32),
                             jax.random.key(1), table)
    _assert_isolated(leaves(pools), leaves(after), d,
                     _written(table, lengths, 1))

    verify = jax.jit(lambda toks, caches: model.decode_step(
        params, toks, caches, lengths, dtype=jnp.float32, pages=table)[1])
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, 3)), jnp.int32)
    after = verify(toks, pools)
    _assert_isolated(leaves(pools), leaves(after), d,
                     _written(table, lengths, 3))

    join = jit_paged_join(model, scfg, eos_id=None)
    prefix = jnp.asarray([0, 16, 32, 64], jnp.int32)
    ones = jnp.ones((B,), bool)
    toks, plens = _prompts(cfg)
    after = join(params, fresh(), jnp.copy(tok), jnp.copy(lengths),
                 jnp.ones((B,), bool), jnp.zeros((B,), jnp.int32), ones,
                 toks, plens,
                 jnp.full((B,), 4, jnp.int32), jax.random.key(2), table,
                 prefix, ones)[0]
    # each joining row writes its whole padded piece from its prefix on
    _assert_isolated(leaves(pools), leaves(after), d,
                     _written(table, prefix, WIDTH))
