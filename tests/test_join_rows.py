"""The paged join computes only the joining rows.

``make_paged_join`` gathers the slots in ``join_mask`` into groups of
``JOIN_GROUP_ROWS`` rows, prefills each group and scatters its results
back.  At a reduced size in float32, on a pool and slot state filled
with random values: every joiner's first token, length, remaining budget
and done flag equal a one-row prefill of that joiner alone; every other
slot's state, and every page outside the joiners' tables, is
bit-identical before and after; the all-false mask the benchmark's
warm-up passes returns the whole state unchanged; a chunk that does not
commit stays frozen; a prefix-cache reader in a lower slot than its
writer reads the writer's pages across a group boundary; and a hybrid
SSM model's non-joining slots keep their recurrent state.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import BlockKind
from repro.models import param as pm
from repro.models.model_zoo import Model
from repro.serve.engine import (JOIN_GROUP_ROWS, PAD_TOKEN, ServeConfig,
                                jit_paged_join)

R = JOIN_GROUP_ROWS
B = 2 * R + 3           # join counts 0, 1, R, R + 1 and B all fit
PS = 8
MAX_LEN = 48            # 6 pages a slot
SPARE = 3               # pool pages in no slot's table
WIDTH = 32
BUDGET = 5


def _setup(arch):
    """The reduced model, its serving config, the join with the weights
    bound, and a jitted one-row paged prefill."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    scfg = ServeConfig(max_len=MAX_LEN, batch=B, dtype=jnp.float32,
                       paged=True, page_size=PS,
                       total_pages=B * MAX_LEN // PS + SPARE)
    one_row = jax.jit(lambda toks, caches, table, last, depth:
                      model.prefill_paged(
                          params, {"tokens": toks}, caches, table,
                          dtype=jnp.float32, last_pos=last, cache_len=depth))
    join = functools.partial(jit_paged_join(model, scfg, eos_id=None),
                             params)
    return cfg, model, scfg, join, one_row


@pytest.fixture(scope="module")
def qwen():
    return _setup("qwen2-0.5b")


@pytest.fixture(scope="module")
def zamba():
    return _setup("zamba2-1.2b")


def _random_state(model, scfg, rng):
    """Pools and per-slot state filled with random values, so that an
    untouched row or page is told apart from a written one."""
    caches = model.init_paged_caches(B, scfg.pool_pages, PS, jnp.float32)
    caches = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype), caches)
    tok = jnp.asarray(rng.integers(0, 256, (B, 1)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, MAX_LEN, B), jnp.int32)
    done = jnp.asarray(rng.integers(0, 2, B).astype(bool))
    remaining = jnp.asarray(rng.integers(0, 9, B), jnp.int32)
    # slot i owns pages [6 i, 6 i + 6); the SPARE pages sit in no table
    pages = np.arange(B * MAX_LEN // PS, dtype=np.int32).reshape(B, -1)
    return [caches, tok, lengths, done, remaining,
            jax.random.PRNGKey(7)], pages


def _host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def _join(fn, state, pages, joins):
    """Run the join for ``joins``: {slot: (piece, prefix_len, commit)}."""
    join_mask = np.zeros(B, bool)
    commit_mask = np.zeros(B, bool)
    prompts = np.zeros((B, WIDTH), np.int32)
    plens = np.ones(B, np.int32)
    prefix_lens = np.zeros(B, np.int32)
    for slot, (piece, depth, commit) in joins.items():
        join_mask[slot], commit_mask[slot] = True, commit
        prompts[slot, :len(piece)] = piece
        plens[slot], prefix_lens[slot] = len(piece), depth
    return _host(fn(*state[:5], *map(jnp.asarray, (
        join_mask, prompts, plens, np.full(B, BUDGET, np.int32))), state[5],
        jnp.asarray(pages), jnp.asarray(prefix_lens),
        jnp.asarray(commit_mask)))


def _one_row(model, one_row, caches, slot, piece, depth, pages):
    """First token and caches of a prefill of ``slot``'s piece alone."""
    kinds = [s.kind for s in model.cfg.resolved_segments()]
    row = [jax.tree_util.tree_map(lambda a: a[:, slot:slot + 1], c)
           if k is BlockKind.SSM else c for k, c in zip(kinds, caches)]
    toks = np.zeros((1, WIDTH), np.int32)
    toks[0, :len(piece)] = piece
    logits, new = one_row(jnp.asarray(toks), row,
                          jnp.asarray(pages[slot:slot + 1]),
                          jnp.asarray([len(piece) - 1], jnp.int32),
                          jnp.asarray([depth], jnp.int32))
    return int(jnp.argmax(logits[0, -1])), _host(new)


def _assert_untouched(model, before, after, pages, joining):
    """Non-joining slots' state and SSM rows, and every page outside the
    joiners' tables, are bit-identical."""
    keep = np.setdiff1d(np.arange(B), joining)
    for a, b in zip(before[1:5], after[1:5]):
        np.testing.assert_array_equal(a[keep], b[keep])
    kinds = [s.kind for s in model.cfg.resolved_segments()]
    written = pages[joining].ravel()
    outside = np.setdiff1d(np.arange(B * MAX_LEN // PS + SPARE), written)
    for k, c0, c1 in zip(kinds, before[0], after[0]):
        for a, b in zip(jax.tree_util.tree_leaves(c0),
                        jax.tree_util.tree_leaves(c1)):
            if k is BlockKind.SSM:
                np.testing.assert_array_equal(a[:, keep], b[:, keep])
            else:
                np.testing.assert_array_equal(a[:, outside], b[:, outside])


CASES = {"none": (0, False), "one": (1, False), "group": (R, False),
         "group_plus_one": (R + 1, False), "all": (B, False),
         "chunked": (R + 1, True)}


@pytest.mark.parametrize("case", list(CASES))
def test_join_equals_one_row_prefills(qwen, case):
    """Each joiner's outputs equal its own one-row prefill; everything
    else is untouched.  ``chunked``: every other joiner's piece is a
    mid-prompt chunk (``commit_mask`` ≠ ``join_mask``), which keeps its
    token, reads ``remaining`` 0 and ``done`` True."""
    cfg, model, scfg, fn, one_row = qwen
    count, chunked = CASES[case]
    rng = np.random.default_rng(count + 10 * chunked)
    state, pages = _random_state(model, scfg, rng)
    before = _host(state)
    joining = np.sort(rng.permutation(B)[:count])
    joins = {}
    for i, slot in enumerate(joining):
        piece = rng.integers(0, cfg.vocab, int(rng.integers(1, WIDTH + 1)))
        depth = int(rng.integers(0, 2)) * PS     # some resume at a page
        joins[int(slot)] = (piece.tolist(), depth,
                            not (chunked and i % 2 == 0))
    caches, tok, lengths, done, remaining, key, first = _join(
        fn, state, pages, joins)
    after = [caches, tok, lengths, done, remaining, key]
    _assert_untouched(model, before, after, pages, joining)
    for slot, (piece, depth, commit) in joins.items():
        want, _ = _one_row(model, one_row, before[0], slot, piece, depth,
                           pages)
        assert first[slot] == want
        assert lengths[slot] == depth + len(piece)
        if commit:
            assert tok[slot, 0] == want
            assert remaining[slot] == BUDGET - 1 and not done[slot]
        else:
            assert tok[slot, 0] == before[1][slot, 0]
            assert remaining[slot] == 0 and done[slot]
    assert (first[np.setdiff1d(np.arange(B), joining)] == PAD_TOKEN).all()
    if count == 0:
        # the warm-up's all-false mask: no group runs, nothing changes
        for a, b in zip(jax.tree_util.tree_leaves(before[:5]),
                        jax.tree_util.tree_leaves(after[:5])):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(before[5], after[5])


def test_prefix_writer_in_higher_slot_across_groups(qwen):
    """The highest joining slot writes a two-page prompt prefix that the
    lowest joining slot reads through its table in the same join, and
    they land in different groups: the reader's first token equals a
    run without the cache, where it prefills the whole prompt itself."""
    cfg, model, scfg, fn, one_row = qwen
    rng = np.random.default_rng(5)
    state, pages = _random_state(model, scfg, rng)
    before = _host(state)
    shared = rng.integers(0, cfg.vocab, 2 * PS).tolist()
    suffix = rng.integers(0, cfg.vocab, 5).tolist()
    writer, reader = B - 1, 0
    # R prefix-free joiners (the writer among them) fill the first group
    joins = {s: (rng.integers(0, cfg.vocab, 9).tolist(), 0, True)
             for s in range(1, R)}
    joins[writer] = (shared + rng.integers(0, cfg.vocab, 3).tolist(), 0,
                     True)
    cached = pages.copy()
    cached[reader, :2] = pages[writer, :2]
    got = _join(fn, state, cached, {**joins, reader: (suffix, 2 * PS,
                                                      True)})
    plain = _join(fn, [jax.tree_util.tree_map(jnp.asarray, x)
                       for x in before[:5]] + [state[5]], pages,
                  {**joins, reader: (shared + suffix, 0, True)})
    assert got[6][reader] == plain[6][reader]
    assert got[2][reader] == plain[2][reader] == 2 * PS + len(suffix)
    for slot in joins:
        assert got[6][slot] == plain[6][slot]


@pytest.mark.parametrize("count", [1, R + 1])
def test_hybrid_ssm_rows(zamba, count):
    """A paged hybrid SSM model: joiners take the recurrent state of
    their own one-row prefill; non-joining slots keep theirs."""
    cfg, model, scfg, fn, one_row = zamba
    rng = np.random.default_rng(count)
    state, pages = _random_state(model, scfg, rng)
    before = _host(state)
    joining = np.sort(rng.permutation(B)[:count])
    joins = {int(s): (rng.integers(0, cfg.vocab, int(
        rng.integers(1, WIDTH + 1))).tolist(), 0, True) for s in joining}
    out = _join(fn, state, pages, joins)
    _assert_untouched(model, before, out[:6], pages, joining)
    kinds = [s.kind for s in model.cfg.resolved_segments()]
    for slot, (piece, depth, _) in joins.items():
        want, new = _one_row(model, one_row, before[0], slot, piece, depth,
                             pages)
        assert out[6][slot] == want
        for k, c_new, c_got in zip(kinds, new, out[0]):
            if k is not BlockKind.SSM:
                continue
            for a, b in zip(jax.tree_util.tree_leaves(c_new),
                            jax.tree_util.tree_leaves(c_got)):
                np.testing.assert_allclose(a[:, 0], b[:, slot], rtol=1e-5,
                                           atol=1e-5)
