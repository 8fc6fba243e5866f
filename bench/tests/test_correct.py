"""``correct`` comes out false for what it must catch: the reference one
precision step lower (float8) in the program's place, and the timed path
broken underneath (a token altered where it is produced, the decode
step's cache write left out).  At the test
size on the CPU; the same readings at the cells' own sizes on the chip
are in PERF.md."""
import time

import numpy as np
import pytest

import repro.models.attention as attention
import repro.serve.engine as engine
from bench.harness import check, runner, spec
from bench.reference import dense_gqa
from bench.tests import small

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_where_the_program_passes(cell):
    c, ov = small.cell(cell)
    ov.control = True
    res = runner.run(cell, 2**31 + 303, 3, False, time.perf_counter(),
                     cell=c, ov=ov)
    prog = res["compared"]["max_logit_gap"]["value"]
    ctrl = res["compared"]["control_max_logit_gap"]["value"]
    assert res["correct"] is True
    assert prog <= small.TEST_LIMIT < ctrl
    assert ctrl >= 3 * max(prog, 1e-3)


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_fails(monkeypatch, cell):
    real = engine.sample_tokens

    def altered(logits, key, temperature):
        return (real(logits, key, temperature) + 1) % logits.shape[-1]
    # the decode loop and the join look the sampler up when they are
    # traced, which happens inside the run
    monkeypatch.setattr(engine, "sample_tokens", altered)
    c, ov = small.cell(cell)
    res = runner.run(cell, 2**31 + 304, 3, False, time.perf_counter(),
                     cell=c, ov=ov)
    assert res["correct"] is False
    assert res["compared"]["max_logit_gap"]["value"] > 10 * small.TEST_LIMIT


@pytest.mark.parametrize("cell", CELLS)
def test_decode_cache_write_left_out_fails(monkeypatch, cell):
    # the decode step returns the paged cache unchanged: the token it
    # feeds back is never written, and later steps read a stale page
    real = attention._paged_insert

    def unchanged(pool, vals, table, length):
        return pool if vals.shape[1] == 1 else real(pool, vals, table, length)
    monkeypatch.setattr(attention, "_paged_insert", unchanged)
    c, ov = small.cell(cell)
    res = runner.run(cell, 2**31 + 305, 3, False, time.perf_counter(),
                     cell=c, ov=ov)
    assert res["correct"] is False
    assert res["compared"]["max_logit_gap"]["value"] > 10 * small.TEST_LIMIT


def test_sample_holds_the_longest_and_depends_on_the_seed():
    done = [(rid, 10 + (rid * 7) % 13) for rid in range(30)]
    a = check.sample(done, 5, 1)
    assert a[0] == max(done, key=lambda f: f[1])[0] and len(set(a)) == 5
    assert a == check.sample(list(reversed(done)), 5, 1)
    assert a != check.sample(done, 5, 2)


def test_gaps_of_the_reference_own_argmax_are_zero():
    m = {"layers": 2, "d": 32, "heads": 4, "kv_heads": 2, "head_dim": 8,
         "ffn": 64, "vocab": 97, "theta": 1e4, "eps": 1e-6, "act": "silu",
         "gated": True, "qkv_bias": True}
    w = dense_gqa.make_weights(m, 5)
    prompt = list(np.random.default_rng(0).integers(0, 97, 20))
    toks = list(prompt)
    for _ in range(6):               # greedy decode with the reference
        lg = dense_gqa.logits_at(w, m, toks, [len(toks) - 1])
        toks.append(int(np.argmax(np.asarray(lg)[0])))
    served = toks[len(prompt):]
    gaps = check.served_gaps(dense_gqa, w, m, prompt, served)
    assert gaps.shape == (6,) and float(gaps.max()) == 0.0
    wrong = [(t + 1) % 97 for t in served]
    assert float(check.served_gaps(dense_gqa, w, m, prompt, wrong).min()) > 0
