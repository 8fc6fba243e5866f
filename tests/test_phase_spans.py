"""The scheduler's phase spans on the profiler's clock.

A few requests are served at a reduced size under ``jax.profiler.trace``
and the ``serve.*`` annotations are read back from the ``.xplane.pb``
with ``ProfileData``: every phase of a round appears, nested in its
``serve.round`` span and one after another; the counts the join and
pages spans carry agree with the registry and the batcher's own records,
and no other span carries any; a Tracer records
the same intervals; and attaching a Tracer neither changes the tokens
nor adds a host-device sync.
"""
import glob
import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import param as pm
from repro.models.model_zoo import Model
from repro.serve.chaos import ChaosInjector
from repro.serve.engine import JOIN_GROUP_ROWS, ServeConfig
from repro.serve.scheduler import Batcher
from repro.serve.telemetry import Tracer

PHASES = ("chaos", "sweep", "admit", "join", "pages", "decode-segment",
          "collect")
SERVE = dict(max_len=96, batch=6, dtype=jnp.float32, sync_every=4,
             paged=True, page_size=8, total_pages=10, prefill_chunk=8,
             admission_mode="optimistic")
MAX_NEW = 10


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-0.5b").reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    return cfg, model, params


def _serve(setup, tracer=None):
    """Seven requests (prompts of 8-20 tokens, so some take two chunks)
    into six slots over a ten-page pool, a forced exhaustion at round 2
    (preemptions) and one request whose timeout has passed (a
    cancellation in the sweep)."""
    cfg, model, params = setup
    chaos = ChaosInjector(exhaust_at={2: 0}, release_at=(5,))
    b = Batcher(model, params, ServeConfig(**SERVE), chaos=chaos,
                telemetry=tracer)
    rng = np.random.default_rng(3)
    for rid in range(6):
        b.submit(rid, rng.integers(0, cfg.vocab, size=int(
            rng.integers(8, 21))).tolist())
    b.submit(6, rng.integers(0, cfg.vocab, size=12).tolist(), timeout_s=0.0)
    return b, b.run(max_new=MAX_NEW)


def _profiled(setup, tmp_path, tracer=None):
    with jax.profiler.trace(str(tmp_path)):
        b, results = _serve(setup, tracer)
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    s = int(e.start_ns)
                    spans.append((e.name[len("serve."):], s,
                                  s + int(e.duration_ns), dict(e.stats)))
    return b, results, sorted(spans, key=lambda x: (x[1], -x[2]))


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    tr = Tracer()
    b, results, spans = _profiled(setup, tmp_path_factory.mktemp("xplane"),
                                  tr)
    return b, results, spans, tr


def _by_round(spans):
    """The round spans in order, and each round's (numbered from 1)
    phase spans."""
    rounds = [s for s in spans if s[0] == "round"]
    inner = {n: [] for n in range(1, len(rounds) + 1)}
    for sp in spans:
        if sp[0] == "round":
            continue
        (n,) = [n for n, r in enumerate(rounds, 1)
                if r[1] <= sp[1] and sp[2] <= r[2]]
        inner[n].append(sp)
    return rounds, inner


def test_every_phase_nests_in_its_round_in_order(traced):
    b, _, spans, _ = traced
    rounds, inner = _by_round(spans)
    assert len(rounds) == b.round
    assert all(a[2] <= c[1] for a, c in zip(rounds, rounds[1:]))
    assert {sp[0] for sp in spans} == {"round", *PHASES}
    for phases in inner.values():
        names = [sp[0] for sp in phases]
        # a subsequence of the round's phase order, each phase once
        assert names == [p for p in PHASES if p in names]
        for a, c in zip(phases, phases[1:]):
            assert a[2] <= c[1]


def test_span_counts_agree_with_the_batcher(traced):
    b, results, spans, tr = traced
    args = {p: [sp[3] for sp in spans if sp[0] == p]
            for p in ("round", *PHASES)}
    joins = args["join"]
    assert sum(a["tokens"] for a in joins) == \
        b.metrics.value("prefill.computed_tokens")
    # the join computes whole groups of JOIN_GROUP_ROWS over its pieces
    pieces = Counter(e["round"] for e in tr.events
                     if e["kind"] == "PREFILL_CHUNK")
    r = JOIN_GROUP_ROWS
    assert [a["rows_computed"] for a in joins] == \
        [-(-pieces[n] // r) * r for n in sorted(pieces)]
    assert sum(a["rows_computed"] for a in joins) == \
        b.metrics.value("join.rows_computed")
    widths = [a["width"] for a in joins]
    assert all(w >= 8 and w & (w - 1) == 0 for w in widths)
    assert sorted(widths) == sorted(b.metrics.samples("join.width"))
    assert all(a["tokens"] <= a["rows_computed"] * a["width"]
               for a in joins)
    pages = [(a["live_tokens"], a["mapped_tokens"]) for a in args["pages"]
             if a]          # a round whose preemptions left no decoder
    assert pages == [(lt, cap) for lt, cap, _ in b.kv_samples]
    assert all(0 < lt <= cap for lt, cap in pages)
    # the run took its preemption and its cancellation, and no phase
    # but the join and the pages carries arguments
    assert b.metrics.value("preempt.count") > 0
    assert b.metrics.value("cancel.count") == 1 and len(results) == 6
    assert all(a == {} for p in ("round", "chaos", "sweep", "admit",
                                 "decode-segment", "collect")
               for a in args[p])


def test_tracer_records_the_same_phases(traced):
    _, _, spans, tr = traced
    rounds, inner = _by_round(spans)
    want = [("round", n) for n in range(1, len(rounds) + 1)] + [
        (sp[0], n) for n, phases in inner.items() for sp in phases]
    assert sorted((sp["name"], sp["round"]) for sp in tr.spans) == \
        sorted(want)


def test_tracer_changes_neither_tokens_nor_syncs(setup, traced,
                                                 monkeypatch):
    _, plain = _serve(setup)
    calls = []
    real = jax.block_until_ready

    def counted(x):
        calls.append(1)
        return real(x)
    monkeypatch.setattr(jax, "block_until_ready", counted)
    _, with_tracer = _serve(setup, Tracer())
    assert calls == []
    assert with_tracer == plain == traced[1]
