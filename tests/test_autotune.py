"""Autotuning harness for the paged-attention family: static
feasibility pruning (infeasible tilings never run), the end-to-end
sweep's winner selection + tuned-shape cache roundtrip, policy-side
loading (hit / miss / corrupt fallback / env override), and bit-exact
parity between tuned and default launches on every op."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core.hwspec import DEFAULT_TPU
from repro.kernels.decode_attn import decode_attn_policy
from repro.kernels.paged_attn import autotune as at
from repro.kernels.paged_attn.kernel import default_pages_per_block
from repro.kernels.paged_attn.ops import decode_launch
from repro.kernels.paged_attn import (paged_attn, paged_prefill_attn,
                                      paged_prefill_attn_ref,
                                      paged_verify_attn)

GEOM = at.Geometry(hq=4, hkv=2, d=8, page_size=8)   # g=2, tiny but GQA


def _no_env(monkeypatch):
    monkeypatch.delenv(at.ENV_VAR, raising=False)


# --------------------------------------------------------------------------
# analytic pruner
# --------------------------------------------------------------------------

def test_feasible_rejects_non_divisor_block_rows():
    ok, why = at.feasible(at.Candidate("bh", 7), op="prefill", lg=24,
                          geom=GEOM)
    assert not ok and "divide" in why


def test_feasible_rejects_block_rows_on_decode():
    ok, why = at.feasible(at.Candidate("bh", 2), op="decode", lg=2,
                          geom=GEOM)
    assert not ok and "decode" in why


def test_feasible_rejects_vmem_overflow():
    """A roofline-infeasible tiling (working set past VMEM) is rejected
    statically — it must never reach the benchmark stage."""
    tiny = dataclasses.replace(DEFAULT_TPU, vmem_bytes=64)
    ok, why = at.feasible(at.Candidate(), op="prefill", lg=16, geom=GEOM,
                          spec=tiny)
    assert not ok and "VMEM" in why
    # sanity: the same tiling fits a real VMEM
    assert at.feasible(at.Candidate(), op="prefill", lg=16, geom=GEOM)[0]


def test_decode_candidates_walk_page_blocks():
    """Decode sweeps every power-of-two page block up to the table; a
    page block is no launch option of the prefill kernel."""
    cands = at.enumerate_candidates("decode", GEOM.g, pages=8)
    assert cands[0] == at.Candidate()
    assert {c.pages_per_block for c in cands} == {None, 1, 2, 4, 8}
    assert {c.grid_order for c in cands} == {"bh", "hb"}
    ok, why = at.feasible(at.Candidate(pages_per_block=2), op="prefill",
                          lg=16, geom=GEOM)
    assert not ok and "page block" in why


def test_prune_drops_infeasible_and_keeps_default_first():
    wl = at.make_workload("prefill", GEOM)       # lq=8, g=2 -> lg=16
    bad = at.Candidate("bh", 7)                  # 7 does not divide 16
    survivors, pruned = at.prune(wl, [at.Candidate(), at.Candidate("hb"),
                                      bad])
    assert survivors[0] == at.Candidate()
    assert bad not in survivors
    assert any(c == bad and "divide" in why for c, why in pruned)


def test_prune_budget_cut_retains_default():
    wl = at.make_workload("prefill", GEOM)
    survivors, pruned = at.prune(wl, budget=2)
    assert len(survivors) == 2
    assert survivors[0] == at.Candidate()
    assert any("budget" in why for _, why in pruned)


# --------------------------------------------------------------------------
# end-to-end sweep + cache roundtrip
# --------------------------------------------------------------------------

def test_autotune_selects_and_persists_winner_per_op(tmp_path, monkeypatch):
    _no_env(monkeypatch)
    res = at.autotune(geom=GEOM, budget=2, reps=1)
    assert set(res) == set(at.OPS)
    for op, r in res.items():
        assert isinstance(r["winner"], dict)
        # the default is always in the measured set, so the wall-time
        # argmin can never lose to it
        assert r["winner_wall_s"] <= r["default_wall_s"]
        assert r["achieved_gbps"] > 0
    path = at.save_entries(res, str(tmp_path / "tuned.json"))
    entries = at.load_entries(path)
    backend = jax.default_backend()
    for op in at.OPS:
        ent = entries[at.entry_key(backend, op, GEOM)]
        assert ent["config"] == res[op]["winner"]


def test_save_entries_merges_and_discards_unknown_schema(tmp_path,
                                                         monkeypatch):
    _no_env(monkeypatch)
    p = tmp_path / "tuned.json"
    keep = {"schema": at.SCHEMA,
            "entries": {"tpu|decode|other": {"config": {"grid_order": "hb"}}}}
    p.write_text(json.dumps(keep))
    res = at.autotune(ops=("decode",), geom=GEOM, reps=1)
    at.save_entries(res, str(p))
    entries = at.load_entries(str(p))
    assert "tpu|decode|other" in entries           # merged, not clobbered
    assert at.entry_key(jax.default_backend(), "decode", GEOM) in entries
    # an unknown on-disk schema is discarded rather than half-merged
    p.write_text(json.dumps({"schema": 99, "entries": keep["entries"]}))
    at.save_entries(res, str(p))
    assert "tpu|decode|other" not in at.load_entries(str(p))


# --------------------------------------------------------------------------
# policy-side loading
# --------------------------------------------------------------------------

def _cache_file(tmp_path, config, op="decode", geom=GEOM):
    key = at.entry_key(jax.default_backend(), op, geom)
    p = tmp_path / "cache.json"
    p.write_text(json.dumps({"schema": at.SCHEMA,
                             "entries": {key: {"config": config}}}))
    return str(p)


def test_policy_cache_hit(tmp_path, monkeypatch):
    _no_env(monkeypatch)
    path = _cache_file(tmp_path, {"grid_order": "hb"})
    with decode_attn_policy(use_tuned=True, tuned_path=path) as pol:
        assert pol.tuned_config("decode", hq=4, hkv=2, d=8,
                                page_size=8) == {"grid_order": "hb"}


def test_policy_cache_miss_returns_none(tmp_path, monkeypatch):
    _no_env(monkeypatch)
    path = _cache_file(tmp_path, {"grid_order": "hb"})
    with decode_attn_policy(use_tuned=True, tuned_path=path) as pol:
        assert pol.tuned_config("decode", hq=8, hkv=8, d=64,
                                page_size=16) is None
        assert pol.tuned_config("prefill", hq=4, hkv=2, d=8,
                                page_size=8, lg=16) is None


def test_policy_corrupt_cache_degrades_to_defaults(tmp_path, monkeypatch):
    _no_env(monkeypatch)
    p = tmp_path / "corrupt.json"
    p.write_text("{not json")
    with decode_attn_policy(use_tuned=True, tuned_path=str(p)) as pol:
        assert pol.tuned_config("decode", hq=4, hkv=2, d=8,
                                page_size=8) is None


def test_policy_sanitizes_block_rows_against_lg(tmp_path, monkeypatch):
    """Entries are keyed without Lq, so a tuned row tiling only applies
    to calls whose fused row count it divides."""
    _no_env(monkeypatch)
    path = _cache_file(tmp_path, {"grid_order": "hb", "block_rows": 6},
                       op="prefill")
    with decode_attn_policy(use_tuned=True, tuned_path=path) as pol:
        assert pol.tuned_config("prefill", hq=4, hkv=2, d=8, page_size=8,
                                lg=12) == {"grid_order": "hb",
                                           "block_rows": 6}
        assert pol.tuned_config("prefill", hq=4, hkv=2, d=8, page_size=8,
                                lg=16) == {"grid_order": "hb"}
        assert pol.tuned_config("prefill", hq=4, hkv=2, d=8, page_size=8,
                                lg=None) == {"grid_order": "hb"}


@pytest.mark.parametrize("config,want", [
    ({"grid_order": "hb"}, ("hb", None)),         # no block: shape default
    ({"grid_order": "hb", "pages_per_block": 2}, ("hb", 2)),
    ({"pages_per_block": 0}, ("bh", None)),       # malformed: dropped
    ({"pages_per_block": 64}, ("bh", 8)),         # held to the table
])
def test_decode_launch_resolves_pages_per_block(config, want, tmp_path,
                                                monkeypatch):
    """The decode kernel's page block: an explicit value wins, then the
    tuned entry's, then the shape default."""
    _no_env(monkeypatch)
    path = _cache_file(tmp_path, config)
    kw = dict(hq=4, hkv=2, d=8, page_size=8, p_cap=8)
    with decode_attn_policy(use_tuned=True, tuned_path=path):
        got = decode_launch(**kw)
        assert decode_launch(**kw, pages_per_block=4) == (want[0], 4)
    assert got == (want[0], want[1] or default_pages_per_block(8, 8))


def test_env_var_disables_and_redirects(tmp_path, monkeypatch):
    path = _cache_file(tmp_path, {"grid_order": "hb"})
    for off in ("", "off", "0", "ignore"):
        monkeypatch.setenv(at.ENV_VAR, off)
        assert at.load_entries(path) == {}       # env wins over the path
    other = tmp_path / "other.json"
    other.write_text(json.dumps({"schema": at.SCHEMA,
                                 "entries": {"x|y|z": {"config": {}}}}))
    monkeypatch.setenv(at.ENV_VAR, str(other))
    assert "x|y|z" in at.load_entries(path)


def test_load_memo_invalidates_on_rewrite(tmp_path, monkeypatch):
    _no_env(monkeypatch)
    path = _cache_file(tmp_path, {"grid_order": "hb"})
    first = at.load_entries(path)
    assert first and at.load_entries(path) is first      # memo hit
    with open(path, "w") as f:
        json.dump({"schema": at.SCHEMA, "entries": {}}, f)
    assert at.load_entries(path) == {}                   # mtime/size key


# --------------------------------------------------------------------------
# tuned-vs-default parity on the live ops
# --------------------------------------------------------------------------

def _run_op(op, wl, route):
    kw = dict(mode=route, interpret=True) if route == "kernel" \
        else dict(mode=route)
    if op == "decode":
        return lambda pol_kw: _call(paged_attn, wl, dict(kw, **pol_kw),
                                    decode=True)
    fn = paged_verify_attn if op == "verify" else paged_prefill_attn
    return lambda pol_kw: _call(fn, wl, dict(kw, **pol_kw))


def _call(fn, wl, pol_kw, decode=False):
    with decode_attn_policy(**pol_kw):
        if decode:
            return np.asarray(fn(wl.q, wl.k_pages, wl.v_pages, wl.table,
                                 wl.lengths, interpret=True))
        return np.asarray(fn(wl.q, wl.k_pages, wl.v_pages, wl.table,
                             wl.q_offset, wl.lengths))


@pytest.mark.parametrize("op", at.OPS)
@pytest.mark.parametrize("route", ["kernel", "xla"])
def test_tuned_vs_default_bit_exact(op, route, tmp_path, monkeypatch):
    """With a tuned grid order persisted for this geometry, routing
    through the cache must produce bit-identical outputs to the
    defaults on both the kernel route (grid order permutes independent
    (b, h) programs) and the XLA route (which ignores launch config
    entirely)."""
    _no_env(monkeypatch)
    path = _cache_file(tmp_path, {"grid_order": "hb"}, op=op)
    wl = at.make_workload(op, GEOM)
    run = _run_op(op, wl, route)
    default = run(dict(use_tuned=False))
    tuned = run(dict(use_tuned=True, tuned_path=path))
    assert np.array_equal(default, tuned)


@pytest.mark.parametrize("br", [1, 2, 4, 8, 16])
def test_block_rows_divisors_match_oracle(br):
    """Every divisor row fold must stay numerically equivalent to the
    gather oracle (bit-exactness across folds is a backend lowering
    property — the autotuner parity-gates it; correctness is not)."""
    wl = at.make_workload("prefill", GEOM)       # lg = 16
    with decode_attn_policy(mode="kernel", interpret=True,
                            use_tuned=False):
        out = paged_prefill_attn(wl.q, wl.k_pages, wl.v_pages, wl.table,
                                 wl.q_offset, wl.lengths, block_rows=br)
    ref = paged_prefill_attn_ref(wl.q, wl.k_pages, wl.v_pages, wl.table,
                                 wl.q_offset, wl.lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
