"""Every configuration (with its reference and family modules), traffic
mix and metric loads by name, agrees with BENCHMARK.json, and a new one
of each kind is found without an edit to any file that is already
there."""
import json
import os
import re
import shutil
import time

import pytest

from bench.harness import runner, spec
from bench.tests import small

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.benchmark()


def test_benchmark_file_follows_its_rules():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or w in moved["workloads"]
    for w in cells:
        c = spec.cell(w)
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and c.per_layer
    assert {w["config"] for w in cells.values()} == \
        {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_loads_by_name(cfg):
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    data = spec.config(cfg["name"])
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    for key in cfg["reduced"]:
        assert key in data and NAME.match(key)
    # the program's registry entry agrees with the sizes the reference runs
    m = spec.reference_module(data).dims(data)
    fam = spec.family_module(data)
    fam.check(fam.arch_config(data), m)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_small_cut_passes_the_family_check(cfg):
    data = spec.config(cfg["name"])
    fam = spec.family_module(data)
    cut, arch = fam.small_cut(data)
    m = spec.reference_module(cut).dims(cut)
    fam.check(arch, m)
    assert m["layers"] < spec.reference_module(data).dims(data)["layers"]
    if data["reference"]["module"] == "dense_gqa":
        assert (m["layers"], m["d"], m["heads"], m["head_dim"], m["vocab"]) \
            == (2, 64, 4, 16, 256)
    # a departure from the cut sizes is refused
    with pytest.raises(ValueError):
        fam.check(arch, dict(m, vocab=m["vocab"] + 1))


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_traffic_file_loads_by_name(w):
    mix = spec.traffic(w["traffic"])
    assert mix["arrivals"]["kind"] in ("closed", "poisson")
    assert spec.cell(w["name"]).traffic == mix


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_file_loads_by_name(m):
    mod = spec.metric_module(m["name"])
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"],
                                                m["moves"])
    assert callable(mod.read)


def test_peak_table_names_its_source_and_refuses_others():
    pk = spec.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com" in pk["source"]
    with pytest.raises(KeyError):
        spec.peaks("TPU v4")


ROUTED = os.path.join(os.path.dirname(__file__), "routed_gqa")


def test_new_files_need_no_edit(tmp_path, monkeypatch):
    bench_dir = tmp_path / "bench"
    shutil.copytree(spec.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {os.path.join(r, p): open(os.path.join(r, p), "rb").read()
              for r, _, fs in os.walk(bench_dir) for p in fs}
    cfg = spec.config("qwen2-0.5b")
    cfg["name"] = "qwen2-0.5b-b64"
    cfg["serve"] = dict(cfg["serve"], batch=64)
    # its own reference and family modules, copies of dense_gqa that
    # record being called
    cfg["reference"] = dict(cfg["reference"], module="plain_decoder")
    ref_src = open(bench_dir / "reference" / "dense_gqa.py").read()
    (bench_dir / "reference" / "plain_decoder.py").write_text(
        ref_src + "\n\nMADE = []\n_make = make_weights\n\n\n"
        "def make_weights(m, seed, dtype=jnp.bfloat16):\n"
        "    MADE.append(seed)\n    return _make(m, seed, dtype)\n")
    fam_src = open(bench_dir / "harness" / "families" / "dense_gqa.py").read()
    checks = ("\n\nCHECKED = []\n_check = check\n\n\n"
              "def check(arch, m):\n    _check(arch, m)\n"
              "    CHECKED.append((arch.name, m['layers']))\n")
    (bench_dir / "harness" / "families" / "plain_decoder.py").write_text(
        fam_src + "\n\nHANDED = []\n_to = to_program\n\n\n"
        "def to_program(w):\n    HANDED.append(len(w))\n"
        "    return _to(w)\n" + checks)
    (bench_dir / "configs" / "qwen2-0.5b-b64.json").write_text(
        json.dumps(cfg))
    # a second family, not dense_gqa: grouped-query attention and routed
    # experts, with an expert width the dense cut does not know, cut by
    # its own family, and a ledger key of its own that a reader reads
    shutil.copy(os.path.join(ROUTED, "reference.py"),
                bench_dir / "reference" / "routed_gqa.py")
    (bench_dir / "harness" / "families" / "routed_gqa.py").write_text(
        open(os.path.join(ROUTED, "family.py")).read() + checks)
    shutil.copy(os.path.join(ROUTED, "config.json"),
                bench_dir / "configs" / "routed-gqa.json")
    shutil.copy(os.path.join(ROUTED, "expert_flop_share.py"),
                bench_dir / "metrics" / "expert_flop_share.py")
    mix = dict(spec.traffic("code_completion"), max_new=32)
    (bench_dir / "traffic" / "short_code.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "join_share.py").write_text(
        'LAYER = "model step (serve/engine.py join and decode loop)"\n'
        'UNIT = "%"\nMOVES = "ttft_p95_s"\n'
        "def read(record, trace):\n    return 1.0\n")
    new_cells = ["qwen2-0.5b-b64.short_code", "routed-gqa.short_code"]
    bench = dict(BENCH)
    bench["configs"] = BENCH["configs"] + [
        dict(BENCH["configs"][0], name="qwen2-0.5b-b64",
             file="bench/configs/qwen2-0.5b-b64.json"),
        {"name": "routed-gqa", "source": "test fixture",
         "file": "bench/configs/routed-gqa.json", "reduced": [],
         "why": "test"}]
    bench["workloads"] = BENCH["workloads"] + [
        {"name": n, "config": n.rsplit(".", 1)[0], "traffic": "short_code",
         "chips": 1, "why": "test"} for n in new_cells]
    bench["end_to_end"] = [
        dict(m, workloads=m["workloads"] + new_cells)
        if m["name"] == "ttft_p95_s" else m for m in BENCH["end_to_end"]]
    bench["per_layer"] = BENCH["per_layer"] + [
        {"name": "join_share", "unit": "%", "better": "lower",
         "source": "device_trace", "moves": "ttft_p95_s",
         "layer": "model step (serve/engine.py join and decode loop)",
         "workloads": new_cells[:1]},
        {"name": "expert_flop_share", "unit": "%", "better": "higher",
         "source": "host_clock", "moves": "ttft_p95_s", "layer": "experts",
         "workloads": new_cells[1:]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "BENCH_DIR", str(bench_dir))
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    c = spec.cell("qwen2-0.5b-b64.short_code")
    assert c.config["serve"]["batch"] == 64 and c.traffic["max_new"] == 32
    assert [m["name"] for m in c.per_layer] == ["join_share"]
    assert spec.metric_module("join_share").read({}, None) == 1.0
    assert spec.cell("qwen2-0.5b.offline_long_output").config["serve"][
        "batch"] == 128
    ref, fam = spec.reference_module(c.config), spec.family_module(c.config)
    assert ref.__file__ == str(bench_dir / "reference" / "plain_decoder.py")
    assert fam.__file__ == str(
        bench_dir / "harness" / "families" / "plain_decoder.py")
    # the harness runs the new cell through them (at the test size)
    seed = 2**31 + 909
    sc, ov = small.cell(c.name)
    res = runner.run(c.name, seed, 2, False, time.perf_counter(), cell=sc,
                     ov=ov)
    assert res["correct"] is True and res["attempted"] > 0
    assert ref.MADE == [seed, seed] and len(fam.HANDED) == 1
    assert fam.CHECKED == [("qwen2-0.5b-smoke", 2)]

    # the second family: its configuration at full size passes its check,
    # the dense cut leaves its expert keys at their published values and
    # fails it, and its own cut passes it
    rc = spec.cell("routed-gqa.short_code")
    rref, rfam = spec.reference_module(rc.config), \
        spec.family_module(rc.config)
    assert rfam.__file__ == str(
        bench_dir / "harness" / "families" / "routed_gqa.py")
    rfam.check(rfam.arch_config(rc.config), rref.dims(rc.config))
    dense_cut, _ = spec.family_module(spec.config("qwen2-0.5b")).small_cut(
        rc.config)
    sc, ov = small.cell(rc.name)
    m = rref.dims(sc.config)
    assert (rref.dims(dense_cut)["expert_ffn"], m["expert_ffn"]) == (1408, 32)
    with pytest.raises(ValueError):
        rfam.check(ov.arch, rref.dims(dense_cut))
    res = runner.run(rc.name, seed, 2, True, time.perf_counter(), cell=sc,
                     ov=ov)
    assert res["correct"] is True and res["attempted"] > 0
    assert rfam.CHECKED == [("moonshot-v1-16b-a3b", 48),
                            ("moonshot-v1-16b-a3b-smoke", 3)]
    share = res["metrics"]["expert_flop_share"]["value"]
    assert 0 < share < 100
    after = {p: open(p, "rb").read() for p in before}
    assert after == before
