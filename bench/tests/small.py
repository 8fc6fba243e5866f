"""A cell of the benchmark cut to a size the CPU runs in seconds: the
configuration's own family at ``reduced()`` widths, a few slots, short
prompts and outputs, and the device check skipped.  For the tests only;
the command line never runs a cell this way."""
import dataclasses
import os

from bench.harness import runner, spec
from bench.reference import dense_gqa
from repro.configs import get_config

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# the widest gap the test size allows: sound runs read under 1e-3 there,
# the float8 control 0.03-0.11 and an altered token 0.7-1.0 (CPU, three
# seeds each)
TEST_LIMIT = 0.01


def _cell(name: str) -> spec.Cell:
    """The cell as BENCHMARK.json lists it; a cell kept as files alone
    (its configuration and traffic mix) runs with every metric file."""
    bench = spec.benchmark()
    if any(w["name"] == name for w in bench["workloads"]):
        return spec.cell(name, bench)
    conf, mix = name.rsplit(".", 1)
    names = sorted(f[:-3] for f in os.listdir(os.path.join(
        spec.BENCH_DIR, "metrics")) if f.endswith(".py"))
    per = tuple({"name": n, "unit": spec.metric_module(n).UNIT}
                for n in names)
    e2e = ({"name": "ttft_p95_s", "unit": "s"},
           {"name": "tpot_p95_ms", "unit": "ms"},
           {"name": "setup_s", "unit": "s"})
    return spec.Cell(name, 1, spec.config(conf), spec.traffic(mix), e2e,
                     per, bench["run_seconds"])


def cell(name: str, *, sample: int = 4):
    c = _cell(name)
    cfg = dict(c.config)
    arch = get_config(cfg["program"]["arch"]).reduced()
    arch = dataclasses.replace(arch, **cfg["program"].get("arch_overrides",
                                                          {}))
    cfg.update(hidden_size=arch.d_model, num_attention_heads=arch.n_heads,
               num_key_value_heads=arch.kv_heads, head_dim=arch.head_dim,
               intermediate_size=arch.d_ff, vocab_size=arch.vocab,
               num_hidden_layers=arch.n_layers)
    cfg["correct"] = dict(cfg["correct"], sample_requests=sample,
                          max_logit_gap=TEST_LIMIT)
    mix = dict(c.traffic, ramp_s=1.0, ramp_rounds=3, strata=8,
               trace_offset_s=0.2,
               trace_s=1.0, drain_s=5.0)
    if mix["arrivals"]["kind"] == "closed":
        mix.update(prompt_tokens={"dist": "lognormal", "median": 16,
                                  "sigma": 0.8, "min": 8, "max": 48},
                   output_tokens={"dist": "lognormal", "median": 8,
                                  "sigma": 0.7, "min": 4, "max": 24},
                   max_new=32)
    else:
        mix.update(arrivals={"kind": "poisson", "rate_per_s": 4.0},
                   prompt_tokens={"dist": "lognormal", "median": 40,
                                  "sigma": 0.6, "min": 8, "max": 100},
                   output_tokens={"dist": "fixed", "value": 12}, max_new=12)
    ov = runner.Overrides(device=CPU, arch=arch, dims=dense_gqa.dims(cfg),
                          serve={"batch": 4, "max_len": 256,
                                 "prefill_chunk": 32})
    return dataclasses.replace(c, config=cfg, traffic=mix), ov
