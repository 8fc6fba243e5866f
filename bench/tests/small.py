"""A cell of the benchmark cut to a size the CPU runs in seconds: the
configuration cut by its own family (``small_cut``), a few slots, short
prompts and outputs, and the device check skipped.  For the tests only;
the command line never runs a cell this way."""
import dataclasses

from bench.harness import runner, spec

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# the widest gap the test size allows: sound runs read under 1e-3 there,
# the float8 control 0.03-0.11 and an altered token 0.7-1.0 (CPU, three
# seeds each)
TEST_LIMIT = 0.01


def cell(name: str, *, sample: int = 4):
    """The cell as BENCHMARK.json lists it, cut to the test size, and the
    overrides that run it there."""
    c = spec.cell(name)
    cfg, arch = spec.family_module(c.config).small_cut(c.config)
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
    # the runner checks the cut arch against ``dims`` of the cut
    # configuration, as it checks the file's at full size
    ov = runner.Overrides(device=CPU, arch=arch,
                          serve={"batch": 4, "max_len": 256,
                                 "prefill_chunk": 32})
    return dataclasses.replace(c, config=cfg, traffic=mix), ov
