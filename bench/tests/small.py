"""A cell of the benchmark cut to a size the CPU runs in seconds: the
configuration's own family at ``reduced()`` widths, a few slots, short
prompts and outputs, and the device check skipped.  For the tests only;
the command line never runs a cell this way."""
import dataclasses

from bench.harness import runner, spec
from repro.configs import get_config

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# the widest gap the test size allows: sound runs read under 1e-3 there,
# the float8 control 0.03-0.11 and an altered token 0.7-1.0 (CPU, three
# seeds each)
TEST_LIMIT = 0.01


def cell(name: str, *, sample: int = 4):
    """The cell as BENCHMARK.json lists it, cut to the test size, and the
    overrides that run it there."""
    c = spec.cell(name)
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
    # the sizes the reference runs come from the configuration's own
    # reference module, ``dims`` of the cut configuration
    ov = runner.Overrides(device=CPU, arch=arch,
                          serve={"batch": 4, "max_len": 256,
                                 "prefill_chunk": 32})
    return dataclasses.replace(c, config=cfg, traffic=mix), ov
