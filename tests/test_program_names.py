"""The program names the benchmark's per-layer metrics find in a profiler
trace.

``bench/metrics/*_mfu.py`` match the serving programs by module name
(``jit_join(...)``, ``jit_loop(...)``).  A rename in the program would
turn those metrics null without failing anything; these tests fail
instead.  The programs are lowered on the CPU at a reduced size.  The
attention kernels' custom-call names, which the roofline metrics match,
are pinned in ``test_tpu_compile.py``, the one file that describes a TPU.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import param as pm
from repro.models.model_zoo import Model
from repro.serve.engine import ServeConfig
from repro.serve.scheduler import Batcher

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench", "metrics")


def _pattern(metric: str, attr: str) -> str:
    spec = importlib.util.spec_from_file_location(
        f"pinned_{metric}", os.path.join(METRICS, f"{metric}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def _trace_name(lowered) -> str:
    """The name a program's events carry in the trace's ``XLA Modules``
    line: the module name, then the program's id in parentheses."""
    (name,) = re.findall(r"^module @(\S+)", lowered.as_text(), re.M)
    return f"{name}(1234)"


@pytest.fixture(scope="module")
def batcher():
    def make(**kw):
        cfg = get_config("qwen2-0.5b").reduced()
        model = Model(cfg)
        params = pm.unwrap(model.init(jax.random.key(0)))
        return Batcher(model, params, ServeConfig(
            max_len=64, batch=4, dtype=jnp.float32, sync_every=2,
            page_size=8, **kw))
    return make


def test_join_program_name(batcher):
    b = batcher()
    n, w = b.cfg.batch, 16
    lowered = b._join.lower(
        b.params, b.caches, b.tok, b.lengths, b.done, b.remaining,
        jnp.zeros((n,), bool), jnp.zeros((n, w), jnp.int32),
        jnp.ones((n,), jnp.int32), jnp.full((n,), 4, jnp.int32), b.key,
        jnp.asarray(b.pool.table), jnp.zeros((n,), jnp.int32),
        jnp.zeros((n,), bool))
    assert re.search(_pattern("prefill_mfu", "PROGRAM"),
                     _trace_name(lowered))


@pytest.mark.parametrize("spec_k", [None, 2])
def test_decode_loop_program_name(batcher, spec_k):
    b = batcher(speculate_k=spec_k)
    cap = 4
    pages = jnp.asarray(b.pool.table[:, :cap])
    args = (b.params, b.tok, b.caches, b.lengths, b.done, b.remaining, b.key)
    if spec_k:
        args += (jnp.asarray(b.history),)
    lowered = b._loop(2, cap).lower(*args, pages)
    assert re.search(_pattern("decode_mfu", "PROGRAM"), _trace_name(lowered))
