"""Compile the serving path's Pallas attention kernels for a described TPU
v5e chip at qwen2-0.5b geometry (14 query heads, 2 KV heads, head_dim 64,
page 16, bf16), with interpret mode off.

Interpret mode does not enforce the TPU's block tiling or VMEM limits;
the chip's compiler does, and it is installed here: it compiles for a
chip that is described, not attached.  Nothing runs, so these tests say
nothing about results or times — only that Mosaic accepts each kernel,
and that the paged kernels keep the custom-call names the roofline
metrics of ``bench/metrics`` match in a profiler trace.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and every test worker imports this
file.
"""
import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attn import decode_attn_policy
from repro.kernels.decode_attn.kernel import decode_attn_kernel
from repro.kernels.paged_attn import paged_attn, paged_prefill_attn
from repro.kernels.paged_attn.kernel import GRID_ORDERS, paged_attn_kernel
from repro.kernels.paged_attn.prefill_kernel import paged_prefill_attn_kernel

B, HQ, HKV, D, PS, MAX_LEN = 8, 14, 2, 64, 16, 2048
G = HQ // HKV
PAGES = B * MAX_LEN // PS


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of any cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _pool():
    return ((PAGES, HKV, PS, D), jnp.bfloat16)


@pytest.mark.parametrize("grid_order", GRID_ORDERS)
def test_paged_decode_compiles(one_chip, grid_order):
    _compile(functools.partial(paged_attn_kernel, interpret=False,
                               grid_order=grid_order), one_chip,
             ((B, HKV, G, D), jnp.bfloat16), _pool(), _pool(),
             ((B, MAX_LEN // PS), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("b,g,d,p_cap", [
    (128, 7, 64, 96),       # qwen2-0.5b.offline_long_output's decode step
    (32, 12, 128, 256),     # starcoder2-3b.code_completion's
])
def test_paged_decode_compiles_at_cell_geometry(one_chip, b, g, d, p_cap):
    """The block walk at the benchmark cells' decode geometries, with
    the default pages per block."""
    pool = ((b * p_cap, HKV, PS, d), jnp.bfloat16)
    _compile(functools.partial(paged_attn_kernel, interpret=False),
             one_chip, ((b, HKV, g, d), jnp.bfloat16), pool, pool,
             ((b, p_cap), jnp.int32), ((b,), jnp.int32))


@pytest.mark.parametrize("lq", [16, 1024])
def test_paged_prefill_compiles(one_chip, lq):
    """Lq 1024 is past what one row block fits in VMEM: the default row
    block must split it."""
    _compile(functools.partial(paged_prefill_attn_kernel, g=G,
                               interpret=False), one_chip,
             ((B, HKV, lq * G, D), jnp.bfloat16), _pool(), _pool(),
             ((B, MAX_LEN // PS), jnp.int32), ((B,), jnp.int32),
             ((B,), jnp.int32))


def test_paged_verify_compiles(one_chip):
    """Speculative verify: Lq = k+1 = 5, whose 35 fused rows are not a
    multiple of the sublane tile."""
    _compile(functools.partial(paged_prefill_attn_kernel, g=G,
                               interpret=False), one_chip,
             ((B, HKV, 5 * G, D), jnp.bfloat16), _pool(), _pool(),
             ((B, MAX_LEN // PS), jnp.int32), ((B,), jnp.int32),
             ((B,), jnp.int32))


def test_dense_decode_compiles(one_chip):
    cache = ((B, MAX_LEN, HKV, D), jnp.bfloat16)
    _compile(functools.partial(decode_attn_kernel, interpret=False),
             one_chip, ((B, HKV, G, D), jnp.bfloat16), cache, cache,
             ((B,), jnp.int32))


def _op_pattern(metric: str) -> str:
    """The ``OP`` pattern of ``bench/metrics/<metric>.py``."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"pinned_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.OP


def _custom_calls(fn, sharding, *shapes) -> list[str]:
    """The compiled program's custom-call instructions, named as the
    trace's ``XLA Ops`` line names them."""
    with decode_attn_policy(mode="kernel", interpret=False):
        text = _compile(fn, sharding, *shapes)
    lines = (ln.strip().removeprefix("ROOT ") for ln in text.splitlines())
    return [ln for ln in lines if "custom-call(" in ln]


def test_decode_kernel_op_name(one_chip):
    calls = _custom_calls(
        lambda q, k, v, t, n: paged_attn(q, k, v, t, n, interpret=False),
        one_chip, ((B, HQ, D), jnp.bfloat16), _pool(), _pool(),
        ((B, MAX_LEN // PS), jnp.int32), ((B,), jnp.int32))
    op = _op_pattern("paged_decode_attn_roofline")
    assert any(re.search(op, c) for c in calls), calls


def test_prefill_kernel_op_name(one_chip):
    calls = _custom_calls(
        paged_prefill_attn, one_chip, ((B, 32, HQ, D), jnp.bfloat16),
        _pool(), _pool(), ((B, MAX_LEN // PS), jnp.int32),
        ((B,), jnp.int32), ((B,), jnp.int32))
    op = _op_pattern("paged_prefill_attn_roofline")
    assert any(re.search(op, c) for c in calls), calls
