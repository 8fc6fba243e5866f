"""Compile the serving path's Pallas attention kernels for a described TPU
v5e chip at qwen2-0.5b geometry (14 query heads, 2 KV heads, head_dim 64,
page 16, bf16), with interpret mode off; and the serving programs that
run them (the paged decode loop and the paged join), to check that the
layer loop updates the KV pool in place.

Interpret mode does not enforce the TPU's block tiling or VMEM limits;
the chip's compiler does, and it is installed here: it compiles for a
chip that is described, not attached.  Nothing runs, so these tests say
nothing about results or times — only that Mosaic accepts each kernel,
that the paged kernels keep the custom-call names the roofline metrics
of ``bench/metrics`` match in a profiler trace, and what the compiled
serving programs do with the pool: no copy, slice or re-stack of it.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and every test worker imports this
file.
"""
import dataclasses
import functools
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attn import decode_attn_policy
from repro.kernels.decode_attn.kernel import decode_attn_kernel
from repro.kernels.paged_attn import page_shape, paged_attn, paged_prefill_attn
from repro.kernels.paged_attn.kernel import GRID_ORDERS, paged_attn_kernel
from repro.kernels.paged_attn.prefill_kernel import paged_prefill_attn_kernel
from repro.models.model_zoo import Model
from repro.serve.engine import (ServeConfig, jit_paged_decode_loop,
                                jit_paged_join)

B, HQ, HKV, D, PS, MAX_LEN = 8, 14, 2, 64, 16, 2048
G = HQ // HKV
PAGES = B * MAX_LEN // PS
LAYERS = 2


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


def _pool(pages=PAGES, d=D):
    """A stacked pool as stored: [LAYERS, pages, HKV, PS / f, f * d]."""
    return ((LAYERS, pages, HKV) + page_shape(PS, d), jnp.bfloat16)


LAYER = ((1,), jnp.int32)


@pytest.mark.parametrize("grid_order", GRID_ORDERS)
def test_paged_decode_compiles(one_chip, grid_order):
    _compile(functools.partial(paged_attn_kernel, interpret=False,
                               grid_order=grid_order), one_chip,
             ((B, HKV, G, D), jnp.bfloat16), _pool(), _pool(),
             ((B, MAX_LEN // PS), jnp.int32), ((B,), jnp.int32), LAYER)


@pytest.mark.parametrize("b,g,d,p_cap", [
    (128, 7, 64, 96),       # qwen2-0.5b.offline_long_output's decode step
    (32, 12, 128, 256),     # starcoder2-3b.code_completion's
])
def test_paged_decode_compiles_at_cell_geometry(one_chip, b, g, d, p_cap):
    """The block walk at the benchmark cells' decode geometries, with
    the default pages per block."""
    pool = _pool(b * p_cap, d)
    _compile(functools.partial(paged_attn_kernel, interpret=False),
             one_chip, ((b, HKV, g, d), jnp.bfloat16), pool, pool,
             ((b, p_cap), jnp.int32), ((b,), jnp.int32), LAYER)


@pytest.mark.parametrize("lq", [16, 1024])
@pytest.mark.parametrize("g,d", [(G, D), (12, 128)])
def test_paged_prefill_compiles(one_chip, lq, g, d):
    """Lq 1024 is past what one row block fits in VMEM: the default row
    block must split it.  At head_dim 64 the kernel reads the packed
    page (two tokens to a row) as stored; at 128 a row is a token."""
    _compile(functools.partial(paged_prefill_attn_kernel, g=g,
                               interpret=False), one_chip,
             ((B, HKV, lq * g, d), jnp.bfloat16), _pool(d=d), _pool(d=d),
             ((B, MAX_LEN // PS), jnp.int32), ((B,), jnp.int32),
             ((B,), jnp.int32), LAYER)


def test_paged_verify_compiles(one_chip):
    """Speculative verify: Lq = k+1 = 5, whose 35 fused rows are not a
    multiple of the sublane tile."""
    _compile(functools.partial(paged_prefill_attn_kernel, g=G,
                               interpret=False), one_chip,
             ((B, HKV, 5 * G, D), jnp.bfloat16), _pool(), _pool(),
             ((B, MAX_LEN // PS), jnp.int32), ((B,), jnp.int32),
             ((B,), jnp.int32), LAYER)


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


# --------------------------------------------------------------------------
# the serving programs: the KV pool is updated in place across the layers
# --------------------------------------------------------------------------

# the pool sizes the programs are compiled at: large enough that the
# compiler keeps the pools in HBM (smaller ones it may stage in VMEM
# with copies of its own), small enough to compile in seconds
POOL_SIZES = (4096, 8192)
SERVE_B, SERVE_LEN = 8, 512
PROGRAMS = [(arch, program) for arch in ("qwen2-0.5b", "starcoder2-3b")
            for program in ("loop", "join")]
# ops that move a whole pool or a layer's pool without computing on it
MOVES = ("copy", "dynamic-slice", "dynamic-update-slice", "slice",
         "transpose", "concatenate")
_COMPILED: dict = {}


def _serving_program(sharding, arch: str, program: str, pages: int):
    """The paged decode loop or the paged join of ``arch``'s attention
    geometry (qwen2-0.5b: head_dim 64, two tokens to a stored row;
    starcoder2-3b: head_dim 128, one) cut to two layers, on the kernel
    route, compiled with a pool of ``pages`` pages.  Returns ``(text,
    temp bytes, pool shape)``."""
    key = (arch, program, pages)
    if key in _COMPILED:
        return _COMPILED[key]
    cfg = dataclasses.replace(get_config(arch), n_layers=LAYERS, vocab=512,
                              d_ff=256)
    model = Model(cfg)
    scfg = ServeConfig(max_len=SERVE_LEN, batch=SERVE_B, page_size=PS,
                       total_pages=pages, attn_mode="kernel",
                       attn_interpret=False)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype), model.abstract_params(jnp.bfloat16))
    caches = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype), jax.eval_shape(
            lambda: model.init_paged_caches(SERVE_B, pages, PS,
                                            jnp.bfloat16)))
    b, mask = (SERVE_B,), spec((SERVE_B,), jnp.bool_)
    key_spec = spec((2,), jnp.uint32)
    table = spec((SERVE_B, SERVE_LEN // PS))
    if program == "loop":
        fn = jit_paged_decode_loop(model, scfg, steps=4, eos_id=None)
        args = (params, spec((SERVE_B, 1)), caches, spec(b), mask, spec(b),
                key_spec, table)
    else:
        fn = jit_paged_join(model, scfg, eos_id=None)
        args = (params, caches, spec((SERVE_B, 1)), spec(b), mask, spec(b),
                mask, spec((SERVE_B, 64)), spec(b), spec(b), key_spec, table,
                spec(b), mask)
    compiled = fn.lower(*args).compile()
    pool = jax.tree_util.tree_leaves(caches)[0].shape
    out = (compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes,
           pool)
    _COMPILED[key] = out
    return out


def _instructions(text: str):
    """``(name, opcode, result type, line)`` of every instruction, and the
    ROOT opcode of every computation by name."""
    roots, insts, comp = {}, [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) ", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
            continue
        m = re.match(r"^\s*(ROOT )?%(\S+) = (.+?) ([a-z][a-z0-9-]*)\(",
                     line)
        if not m:
            continue
        insts.append((m.group(2), m.group(4), m.group(3), line))
        if m.group(1):
            roots[comp] = m.group(4)
    return insts, roots


@pytest.mark.parametrize("arch,program", PROGRAMS)
def test_pool_updated_in_place(one_chip, arch, program):
    """No op of the compiled decode loop or join copies, slices, relays
    out or re-stacks the whole pool or one layer's pool; each layer's K
    and V writes are one scatter each into the carried, stacked pool;
    and the kernels keep the custom-call names the roofline metrics
    match."""
    text, _, pool = _serving_program(one_chip, arch, program,
                                     POOL_SIZES[0])
    # the pool and a layer of it, in whatever view the compiler takes
    sizes = {math.prod(pool), math.prod(pool[1:])}
    insts, roots = _instructions(text)
    moved, scatters = [], 0
    for name, op, result, line in insts:
        if not any(math.prod(map(int, filter(None, dims.split(","))))
                   in sizes for dims in re.findall(r"bf16\[([\d,]*)\]",
                                                   result)):
            continue
        root = roots.get(m.group(1)) if (
            m := re.search(r"calls=%([\w.-]+)", line)) else None
        if op == "scatter" or (op == "fusion" and root == "scatter"):
            scatters += op == "scatter"
        elif op in MOVES or op.endswith(("-start", "-done")) or (
                op == "fusion" and (root in MOVES
                                    or any(w in name for w in MOVES))):
            moved.append(f"{name} {op} {result}")
    assert not moved, moved
    assert scatters == 2, scatters          # K and V, in the layer loop
    calls = [ln for _, op, _, ln in insts if op == "custom-call"]
    metric = ("paged_decode_attn_roofline" if program == "loop"
              else "paged_prefill_attn_roofline")
    op = _op_pattern(metric)
    assert any(re.search(op, c.strip().removeprefix("ROOT "))
               for c in calls), calls


@pytest.mark.parametrize("arch,program", PROGRAMS)
def test_temporaries_do_not_grow_with_the_pool(one_chip, arch, program):
    """Doubling the pool moves the program's temporaries by less than one
    layer's pool: no temporary holds a copy of the pool or of a layer."""
    small, big = (_serving_program(one_chip, arch, program, pages)
                  for pages in POOL_SIZES)
    layer_bytes = 2 * math.prod(small[2][1:])
    assert abs(big[1] - small[1]) < layer_bytes, (small[1], big[1])
