"""GQA decode attention (flash-decode, split-KV) — the paper's regime.

One new token against a deep KV cache is the memory-bound skinny op that
the PIM-amenability test flags (op/byte ~ 1): the cache streams HBM->VMEM
once, the queries stay resident.  The kernel mirrors the pim-register
staging pattern: the grid walks KV blocks, an online-softmax accumulator
(m, l, acc) lives in VMEM scratch across the walk (registers staging an
open row), and the output is written once at the end.  The (B, Hkv) grid
dims are embarrassingly parallel (bank-level parallelism); the KV-block dim
streams (column walk within an open row).

Lengths are *per slot* ([B] int32, scalar-prefetched): each batch row may
sit at a different depth into the cache (continuous batching), and every
KV block past that slot's live length is skipped before any compute — the
paper's §5.1.2 command skipping applied at kernel-block granularity.  The
caller can additionally prune the grid itself by slicing the cache to a
host-known bound on the deepest live slot (see ops.decode_attn's s_cap).

Block shapes keep D on the 128-lane axis and the KV block on the sublane
axis (multiples of 8/16), so HBM reads are sequential full tiles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BS = 512    # KV rows per block


def _make_kernel(bs: int, hkv: int, d: int, scale: float):
    def kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        bi = pl.program_id(0)
        s = pl.program_id(1)
        ns = pl.num_programs(1)
        ln = len_ref[bi]

        @pl.when(s == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, -1e30)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        base = s * bs
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)

        # §5.1.2 command skipping: blocks past *this slot's* length do no
        # compute at all — the accumulator simply carries through.
        @pl.when(base < ln)
        def _():
            k_all = k_ref[0]                 # [BS, Hkv * D]
            v_all = v_ref[0]
            live = kpos < ln                 # [1, BS]
            for h in range(hkv):             # static: heads share the DMA
                q = q_ref[0, h]              # [G, D]
                k = k_all[:, h * d:(h + 1) * d]
                v = v_all[:, h * d:(h + 1) * d]
                scores = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # [G, BS]
                scores = jnp.where(live, scores, -1e30)
                m_prev = m_ref[h]            # [G, 1]
                m_new = jnp.maximum(m_prev,
                                    scores.max(axis=-1, keepdims=True))
                p = jnp.exp(scores - m_new)  # [G, BS]
                corr = jnp.exp(m_prev - m_new)
                l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
                acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                    p.astype(jnp.float32), v.astype(jnp.float32),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_new

        @pl.when(s == ns - 1)
        def _():
            o_ref[0] = (acc_ref[...]
                        / jnp.maximum(l_ref[...], 1e-30)
                        ).astype(o_ref.dtype)
    return kernel


def decode_attn_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       lengths: jnp.ndarray, *, bs: int = BS,
                       interpret: bool = True) -> jnp.ndarray:
    """q: [B, Hkv, G, D]; k/v: [B, S, Hkv, D]; lengths: [B] int32 per-slot
    live lengths."""
    b, hkv, g, d = q.shape
    s = k.shape[1]
    bs = min(bs, s)
    # merging the two minor axes is free (row-major) and makes one KV
    # block a dense [bs, Hkv * D] tile: its minor dim is the array's full
    # extent, which Mosaic accepts at any head count and head_dim
    k = k.reshape(b, s, hkv * d)
    v = v.reshape(b, s, hkv * d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, pl.cdiv(s, bs)),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), lambda bi, si, ln: (bi, 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv * d), lambda bi, si, ln: (bi, si, 0)),
            pl.BlockSpec((1, bs, hkv * d), lambda bi, si, ln: (bi, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d),
                               lambda bi, si, ln: (bi, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((hkv, g, 1), jnp.float32),
                        pltpu.VMEM((hkv, g, 1), jnp.float32),
                        pltpu.VMEM((hkv, g, d), jnp.float32)],
    )
    return pl.pallas_call(
        _make_kernel(bs, hkv, d, 1.0 / math.sqrt(d)), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret)(lengths, q, k, v)
