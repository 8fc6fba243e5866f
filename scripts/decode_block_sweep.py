"""Sweep the paged decode kernel's ``pages_per_block`` on a TPU.

Times one decode-attention call (one layer) at the two benchmark cells'
decode geometries, for each ``pages_per_block`` given, with per-slot
lengths drawn like the cells' live depths:

* ``qwen2``: B 128, Hkv 2, G 7, D 64, a table of 96 pages — prompt
  (lognormal, median 128, sigma 0.8, in [32, 512]) plus a uniform share
  of an output (median 256, sigma 0.7, in [32, 1024]);
* ``starcoder2``: B 32, Hkv 2, G 12, D 128, a table of 256 pages —
  prompt (median 1536, sigma 0.6, in [256, 3584]) plus up to 64 tokens.

Each point runs the kernel ``--reps`` times inside one jitted loop (each
call's query depends on the last output, so no call is hoisted) and
reports the mean time per call, the HBM bytes of the live K/V pages it
needs, and that traffic's share of the v5e's 819 GB/s.
``--baseline-src DIR`` also times the ``paged_attn_kernel`` of another
checkout's ``src`` (a kernel without ``pages_per_block``) on the same
inputs.  Prints one JSON line per point and exits non-zero off a TPU.

    python scripts/decode_block_sweep.py [--ppb 1,4,8,16,32] [--reps 200]
        [--baseline-src DIR] [--seed 0]
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.kernels.paged_attn.kernel import (  # noqa: E402
    default_pages_per_block, paged_attn_kernel)

HBM_GBPS = 819.0             # TPU v5e, Google Cloud "TPU v5e" documentation
PS = 16
GEOMETRIES = {
    "qwen2": dict(b=128, hkv=2, g=7, d=64, p_cap=96),
    "starcoder2": dict(b=32, hkv=2, g=12, d=128, p_cap=256),
}


def _lognormal(rng, median, sigma, lo, hi, n):
    return np.clip(np.rint(median * np.exp(sigma * rng.standard_normal(n))),
                   lo, hi).astype(np.int64)


def lengths(name: str, b: int, rng) -> np.ndarray:
    if name == "qwen2":
        prompt = _lognormal(rng, 128, 0.8, 32, 512, b)
        out = _lognormal(rng, 256, 0.7, 32, 1024, b)
        return prompt + np.floor(rng.random(b) * out).astype(np.int64)
    prompt = _lognormal(rng, 1536, 0.6, 256, 3584, b)
    return prompt + rng.integers(0, 65, b)


def inputs(name: str, seed: int):
    geo = GEOMETRIES[name]
    b, hkv, g, d, p_cap = (geo[k] for k in ("b", "hkv", "g", "d", "p_cap"))
    rng = np.random.default_rng(seed)
    ln = np.minimum(lengths(name, b, rng), p_cap * PS)
    n = b * p_cap
    tbl = rng.permutation(n).reshape(b, p_cap).astype(np.int32)
    key = jax.random.key(seed)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, hkv, g, d), jnp.bfloat16)
    k = jax.random.normal(kk, (n, hkv, PS, d), jnp.bfloat16)
    v = jax.random.normal(kv, (n, hkv, PS, d), jnp.bfloat16)
    live_pages = int(np.sum(-(-ln // PS)))
    need = live_pages * PS * hkv * d * 2 * 2 + 2 * b * hkv * g * d * 2
    return (q, k, v, jnp.asarray(tbl), jnp.asarray(ln, jnp.int32)), need


def timed(kernel, args, reps: int) -> float:
    """Mean seconds per call of ``kernel(*args)`` over ``reps`` calls in
    one jitted loop."""
    q, k, v, tbl, ln = args

    @jax.jit
    def loop(q):
        def body(_, qc):
            out = kernel(qc, k, v, tbl, ln)
            return qc + (out * 0).astype(qc.dtype)
        return jax.lax.fori_loop(0, reps, body, q)
    jax.block_until_ready(loop(q))              # compile and warm up
    t0 = time.perf_counter()
    jax.block_until_ready(loop(q))
    return (time.perf_counter() - t0) / reps


def _baseline(src: str):
    path = os.path.join(src, "repro", "kernels", "paged_attn", "kernel.py")
    spec = importlib.util.spec_from_file_location("baseline_kernel", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.paged_attn_kernel


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ppb", default="1,4,8,16,32")
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--geometries", default="qwen2,starcoder2")
    ap.add_argument("--baseline-src", default=None)
    a = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("decode_block_sweep: no TPU", file=sys.stderr)
        return 1
    base = _baseline(a.baseline_src) if a.baseline_src else None
    for name in a.geometries.split(","):
        args, need = inputs(name, a.seed)
        p_cap = GEOMETRIES[name]["p_cap"]
        points = [("default", default_pages_per_block(PS, p_cap))]
        points += [(str(p), int(p)) for p in a.ppb.split(",")]
        if base is not None:
            points.append(("baseline", None))
        for label, ppb in points:
            if label == "baseline":
                def kern(*xs):
                    return base(*xs, interpret=False)
            else:
                def kern(*xs, ppb=ppb):
                    return paged_attn_kernel(*xs, interpret=False,
                                             pages_per_block=ppb)
            s = timed(kern, args, a.reps)
            print(json.dumps({
                "geometry": name, "pages_per_block": label, "ppb": ppb,
                "us_per_call": round(s * 1e6, 3), "need_bytes": need,
                "hbm_roofline_pct": round(100 * need / HBM_GBPS / 1e9 / s,
                                          4),
                "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
