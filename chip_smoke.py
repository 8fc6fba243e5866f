"""Bring-up check on one TPU chip: qwen2-0.5b served at its published
widths through the normal entry point, with the Pallas attention kernels
compiled by Mosaic.

    python chip_smoke.py        # on a machine with one TPU chip

Phases, all in this one process:

(a) device check — exits non-zero unless JAX's first device is a TPU;
(b) the paged decode (both grid orders), prefill (Lq 16 and 1024) and
    verify (Lq 5) kernels at qwen2-0.5b geometry against the ``ref.py``
    oracles;
(c) ``repro.launch.serve.run`` at published widths with random weights
    from ``--seed``, bf16, greedy: paged, paged with ``speculate_k=4``,
    and paged on ``attn_mode="xla"`` — 16 requests with prompts of
    32-1536 tokens, so that joins reach the 1024 and 2048 buckets.  The
    first wave's prefill and first-decode logits are compared between
    the kernel and XLA routes over the model's dense caches and over the
    paged pool, and the greedy-token agreement with the XLA run is
    printed.

Any failed check raises, so the script exits non-zero without printing
its last line, ``{"ok": true, "device": {...}}``.  The tok/s it prints
come from one cold run with compilation included; they are not a
benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ARCH = "qwen2-0.5b"
SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
# kernel vs oracle (phase b): both sides see the same bf16 inputs; the
# kernel accumulates in f32 by pages, the oracle in one f32 softmax, and
# the output is rounded to bf16 (2^-8 relative) — 3e-2 allows a few ulps
# of the O(1) outputs, and a wrong page or mask gives O(1) errors
KERNEL_TOL = 3e-2
# kernel route vs XLA route logits (phase c), as ||a - b|| / ||b|| per row.
# The routes differ only in attention arithmetic (the XLA route rounds the
# softmax weights to bf16 before PV), which leaves bf16 noise in the
# logits: with this arithmetic on the CPU it measured 0.014 at 2 layers
# and 0.025 at 24 (d_model 64; width changed it little), so 0.05 is twice
# the noise.  An off-by-one attention mask in the paged kernels measured
# 0.53-0.65 there, an order of magnitude above the limit.
LOGITS_RTOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of one smoke run (``main`` uses the defaults)."""
    reduced: bool = False
    batch: int = 8
    max_len: int = 2048
    page_size: int = 16
    requests: int = 16
    prompt_range: tuple[int, int] = (32, 1536)
    max_new: int = 32
    speculate_k: int = 4
    prefill_lqs: tuple[int, ...] = (16, 1024)
    widths: tuple[int, ...] = (1024, 2048)   # join buckets to reach
    attn_mode: str = "auto"
    seed: int = 0


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _import_repro() -> None:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {SRC}")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def device_check() -> dict:
    """Phase (a): the device as JAX reports it; exits unless it is a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform!r}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _assert_close(name: str, out, ref, tol: float) -> float:
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.max(np.abs(out - ref) - tol * np.abs(ref)))
    if out.shape != ref.shape or not np.isfinite(out).all() or err > tol:
        raise AssertionError(f"{name}: kernel disagrees with the oracle "
                             f"(max excess {err:.3g} over atol=rtol={tol})")
    return float(np.max(np.abs(out - ref)))


def check_kernels(cfg, plan: Plan, *, interpret: bool) -> None:
    """Phase (b): each paged kernel against its ``ref.py`` oracle at the
    model's attention geometry, on a permuted pool that fills the
    table."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attn import (pack_pages, paged_attn,
                                          paged_attn_ref,
                                          paged_prefill_attn_pallas,
                                          paged_prefill_attn_ref)
    from repro.kernels.paged_attn.kernel import GRID_ORDERS
    hq, hkv, d = cfg.n_heads, cfg.kv_heads, cfg.resolved_head_dim
    b, ps = plan.batch, plan.page_size
    p_max = plan.max_len // ps
    n = b * p_max
    rng = np.random.default_rng(plan.seed)

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    # two layers' pools as stored; the ops read the second
    k, v = (pack_pages(normal((2, n, hkv, ps, d))) for _ in range(2))
    table = jnp.asarray(rng.permutation(n).reshape(b, p_max)
                        .astype(np.int32))
    q = normal((b, hq, d))
    ln = jnp.asarray(rng.integers(1, plan.max_len + 1, size=b), jnp.int32)
    ref = paged_attn_ref(q, k, v, table, ln, 1)
    for order in GRID_ORDERS:
        out = paged_attn(q, k, v, table, ln, 1, interpret=interpret,
                         grid_order=order)
        err = _assert_close(f"decode/{order}", out, ref, KERNEL_TOL)
        _say(f"kernel decode grid_order={order}: max |err| {err:.3g}")
    cases = [("prefill", lq) for lq in plan.prefill_lqs]
    cases.append(("verify", plan.speculate_k + 1))
    for op, lq in cases:
        q = normal((b, lq, hq, d))
        off = jnp.asarray(rng.integers(0, plan.max_len - lq + 1, size=b),
                          jnp.int32)
        out = paged_prefill_attn_pallas(q, k, v, table, off, off + lq, 1,
                                        interpret=interpret)
        ref = paged_prefill_attn_ref(q, k, v, table, off, off + lq, 1)
        err = _assert_close(f"{op}/Lq={lq}", out, ref, KERNEL_TOL)
        _say(f"kernel {op} Lq={lq}: max |err| {err:.3g}")


def _first_logits(model, params, prompts, plan: Plan, *, paged: bool,
                  mode: str, interpret: bool | None):
    """Prefill logits at each prompt's last token and the logits of one
    decode step after it, on one attention route.  Returns
    ``(prefill, decode, compiled_text)``; the decode step's input token
    is the XLA route's prefill argmax, so both routes decode the same
    token."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attn import decode_attn_policy
    b = len(prompts)
    width = min(plan.max_len, 1 << (max(map(len, prompts)) - 1).bit_length())
    toks = np.zeros((b, width), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    plens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    p_max = plan.max_len // plan.page_size
    dt = jnp.bfloat16

    def step(params, toks, plens, nxt):
        if paged:
            table = jnp.arange(b * p_max, dtype=jnp.int32).reshape(b, p_max)
            caches = model.init_paged_caches(b, b * p_max, plan.page_size,
                                             dt)
            lg0, caches = model.prefill_paged(
                params, {"tokens": toks}, caches, table, dtype=dt,
                last_pos=plens - 1)
            lg1, _ = model.decode_step(params, nxt[:, None], caches, plens,
                                       dtype=dt, pages=table)
        else:
            lg0, caches = model.prefill(params, {"tokens": toks},
                                        plan.max_len, dtype=dt,
                                        last_pos=plens - 1)
            lg1, _ = model.decode_step(params, nxt[:, None], caches, plens,
                                       dtype=dt)
        return (lg0[:, -1].astype(jnp.float32),
                lg1[:, -1].astype(jnp.float32))

    with decode_attn_policy(mode=mode, interpret=interpret):
        fn = jax.jit(step)
        args = (params, jnp.asarray(toks), plens,
                jnp.zeros((b,), jnp.int32))
        text = fn.lower(*args).compile().as_text()
        return fn, args, text


def compare_routes(model, params, prompts, plan: Plan, *, paged: bool,
                   interpret: bool | None, on_tpu: bool) -> dict:
    """Kernel route vs XLA route: prefill and first-decode logits of the
    same prompts, held to ``LOGITS_RTOL``; on a TPU the kernel route's
    compiled program must hold a Mosaic custom call and the XLA route's
    none.  ``out["mosaic_calls"]`` counts the kernel route's."""
    import jax.numpy as jnp
    import numpy as np
    fx, ax, tx = _first_logits(model, params, prompts, plan, paged=paged,
                               mode="xla", interpret=None)
    fk, ak, tk = _first_logits(model, params, prompts, plan, paged=paged,
                               mode="kernel", interpret=interpret)
    mosaic = tk.count("tpu_custom_call")
    if on_tpu and (not mosaic or "tpu_custom_call" in tx):
        raise AssertionError("kernel route is not the only one with a "
                             "Mosaic kernel in its compiled program")
    pre_x, _ = fx(*ax)
    nxt = jnp.argmax(pre_x, axis=-1).astype(jnp.int32)
    pre_x, dec_x = (np.asarray(a) for a in fx(*ax[:3], nxt))
    pre_k, dec_k = (np.asarray(a) for a in fk(*ak[:3], nxt))
    out = {"mosaic_calls": mosaic}
    for name, kl, xl in (("prefill", pre_k, pre_x), ("decode", dec_k, dec_x)):
        if not (np.isfinite(kl).all() and np.isfinite(xl).all()):
            raise AssertionError(f"{name} logits are not finite")
        rel = float(np.max(np.linalg.norm(kl - xl, axis=-1)
                           / np.linalg.norm(xl, axis=-1)))
        agree = int(np.sum(kl.argmax(-1) == xl.argmax(-1)))
        if rel > LOGITS_RTOL:
            raise AssertionError(f"{name} logits: kernel vs XLA route "
                                 f"relative error {rel:.3g} > {LOGITS_RTOL}")
        out[name] = {"rel_err": rel, "argmax_agree": agree,
                     "rows": int(kl.shape[0])}
    return out


def serve(plan: Plan, lens: list[int], **kw) -> dict:
    """One served run through ``repro.launch.serve.run`` with the paged
    attention route counters on; checks every request completed."""
    from repro.kernels.paged_attn import attn_telemetry
    from repro.launch.serve import run
    tel = attn_telemetry()
    tel.reset()
    tel.enable()
    try:
        kw.setdefault("attn_mode", plan.attn_mode)
        out = run(ARCH, reduced=plan.reduced, batch=plan.batch,
                  max_len=plan.max_len, page_size=plan.page_size,
                  max_new=plan.max_new, seed=plan.seed, prompt_lens=lens,
                  **kw)
        routes = {k: v["traced_calls"] + v["calls"]
                  for k, v in tel.snapshot().items()}
    finally:
        tel.disable()
        tel.reset()
    res = out["results"]
    if sorted(res) != list(range(len(lens))) or any(
            len(t) != plan.max_new for t in res.values()):
        raise AssertionError("not every request was served in full")
    out["routes"] = routes
    return out


def _agreement(a: dict, b: dict) -> tuple[int, int, int]:
    """(tokens equal before the first divergence, tokens, requests equal
    in full) between two runs' greedy outputs."""
    same = total = full = 0
    for rid, ta in a.items():
        tb = b[rid]
        n = next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y),
                 len(ta))
        same, total, full = same + n, total + len(ta), full + (n == len(ta))
    return same, total, full


def serve_phase(plan: Plan, *, interpret: bool | None,
                on_tpu: bool) -> dict:
    """Phase (c): the three served runs, the route checks and the
    kernel-vs-XLA logits comparison of the first wave."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import param as pm
    from repro.models.model_zoo import Model
    rng = np.random.default_rng(plan.seed)
    lo, hi = plan.prompt_range
    # longest first: the first wave fills the top join bucket and the
    # second, shorter one the bucket below it
    lens = sorted(rng.integers(lo, hi + 1, size=plan.requests).tolist(),
                  reverse=True)
    _say(f"prompt lengths: {lens}")
    runs = {"paged": {},
            f"paged+spec{plan.speculate_k}": dict(
                speculate_k=plan.speculate_k),
            "paged/xla": dict(attn_mode="xla")}
    outs = {}
    for name, kw in runs.items():
        t0 = time.perf_counter()
        out = outs[name] = serve(plan, lens, **kw)
        dt = time.perf_counter() - t0
        # the verify step calls the paged prefill op at Lq = k+1, so a
        # speculative run records prefill calls only; its verify steps
        # are counted by the scheduler
        want = {"prefill"} | (set() if kw.get("speculate_k") else
                              {"decode"})
        route = "xla" if kw.get("attn_mode") == "xla" else "kernel"
        if kw.get("speculate_k") and not out["spec"]["steps"]:
            raise AssertionError(f"{name}: no verify step ran")
        seen = {k.split(".")[0] for k in out["routes"]}
        bad = [k for k in out["routes"] if not k.endswith("." + route)]
        if not want <= seen or bad:
            raise AssertionError(f"{name}: attention routes "
                                 f"{out['routes']}, wanted {route}")
        widths = out["join"]["widths"]
        if not set(plan.widths) <= set(widths):
            raise AssertionError(f"{name}: join widths {widths} miss "
                                 f"{plan.widths}")
        toks = sum(len(t) for t in out["results"].values())
        _say(f"serve {name}: {len(out['results'])} requests, {toks} tokens "
             f"in {out['seconds']:.3f}s = {toks / out['seconds']:.1f} tok/s "
             f"(one cold run, compilation included; not a benchmark); "
             f"join widths {widths}; routes {out['routes']}; "
             f"phase wall {dt:.1f}s")
    ref = outs["paged/xla"]["results"]
    for name in runs:
        if name != "paged/xla":
            same, total, full = _agreement(outs[name]["results"], ref)
            _say(f"greedy-token agreement {name} vs paged/xla: {same}/{total}"
                 f" tokens before first divergence, {full}/{len(ref)} "
                 "requests identical")
    cfg = get_config(ARCH)
    if plan.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    params = pm.unwrap(jax.jit(model.init)(jax.random.key(plan.seed)))
    prompts = [outs["paged"]["prompts"][r] for r in range(plan.batch)]
    for paged in (False, True):
        t0 = time.perf_counter()
        cmp = compare_routes(model, params, prompts, plan, paged=paged,
                             interpret=interpret, on_tpu=on_tpu)
        mosaic = cmp.pop("mosaic_calls")
        _say(f"logits kernel vs xla ({'paged' if paged else 'dense'}): "
             + ", ".join(f"{k} rel err {v['rel_err']:.3g} (argmax agree "
                         f"{v['argmax_agree']}/{v['rows']})"
                         for k, v in cmp.items())
             + f" (tolerance {LOGITS_RTOL}); kernel route compiled with "
             f"{mosaic} Mosaic custom call(s); "
             f"{time.perf_counter() - t0:.1f}s")
    return outs


def _footprint(plan: Plan) -> tuple[int, int]:
    """(f32 parameter bytes, paged pool bytes) from shapes alone."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import param as pm
    from repro.models.model_zoo import Model
    from repro.models.transformer import init_paged_caches
    cfg = get_config(ARCH)
    if plan.reduced:
        cfg = cfg.reduced()
    pages = plan.batch * (plan.max_len // plan.page_size)

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree))
    params = pm.unwrap(Model(cfg).abstract_ptree())
    pool = jax.eval_shape(lambda: init_paged_caches(
        cfg, plan.batch, pages, plan.page_size, jnp.bfloat16))
    return nbytes(params), nbytes(pool)


def main(argv: list[str] | None = None, plan: Plan | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)
    plan = plan or Plan(seed=args.seed)
    _import_repro()
    t_start = time.perf_counter()
    dev = device_check()
    import jax
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    compiles = {"n": 0, "s": 0.0}

    def on_compile(event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["s"] += secs
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    _say(f"device: {dev['kind']} ({dev['platform']}, {dev['count']} "
         f"device(s)); compile cache {enable_compile_cache()}")
    pbytes, kvbytes = _footprint(plan)
    _say(f"{ARCH}{' (reduced)' if plan.reduced else ''}: f32 parameters "
         f"{pbytes / 1e9:.3f} GB, paged KV pool {kvbytes / 1e9:.3f} GB")
    on_tpu = dev["platform"] == "tpu"
    interpret = None if on_tpu else True
    cfg = get_config(ARCH)
    if plan.reduced:
        cfg = cfg.reduced()
    phases = {}
    t0 = time.perf_counter()
    check_kernels(cfg, plan, interpret=not on_tpu)
    phases["kernels"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    serve_phase(plan, interpret=interpret, on_tpu=on_tpu)
    phases["serve"] = time.perf_counter() - t0
    for name, secs in phases.items():
        _say(f"phase {name}: {secs:.1f}s wall")
    stats = jax.devices()[0].memory_stats() or {}
    _say(f"total {time.perf_counter() - t_start:.1f}s wall; "
         f"{compiles['n']} backend compilations ({compiles['s']:.1f}s); "
         f"peak device memory "
         f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return dev


if __name__ == "__main__":
    main()
