"""Public decode-attention op + the runtime routing policy.

``decode_attn`` accepts either one shared length or per-slot lengths
([B] int32) so a continuous-batching scheduler can keep mixed-depth
requests in one launch.  ``s_cap`` statically prunes the KV-block grid to
``cdiv(s_cap, bs)`` — a caller that knows a bound on the deepest live
slot passes it, so blocks past *every* slot's length are never launched
(§5.1.2 command skipping at grid granularity); per-slot skipping inside
the kernel handles the rest.

``DecodeAttnPolicy`` is how the model's attention layer decides, at trace
time, whether decode attention routes through this kernel and whether the
kernel runs interpreted.  ``interpret=None`` resolves by backend: off on
real TPU backends, on everywhere else (this is a Mosaic/TPU kernel — only
TPU can compile it).  ``mode="auto"`` routes through the kernel on TPU and
keeps the plain-XLA path elsewhere, where the interpreter's per-program
overhead would dominate the serving hot loop.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp

from .kernel import BS, decode_attn_kernel


@functools.partial(jax.jit, static_argnames=("bs", "interpret", "s_cap"))
def decode_attn(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                length: jnp.ndarray | int, *, bs: int = BS,
                interpret: bool = True,
                s_cap: int | None = None) -> jnp.ndarray:
    """q: [B, Hq, D] one-token queries; k/v: [B, S, Hkv, D] cache;
    slot b attends over the first ``length[b]`` cache rows (a scalar
    length is broadcast to every slot)."""
    b, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    if s_cap is not None and s_cap < k.shape[1]:
        k, v = k[:, :s_cap], v[:, :s_cap]
    ln = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (b,))
    out = decode_attn_kernel(qg, k, v, ln, bs=bs, interpret=interpret)
    return out.reshape(b, hq, d)


# --------------------------------------------------------------------------
# routing policy (read by repro.models.attention at trace time)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecodeAttnPolicy:
    mode: str = "auto"              # "kernel" | "xla" | "auto"
    interpret: bool | None = None   # None -> auto (CPU interprets)
    block_size: int = BS
    use_tuned: bool = True          # consult the autotuned-shape cache
    tuned_path: str | None = None   # None -> committed default (env wins)

    def __post_init__(self):
        # resolve the tuned-shape table once, at policy construction —
        # ops then do a dict lookup per call shape, never file I/O.
        # A missing/corrupt cache (or REPRO_TUNED_SHAPES=off) degrades
        # to the hand-picked defaults; it must never break routing.
        entries: dict = {}
        if self.use_tuned:
            try:
                from ..paged_attn.autotune import load_entries
                entries = load_entries(self.tuned_path)
            except Exception:
                entries = {}
        object.__setattr__(self, "_tuned", entries)

    def resolve_interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return jax.default_backend() != "tpu"

    def tuned_config(self, op: str, *, hq: int, hkv: int, d: int,
                     page_size: int, lg: int | None = None) -> dict | None:
        """The tuned launch config for ``(backend, op, geometry)``, or
        None on a cache miss.  ``block_rows`` is sanitized against the
        caller's fused row count ``lg`` (entries are keyed without Lq,
        so a tuned row tiling is dropped when it does not divide this
        call's rows), ``pages_per_block`` to a positive int; a malformed
        entry degrades field-by-field."""
        ent = self._tuned.get(f"{jax.default_backend()}|{op}|"
                              f"hq{hq}.hkv{hkv}.d{d}.ps{page_size}")
        if not isinstance(ent, dict):
            return None
        cfg = dict(ent.get("config") or {})
        if cfg.get("grid_order") not in ("bh", "hb"):
            cfg.pop("grid_order", None)
        br = cfg.get("block_rows")
        if br is not None and (not isinstance(br, int) or br <= 0
                               or lg is None or lg % br):
            cfg.pop("block_rows", None)
        ppb = cfg.get("pages_per_block")
        if ppb is not None and (type(ppb) is not int or ppb <= 0):
            cfg.pop("pages_per_block", None)
        return cfg or None

    def kernel_wanted(self) -> bool:
        if self.mode == "kernel":
            return True
        if self.mode == "xla":
            return False
        # auto: only TPU compiles this Mosaic kernel; everywhere else the
        # interpreter would sit in the hot loop, so stay on the XLA path
        return jax.default_backend() == "tpu"


_ACTIVE = DecodeAttnPolicy()


def active_policy() -> DecodeAttnPolicy:
    return _ACTIVE


@contextlib.contextmanager
def decode_attn_policy(**kw):
    """Override the decode-attention routing policy for code traced inside
    this context (jit caches must key on anything that varies)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = dataclasses.replace(prev, **{k: v for k, v in kw.items()
                                           if v is not None
                                           or k == "interpret"})
    try:
        yield _ACTIVE
    finally:
        _ACTIVE = prev
