"""Plain float32 reference of a dense decoder with grouped-query attention.

Written from the published model descriptions (Qwen2, arXiv:2407.10671;
StarCoder2, arXiv:2402.19173), with the departures that the configuration
file lists, and from nothing of the program under test: no import of it,
no weights, scales or tables it made.  The weights come from
:func:`make_weights`, which draws them from the run's seed.

Each layer:  x += Wo . attn(rope(Wq n1(x) + bq), rope(Wk n1(x) + bk),
Wv n1(x) + bv);  x += mlp(n2(x)),  with n = RMSNorm, causal softmax
attention in which each group of ``heads / kv_heads`` query heads reads
one key/value head, rotary embeddings on the two halves of each head
(the "rotate half" form), and the MLP either ``down(act(gate(h)) *
up(h))`` (gated) or ``down(act(up(h)))``.  Logits are the final-normed
hidden state times the tied embedding table.

Every product runs at ``Precision.HIGHEST`` in float32.  With ``quant`` =
``"fp8"`` every product's two operands are first rounded to float8 e4m3
with one scale per row (the control: the step below the bfloat16 that the
configurations state).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block (bounds the scores)
FP8_MAX = 448.0        # largest finite float8 e4m3 value


def dims(cfg: dict) -> dict:
    """The sizes the reference needs, read from a configuration file's
    published keys and its ``reference`` section."""
    ref = cfg["reference"]
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    eps = cfg.get("rms_norm_eps", cfg.get("norm_epsilon"))
    return {"layers": cfg["num_hidden_layers"], "d": d, "heads": h,
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim", d // h),
            "ffn": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "theta": float(cfg["rope_theta"]), "eps": float(eps),
            "act": ref["activation"], "gated": bool(ref["gated_mlp"]),
            "qkv_bias": bool(ref["qkv_bias"])}


def weight_shapes(m: dict) -> dict:
    """name -> (shape, fan-in or None for a norm scale / bias)."""
    L, d, h, kv = m["layers"], m["d"], m["heads"], m["kv_heads"]
    hd, f = m["head_dim"], m["ffn"]
    out = {"embed": ((m["vocab"], d), None),
           "ln1": ((L, d), None), "ln2": ((L, d), None),
           "wq": ((L, d, h, hd), d), "wk": ((L, d, kv, hd), d),
           "wv": ((L, d, kv, hd), d), "wo": ((L, h, hd, d), h * hd),
           "w_up": ((L, d, f), d), "w_down": ((L, f, d), f),
           "ln_f": ((d,), None)}
    if m["qkv_bias"]:
        out.update(bq=((L, h, hd), None), bk=((L, kv, hd), None),
                   bv=((L, kv, hd), None))
    if m["gated"]:
        out["w_gate"] = ((L, d, f), d)
    return out


def make_weights(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Random weights drawn from ``seed``, made on the default device in
    one jitted call, in ``dtype``.  Matrices are N(0, 1/fan_in), the
    embedding N(0, 0.02^2), biases N(0, 0.02^2), norm scales 1 + N(0,
    0.05^2)."""
    shapes = weight_shapes(m)
    names = sorted(shapes)

    def gen(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, n in zip(keys, names):
            shape, fan_in = shapes[n]
            z = jax.random.normal(k, shape, jnp.float32)
            if n.startswith("ln"):
                v = 1.0 + 0.05 * z
            elif fan_in is None:
                v = 0.02 * z
            else:
                v = z / math.sqrt(fan_in)
            out[n] = v.astype(dtype)
        return out
    return jax.jit(gen)(jax.random.key(seed))


def _fq(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, a_axis, b_axis, quant):
    if quant == "fp8":
        a, b = _fq(a, a_axis), _fq(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    """x [T, H, hd]; rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs            # [T, half]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _act(name, x):
    if name == "silu":
        return x * jax.nn.sigmoid(x)
    if name == "gelu_tanh":
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    raise ValueError(f"unknown activation {name!r}")


def _attention(q, k, v, quant):
    """q [T, H, hd], k/v [T, KV, hd] -> [T, H, hd]; causal, by blocks of
    query rows."""
    t, h, hd = q.shape
    kv = k.shape[1]
    g = h // kv
    qg = q.reshape(t, kv, g, hd) / math.sqrt(hd)
    outs = []
    for r0 in range(0, t, Q_BLOCK):
        qb = qg[r0:r0 + Q_BLOCK]
        s = _mm("qkgd,skd->kgqs", qb, k, -1, -1, quant)
        rows = r0 + jnp.arange(qb.shape[0])
        mask = jnp.arange(t)[None, :] <= rows[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(_mm("kgqs,skd->qkgd", p, v, -1, 0, quant))
    return jnp.concatenate(outs, axis=0).reshape(t, h, hd)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _logits(w, tokens, read, *, m, quant):
    mt = dict(m)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    pos = jnp.arange(tokens.shape[0])
    x = f32(w["embed"])[tokens]
    layer_names = [n for n in w if n not in ("embed", "ln_f")]

    def layer(x, lw):
        lw = {n: f32(a) for n, a in lw.items()}
        h = _rms(x, lw["ln1"], mt["eps"])
        q = _mm("td,dhe->the", h, lw["wq"], -1, 0, quant)
        k = _mm("td,dhe->the", h, lw["wk"], -1, 0, quant)
        v = _mm("td,dhe->the", h, lw["wv"], -1, 0, quant)
        if mt["qkv_bias"]:
            q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
        q, k = _rope(q, pos, mt["theta"]), _rope(k, pos, mt["theta"])
        a = _attention(q, k, v, quant)
        x = x + _mm("the,hed->td", a, lw["wo"], (-2, -1), (0, 1), quant)
        h = _rms(x, lw["ln2"], mt["eps"])
        u = _mm("td,df->tf", h, lw["w_up"], -1, 0, quant)
        if mt["gated"]:
            u = _act(mt["act"], _mm("td,df->tf", h, lw["w_gate"], -1, 0,
                                    quant)) * u
        else:
            u = _act(mt["act"], u)
        return x + _mm("tf,fd->td", u, lw["w_down"], -1, 0, quant), None

    x, _ = jax.lax.scan(layer, x, {n: w[n] for n in layer_names})
    h = _rms(x[read], f32(w["ln_f"]), mt["eps"])
    return _mm("nd,vd->nv", h, f32(w["embed"]), -1, -1, quant)


def logits_at(w: dict, m: dict, tokens, read, *, quant: str | None = None,
              pad_to: int = 512) -> jnp.ndarray:
    """Logits [len(read), vocab] of the sequence ``tokens`` at positions
    ``read``.  The sequence is padded at its end to a multiple of
    ``pad_to`` (later positions never reach earlier ones through the
    causal mask) and ``read`` to the same length, so that one program
    serves each padded length."""
    tokens = np.asarray(tokens, np.int32)
    n, r = len(tokens), len(read)
    padded = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
    padded[:n] = tokens
    rpad = np.zeros((len(padded),), np.int32)
    rpad[:r] = read
    return _logits(w, jnp.asarray(padded), jnp.asarray(rpad),
                   m=tuple(sorted(m.items())), quant=quant)[:r]
