"""Plain float32 reference of a decoder with grouped-query attention and
routed experts, in the DeepSeek-MoE layout: the first
``first_k_dense_replace`` layers have a dense gated MLP; each later one a
softmax router over ``n_routed_experts`` gated experts of width
``moe_intermediate_size``, of which every token takes the
``num_experts_per_tok`` most probable, weighted by their renormalised
probabilities, plus a shared gated MLP of ``n_shared_experts`` experts'
width that every token takes.  The output head is not tied.  Attention,
norms and rotary embeddings are those of ``bench.reference.dense_gqa``.

A fixture of the harness's tests (``test_registry.py``), which copy it to
``bench/reference/routed_gqa.py`` of a copied tree; it imports nothing
of the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense_gqa as dense


def dims(cfg: dict) -> dict:
    m = dense.dims(cfg)
    m.update(dense_layers=cfg["first_k_dense_replace"],
             experts=cfg["n_routed_experts"],
             top_k=cfg["num_experts_per_tok"],
             expert_ffn=cfg["moe_intermediate_size"],
             shared=cfg["n_shared_experts"])
    return m


def weight_shapes(m: dict) -> dict:
    """name -> (shape, fan-in or None for a norm scale); names start with
    the group of layers they stack (``dense.``, ``moe.``)."""
    d, h, kv, hd = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    e, fe, fs = m["experts"], m["expert_ffn"], m["shared"] * m["expert_ffn"]
    # the untied head drawn as the embedding table is (the dense
    # reference's head is that table), so the logits have its scale
    out = {"embed": ((m["vocab"], d), None), "head": ((d, m["vocab"]), None),
           "ln_f": ((d,), None)}
    groups = {"dense": m["dense_layers"], "moe": m["layers"]
              - m["dense_layers"]}
    for g, n in groups.items():
        out.update({f"{g}.ln1": ((n, d), None), f"{g}.ln2": ((n, d), None),
                    f"{g}.wq": ((n, d, h, hd), d),
                    f"{g}.wk": ((n, d, kv, hd), d),
                    f"{g}.wv": ((n, d, kv, hd), d),
                    f"{g}.wo": ((n, h, hd, d), h * hd)})
    out.update({"dense.w_gate": ((groups["dense"], d, m["ffn"]), d),
                "dense.w_up": ((groups["dense"], d, m["ffn"]), d),
                "dense.w_down": ((groups["dense"], m["ffn"], d), m["ffn"]),
                "moe.router": ((groups["moe"], d, e), d),
                "moe.e_gate": ((groups["moe"], e, d, fe), d),
                "moe.e_up": ((groups["moe"], e, d, fe), d),
                "moe.e_down": ((groups["moe"], e, fe, d), fe),
                "moe.s_gate": ((groups["moe"], d, fs), d),
                "moe.s_up": ((groups["moe"], d, fs), d),
                "moe.s_down": ((groups["moe"], fs, d), fs)})
    return out


def make_weights(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Drawn from ``seed`` on the default device in one jitted call, as
    ``dense_gqa.make_weights`` draws them."""
    shapes = weight_shapes(m)
    names = sorted(shapes)

    def gen(key):
        out = {}
        for k, n in zip(jax.random.split(key, len(names)), names):
            shape, fan_in = shapes[n]
            z = jax.random.normal(k, shape, jnp.float32)
            if ".ln" in n or n == "ln_f":
                v = 1.0 + 0.05 * z
            elif fan_in is None:
                v = 0.02 * z
            else:
                v = z / math.sqrt(fan_in)
            out[n] = v.astype(dtype)
        return out
    return jax.jit(gen)(jax.random.key(seed))


def _gated(h, gate, up, down, act, quant):
    g = dense._mm("td,df->tf", h, gate, -1, 0, quant)
    u = dense._mm("td,df->tf", h, up, -1, 0, quant)
    return dense._mm("tf,fd->td", dense._act(act, g) * u, down, -1, 0, quant)


def _experts(h, lw, m, quant):
    p = jax.nn.softmax(dense._mm("td,de->te", h, lw["router"], -1, 0,
                                 quant), axis=-1)
    top, idx = jax.lax.top_k(p, m["top_k"])
    top = top / top.sum(-1, keepdims=True)
    gates = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None],
                                 idx].set(top)
    g = dense._mm("td,edf->tef", h, lw["e_gate"], -1, 1, quant)
    u = dense._mm("td,edf->tef", h, lw["e_up"], -1, 1, quant)
    y = dense._mm("tef,efd->ted", dense._act(m["act"], g) * u, lw["e_down"],
                  -1, 1, quant)
    return jnp.einsum("te,ted->td", gates, y, precision=dense.HIGHEST) \
        + _gated(h, lw["s_gate"], lw["s_up"], lw["s_down"], m["act"], quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _logits(w, tokens, read, *, m, quant):
    m = dict(m)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    pos = jnp.arange(tokens.shape[0])
    x = f32(w["embed"])[tokens]

    def layer(ffn):
        def body(x, lw):
            lw = {n: f32(a) for n, a in lw.items()}
            h = dense._rms(x, lw["ln1"], m["eps"])
            q = dense._rope(dense._mm("td,dhe->the", h, lw["wq"], -1, 0,
                                      quant), pos, m["theta"])
            k = dense._rope(dense._mm("td,dhe->the", h, lw["wk"], -1, 0,
                                      quant), pos, m["theta"])
            v = dense._mm("td,dhe->the", h, lw["wv"], -1, 0, quant)
            a = dense._attention(q, k, v, quant)
            x = x + dense._mm("the,hed->td", a, lw["wo"], (-2, -1), (0, 1),
                              quant)
            return x + ffn(dense._rms(x, lw["ln2"], m["eps"]), lw), None
        return body

    def group(g):
        return {n.split(".", 1)[1]: a for n, a in w.items()
                if n.startswith(g + ".")}
    x, _ = jax.lax.scan(layer(lambda h, lw: _gated(
        h, lw["w_gate"], lw["w_up"], lw["w_down"], m["act"], quant)), x,
        group("dense"))
    x, _ = jax.lax.scan(layer(lambda h, lw: _experts(h, lw, m, quant)), x,
                        group("moe"))
    h = dense._rms(x[read], f32(w["ln_f"]), m["eps"])
    return dense._mm("nd,dv->nv", h, f32(w["head"]), -1, 0, quant)


def logits_at(w: dict, m: dict, tokens, read, *, quant: str | None = None,
              pad_to: int = 512) -> jnp.ndarray:
    """As ``dense_gqa.logits_at``."""
    tokens = np.asarray(tokens, np.int32)
    n, r = len(tokens), len(read)
    padded = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
    padded[:n] = tokens
    rpad = np.zeros((len(padded),), np.int32)
    rpad[:r] = read
    return _logits(w, jnp.asarray(padded), jnp.asarray(rpad),
                   m=tuple(sorted(m.items())), quant=quant)[:r]
