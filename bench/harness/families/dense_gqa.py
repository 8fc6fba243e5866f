"""Hand the benchmark's dense-GQA weights (``bench.reference.dense_gqa``
layout) to the program: its ``ArchConfig`` and its parameter tree."""
from __future__ import annotations

import dataclasses


def arch_config(cfg: dict, m: dict):
    """The program's ArchConfig for this configuration file: the registry
    entry named under ``program.arch`` with ``program.arch_overrides``
    applied, checked against the sizes the reference runs."""
    from repro.configs import get_config
    prog = cfg["program"]
    arch = dataclasses.replace(get_config(prog["arch"]),
                               **prog.get("arch_overrides", {}))
    got = {"layers": arch.n_layers, "d": arch.d_model, "heads": arch.n_heads,
           "kv_heads": arch.kv_heads, "head_dim": arch.resolved_head_dim,
           "ffn": arch.d_ff, "vocab": arch.vocab, "theta": arch.rope_theta,
           "eps": arch.norm_eps,
           "act": {"silu": "silu", "gelu": "gelu_tanh"}[arch.activation],
           "gated": arch.gated_mlp, "qkv_bias": arch.qkv_bias}
    diff = {k: (got[k], m[k]) for k in m if got[k] != m[k]}
    if diff or not arch.tied_embeddings:
        raise ValueError(f"program config {prog['arch']} departs from the "
                         f"configuration file: {diff}")
    return arch


def to_program(w: dict) -> dict:
    """The program's parameter tree over the same arrays (no copies)."""
    def lin(name, bias=None):
        out = {"w": w[name]}
        if bias is not None and bias in w:
            out["b"] = w[bias]
        return out
    mlp = {"wi": lin("w_up"), "wo": lin("w_down")}
    if "w_gate" in w:
        mlp["wg"] = lin("w_gate")
    block = {"norm1": {"scale": w["ln1"]}, "norm2": {"scale": w["ln2"]},
             "attn": {"q": lin("wq", "bq"), "k": lin("wk", "bk"),
                      "v": lin("wv", "bv"), "o": lin("wo")},
             "mlp": mlp}
    return {"embed": {"table": w["embed"]}, "segments": [block],
            "final_norm": {"scale": w["ln_f"]}}
