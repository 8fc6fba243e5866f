"""The dense-GQA family: everything the harness needs of a configuration
whose plain reference is ``bench.reference.dense_gqa``.

* ``arch_config(cfg)`` and ``check(arch, m)``: the program's
  ``ArchConfig`` for a configuration file, and its check against the
  sizes the reference runs (``m``, from the reference's ``dims``);
* ``to_program(w)``: the reference's weights as the program's tree;
* ``small_cut(cfg)``: the configuration cut to the size the CPU tests
  run, and the program's ``ArchConfig`` at that size;
* ``prefill_work`` and ``decode_work``: the needed work of one prompt
  piece and of one decode token, as named numbers that the client adds
  into its ledger by name (``bench.harness.work``).
"""
from __future__ import annotations

import dataclasses

from bench.harness import work


def arch_config(cfg: dict):
    """The registry entry named under ``program.arch`` with
    ``program.arch_overrides`` applied."""
    from repro.configs import get_config
    prog = cfg["program"]
    return dataclasses.replace(get_config(prog["arch"]),
                               **prog.get("arch_overrides", {}))


def check(arch, m: dict) -> None:
    """Raise unless ``arch`` computes the layers the reference runs."""
    got = {"layers": arch.n_layers, "d": arch.d_model, "heads": arch.n_heads,
           "kv_heads": arch.kv_heads, "head_dim": arch.resolved_head_dim,
           "ffn": arch.d_ff, "vocab": arch.vocab, "theta": arch.rope_theta,
           "eps": arch.norm_eps,
           "act": {"silu": "silu", "gelu": "gelu_tanh"}[arch.activation],
           "gated": arch.gated_mlp, "qkv_bias": arch.qkv_bias}
    diff = {k: (got[k], m[k]) for k in m if got[k] != m[k]}
    if diff or not arch.tied_embeddings:
        raise ValueError(f"program config {arch.name} departs from the "
                         f"configuration file: {diff}")


def small_cut(cfg: dict) -> tuple[dict, object]:
    """The registry entry's ``reduced()`` widths (d 64, 4 heads of 16,
    vocabulary 256, two layers) with ``program.arch_overrides`` applied,
    written back into the configuration's published keys."""
    from repro.configs import get_config
    prog = cfg["program"]
    arch = dataclasses.replace(get_config(prog["arch"]).reduced(),
                               **prog.get("arch_overrides", {}))
    cut = dict(cfg, hidden_size=arch.d_model,
               num_attention_heads=arch.n_heads,
               num_key_value_heads=arch.kv_heads, head_dim=arch.head_dim,
               intermediate_size=arch.d_ff, vocab_size=arch.vocab,
               num_hidden_layers=arch.n_layers)
    return cut, arch


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


def prefill_work(m: dict, start: int, end: int, commit: bool) -> dict:
    """A prompt piece at positions [start, end); ``commit`` when it
    completes its prompt."""
    flops, nbytes = work.prefill_attn_work(m, start, end)
    return {"prefill_flops": work.prefill_flops(m, start, end, commit),
            "prefill_attn_flops": flops, "prefill_attn_bytes": nbytes}


def decode_work(m: dict, pos: int) -> dict:
    """The decode step of the token at position ``pos``."""
    flops, nbytes = work.decode_attn_work(m, pos)
    return {"decode_flops": work.decode_flops(m, pos),
            "decode_attn_flops": flops, "decode_attn_bytes": nbytes}
