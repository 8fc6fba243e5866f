"""The routed-experts family (a fixture of the harness's tests, copied to
``bench/harness/families/routed_gqa.py`` of a copied tree): hands
``reference.py``'s weights to the program's MoE blocks, cuts its own
expert keys for the CPU tests, and counts the routed experts' work under
a ledger key of its own, ``expert_flops``.

The program drops the tokens past an expert's capacity, which makes a
row's logits depend on the rest of its batch, so the family asks for
capacity for every token at every expert (``capacity_factor`` =
``n_experts``), and the check refuses less."""
from __future__ import annotations

import dataclasses

from bench.harness import work
from bench.harness.families import dense_gqa as dense

ACT = {"silu": "silu", "gelu": "gelu_tanh"}


def arch_config(cfg: dict):
    arch = dense.arch_config(cfg)
    return dataclasses.replace(arch, moe=dataclasses.replace(
        arch.moe, capacity_factor=float(arch.moe.n_experts)))


def check(arch, m: dict) -> None:
    from repro.configs.base import BlockKind
    moe = arch.moe
    segs = [(s.kind, s.count) for s in arch.resolved_segments()]
    k = m["dense_layers"]
    got = {"layers": arch.n_layers, "d": arch.d_model, "heads": arch.n_heads,
           "kv_heads": arch.kv_heads, "head_dim": arch.resolved_head_dim,
           "ffn": arch.d_ff, "vocab": arch.vocab, "theta": arch.rope_theta,
           "eps": arch.norm_eps, "act": ACT[arch.activation],
           "gated": arch.gated_mlp, "qkv_bias": arch.qkv_bias,
           "dense_layers": segs[0][1] if segs[0][0] is BlockKind.DENSE
           else 0, "experts": moe.n_experts, "top_k": moe.top_k,
           "expert_ffn": moe.d_ff_expert, "shared": moe.n_shared_experts}
    diff = {key: (got[key], m[key]) for key in m if got[key] != m[key]}
    want = [(BlockKind.DENSE, k)] * (k > 0) + [(BlockKind.MOE,
                                                m["layers"] - k)]
    if (diff or segs != want or arch.tied_embeddings
            or moe.d_ff_shared != m["shared"] * m["expert_ffn"]
            or moe.capacity_factor < moe.n_experts):
        raise ValueError(f"program config {arch.name} departs from the "
                         f"configuration file: {diff} {segs}")


def small_cut(cfg: dict) -> tuple[dict, object]:
    """The dense cut, then four experts of width 32 that every token
    takes (a near-tie in the float32 router must not pick another expert
    than the bfloat16 program does) and a shared MLP of two experts'
    width."""
    cut, arch = dense.small_cut(cfg)
    moe = dataclasses.replace(arch.moe, n_experts=4, top_k=4,
                              d_ff_expert=32, d_ff_shared=2 * 32,
                              n_shared_experts=2, capacity_factor=4.0)
    cut.update(moe_intermediate_size=32, n_routed_experts=4,
               num_experts_per_tok=4, n_shared_experts=2,
               first_k_dense_replace=arch.resolved_segments()[0].count)
    return cut, dataclasses.replace(arch, moe=moe)


def to_program(w: dict) -> dict:
    def block(g):
        return {"norm1": {"scale": w[f"{g}.ln1"]},
                "norm2": {"scale": w[f"{g}.ln2"]},
                "attn": {p: {"w": w[f"{g}.w{p}"]} for p in "qkvo"}}
    dense_block = dict(block("dense"), mlp={
        "wi": {"w": w["dense.w_up"]}, "wg": {"w": w["dense.w_gate"]},
        "wo": {"w": w["dense.w_down"]}})
    moe_block = dict(block("moe"), moe={
        "router": {"w": w["moe.router"]}, "wi": w["moe.e_up"],
        "wg": w["moe.e_gate"], "wo": w["moe.e_down"],
        "shared": {"wi": {"w": w["moe.s_up"]}, "wg": {"w": w["moe.s_gate"]},
                   "wo": {"w": w["moe.s_down"]}}})
    return {"embed": {"table": w["embed"]},
            "segments": [dense_block, moe_block],
            "final_norm": {"scale": w["ln_f"]}, "lm_head": {"w": w["head"]}}


def _token_flops(m: dict) -> tuple[int, int]:
    """(all projection and FFN operations of one token over the layers,
    the routed experts' part of them)."""
    d, hd, k = m["d"], m["head_dim"], m["dense_layers"]
    attn = 2 * d * hd * (2 * m["heads"] + 2 * m["kv_heads"])
    experts = 2 * 3 * d * m["expert_ffn"] * m["top_k"]
    moe = 2 * d * m["experts"] + experts \
        + 2 * 3 * d * m["expert_ffn"] * m["shared"]
    n_moe = m["layers"] - k
    return (m["layers"] * attn + k * 2 * 3 * d * m["ffn"] + n_moe * moe,
            n_moe * experts)


def prefill_work(m: dict, start: int, end: int, commit: bool) -> dict:
    linear, experts = _token_flops(m)
    flops, nbytes = work.prefill_attn_work(m, start, end)
    return {"prefill_flops": (end - start) * linear + flops
            + (work.head_flops(m) if commit else 0),
            "prefill_attn_flops": flops, "prefill_attn_bytes": nbytes,
            "expert_flops": (end - start) * experts}


def decode_work(m: dict, pos: int) -> dict:
    linear, experts = _token_flops(m)
    flops, nbytes = work.decode_attn_work(m, pos)
    return {"decode_flops": linear + flops + work.head_flops(m),
            "decode_attn_flops": flops, "decode_attn_bytes": nbytes,
            "expert_flops": experts}
