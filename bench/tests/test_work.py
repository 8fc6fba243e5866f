"""Needed operations and bytes against hand counts at published widths."""
import collections
import json
import os

from bench.harness import work
from bench.harness.families import dense_gqa as family
from bench.reference import dense_gqa

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def dims(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return dense_gqa.dims(json.load(f))


def test_qwen2_linear_and_head_flops():
    m = dims("qwen2-0.5b")
    # qkv 896*(14+2*2)*64, out 14*64*896, gated MLP 3*896*4864
    assert work.linear_flops(m) == 2 * (1_032_192 + 802_816 + 13_074_432)
    assert work.head_flops(m) == 2 * 896 * 151_936


def test_qwen2_decode_token_at_depth():
    m = dims("qwen2-0.5b")
    # position 99 attends to 100 keys: 4 * 14 heads * 64 * 100 per layer
    per_layer = 29_818_880 + 4 * 14 * 64 * 100
    assert work.decode_flops(m, 99) == 24 * per_layer + 272_269_312
    flops, nbytes = work.decode_attn_work(m, 99)
    assert flops == 24 * 358_400
    # K and V: 2 heads * 64 * 100 keys * 2 bytes each; q and o 14*64*2 each
    assert nbytes == 24 * (2 * 2 * 64 * 100 * 2 + 2 * 14 * 64 * 2)


def test_starcoder2_parameters_and_prefill_chunk():
    m = dims("starcoder2-3b")
    per_layer_params = work.linear_flops(m) // 2
    # about 3.0 B parameters with the tied embedding table
    total = 30 * per_layer_params + 49_152 * 3072
    assert 2.9e9 < total < 3.1e9
    assert work.linear_flops(m) == 191_889_408
    # a first 512-token chunk that completes its prompt: 512 * 513 / 2
    # query-key pairs under the causal mask, and one row of logits
    want = 30 * (512 * 191_889_408 + 4 * 24 * 128 * 131_328) \
        + 2 * 3072 * 49_152
    assert work.prefill_flops(m, 0, 512, True) == want == 2_996_136_050_688


def test_prefill_pieces_add_up_to_the_whole_prompt():
    m = dims("starcoder2-3b")
    whole = work.prefill_flops(m, 0, 1300, True)
    pieces = (work.prefill_flops(m, 0, 512, False)
              + work.prefill_flops(m, 512, 1024, False)
              + work.prefill_flops(m, 1024, 1300, True))
    assert pieces == whole
    f_all, b_all = work.prefill_attn_work(m, 0, 1300)
    f_a, _ = work.prefill_attn_work(m, 0, 512)
    f_b, b_b = work.prefill_attn_work(m, 512, 1300)
    assert f_a + f_b == f_all
    # a later chunk reads every earlier key once more
    assert b_b == 30 * (2 * 2 * 128 * 1300 * 2 + 2 * 788 * 24 * 128 * 2)


def test_decode_tokens_equal_a_prefill_of_the_same_positions():
    m = dims("qwen2-0.5b")
    dec = sum(work.decode_flops(m, p) for p in range(40, 48))
    pre = work.prefill_flops(m, 40, 48, False) + 8 * work.head_flops(m)
    assert dec == pre


def test_dense_family_ledger_equals_the_formulas():
    # the pieces and tokens of two requests: one prompt of 1300 tokens in
    # three chunks, one of 40 in one, then a few decode steps of each
    m = dims("starcoder2-3b")
    pieces = [(0, 512, False), (0, 40, True), (512, 1024, False),
              (1024, 1300, True)]
    tokens = [40, 1300, 41, 1301, 42]
    led = collections.Counter()
    for start, end, commit in pieces:
        led.update(family.prefill_work(m, start, end, commit))
    for pos in tokens:
        led.update(family.decode_work(m, pos))
    attn_pre = [work.prefill_attn_work(m, s, e) for s, e, _ in pieces]
    attn_dec = [work.decode_attn_work(m, p) for p in tokens]
    assert dict(led) == {
        "prefill_flops": sum(work.prefill_flops(m, *p) for p in pieces),
        "prefill_attn_flops": sum(f for f, _ in attn_pre),
        "prefill_attn_bytes": sum(b for _, b in attn_pre),
        "decode_flops": sum(work.decode_flops(m, p) for p in tokens),
        "decode_attn_flops": sum(f for f, _ in attn_dec),
        "decode_attn_bytes": sum(b for _, b in attn_dec)}
    # and the whole prompts, as one piece each, need the same
    assert led["prefill_flops"] == work.prefill_flops(m, 0, 1300, True) \
        + work.prefill_flops(m, 0, 40, True)
