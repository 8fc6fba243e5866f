"""The traffic generator and the client that feeds it to a batcher."""
import collections
import math

from bench.harness import spec, work
from bench.harness.client import Client
from bench.harness.families import dense_gqa
from bench.harness.traffic import RequestStream, length_range, quantile

BIG_SEED = 2**31 + 12345


def stream(mix_name, seed=BIG_SEED, vocab=1000):
    return RequestStream(spec.traffic(mix_name), seed, vocab)


def test_same_seed_same_requests_other_seed_same_work():
    a, b, c = (stream("offline_long_output", s) for s in
               (BIG_SEED, BIG_SEED, BIG_SEED + 1))
    for i in range(40):
        assert a.get(i) == b.get(i)
    n = a.strata
    shape = lambda s: [(s.get(i).out_len, len(s.get(i).prompt), s.due(i))
                       for i in range(2 * n)]
    # the mix's schedule seed fixes lengths and times; the run's seed
    # changes the token ids only
    assert shape(a) == shape(c)
    assert [a.get(i).prompt for i in range(3)] != \
        [c.get(i).prompt for i in range(3)]
    assert shape(stream("code_completion", BIG_SEED)) == \
        shape(stream("code_completion", BIG_SEED + 1))
    # a block of ``strata`` requests holds each quantile of a length
    # distribution once
    mix = spec.traffic("offline_long_output")
    for blk in range(2):
        rows = range(blk * n, (blk + 1) * n)
        assert sorted(a.get(i).out_len for i in rows) == sorted(
            quantile(mix["output_tokens"], (k + 0.5) / n) for k in range(n))
        assert sorted(len(a.get(i).prompt) for i in rows) == sorted(
            quantile(mix["prompt_tokens"], (k + 0.5) / n) for k in range(n))
    # another schedule seed serves the same work in another order
    d = RequestStream(dict(mix, schedule_seed=mix["schedule_seed"] + 1),
                      BIG_SEED, 1000)
    assert shape(d) != shape(a)
    for k in range(2):
        assert sorted(x[k] for x in shape(d)[:n]) == \
            sorted(x[k] for x in shape(a)[:n])


def test_warm_start_gives_the_first_batch_residual_lengths():
    mix = spec.traffic("offline_long_output")
    olo, ohi = length_range(mix["output_tokens"])
    s = RequestStream(mix, BIG_SEED, 1000, batch=128)
    cold = stream("offline_long_output")
    warm = [s.get(i).out_len for i in range(128)]
    assert all(1 <= x <= ohi for x in warm) and min(warm) < olo
    # a slot at a random moment holds a request of length-biased lifetime
    # L with a uniform share of it left: mean E[L^2] / (2 E[L])
    lens = [quantile(mix["output_tokens"], (k + 0.5) / 4096)
            for k in range(4096)]
    expect = sum(x * x for x in lens) / (2 * sum(lens))
    assert abs(sum(warm) / 128 - expect) < 0.1 * expect
    # the rest of the stream is the mix's own draw
    assert [s.get(i).out_len for i in range(128, 160)] == \
        [cold.get(i).out_len for i in range(128, 160)]
    assert [len(s.get(i).prompt) for i in range(128)] == \
        [len(cold.get(i).prompt) for i in range(128)]


def test_lengths_are_clipped_and_tokens_in_vocab():
    for name in ("offline_long_output", "code_completion"):
        mix = spec.traffic(name)
        s = stream(name)
        lo, hi = length_range(mix["prompt_tokens"])
        olo, ohi = length_range(mix["output_tokens"])
        for i in range(2 * s.strata):
            r = s.get(i)
            assert lo <= len(r.prompt) <= hi
            assert olo <= r.out_len <= ohi <= mix["max_new"]
            assert all(0 <= t < 1000 for t in r.prompt)


def test_quantiles_follow_the_lognormal():
    d = {"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 32,
         "max": 512}
    assert quantile(d, 0.5) == 128
    assert quantile(d, 1e-6) == 32 and quantile(d, 1 - 1e-6) == 512
    assert quantile({"dist": "fixed", "value": 64}, 0.3) == 64


def test_poisson_schedule():
    s = stream("code_completion")
    rate = spec.traffic("code_completion")["arrivals"]["rate_per_s"]
    dues = [s.due(i) for i in range(4 * s.strata)]
    assert all(b > a for a, b in zip(dues, dues[1:]))
    assert dues[0] > 0
    # the mean gap of each block is the stratified exponential mean
    n = s.strata
    block = dues[2 * n - 1] - dues[n - 1]
    mean_q = sum(-math.log(1 - (k + 0.5) / n) for k in range(n)) / n
    assert math.isclose(block / n, mean_q / rate, rel_tol=1e-9)
    assert stream("offline_long_output").due(5) is None


class FakeBatcher:
    """What the client touches of a Batcher: each ``step`` admits queued
    requests into free slots and gives every live one ``per_step``
    tokens."""

    def __init__(self, slots, per_step=3):
        self.queue = collections.deque()
        self.outputs, self.results = {}, {}
        self.slot_rid = [None] * slots
        self.slot_filled = [0] * slots
        self.prompts = {}
        self.cancelled = []
        self.per_step = per_step

    def submit(self, rid, prompt):
        self.queue.append((rid, prompt))
        self.prompts[rid] = prompt

    def cancel(self, rid, reason):
        self.cancelled.append((rid, reason))
        self.queue = collections.deque(q for q in self.queue if q[0] != rid)
        for i, r in enumerate(self.slot_rid):
            if r == rid:
                self.slot_rid[i] = None

    def step(self):
        for i, r in enumerate(self.slot_rid):
            if r is None and self.queue:
                rid, p = self.queue.popleft()
                self.slot_rid[i] = rid
                self.slot_filled[i] = len(p)
                self.outputs[rid] = []
        for r in self.slot_rid:
            if r is not None:
                self.outputs[r].extend([7] * self.per_step)


def test_closed_backlog_and_client_stop():
    mix = spec.traffic("offline_long_output")
    dims = {"layers": 1, "d": 8, "heads": 2, "kv_heads": 1, "head_dim": 4,
            "ffn": 16, "vocab": 1000, "gated": True}
    c = Client(stream("offline_long_output"), mix, batch=4, dims=dims,
               family=dense_gqa)
    b = FakeBatcher(4, per_step=200)
    c.start(0.0)
    c.pump(b)
    assert len(b.queue) == c.backlog == 4
    b.step()
    c.pump(b)
    assert len(b.queue) == 4          # refilled behind the admitted four
    b.step()
    seen = {rid: rec.seen for rid, rec in c.recs.items()}
    filled = {rid: rec.filled for rid, rec in c.recs.items()}
    c.account = True
    c.pump(b)
    # the needed work of the tokens seen while accounting: output token
    # i >= 1 comes from the decode step at position prompt_len + i - 1
    pos = [rec.prompt_len + i - 1 for rid, rec in c.recs.items()
           for i in range(max(seen.get(rid, 0), 1), rec.seen)]
    assert c.ledger["decode_tokens"] == len(pos)
    assert c.ledger["decode_flops"] == sum(work.decode_flops(dims, p)
                                           for p in pos)
    # and of the prompts that became resident (each in one piece)
    assert c.ledger["prefill_flops"] == sum(
        work.prefill_flops(dims, filled.get(rid, 0), rec.filled, True)
        for rid, rec in c.recs.items() if rec.filled > filled.get(rid, 0))
    # every request got at least 32 tokens: each one stopped at its own
    # drawn length, seen tokens capped there, and cancelled by the client
    for rid, rec in c.recs.items():
        if rid < 4:
            assert rec.seen == min(rec.out_len, 400)
            assert (rec.done_t is not None) == (rec.out_len <= 400)
    stopped = {rid for rid, why in b.cancelled if why == "client"}
    assert {rid for rid in range(4) if c.recs[rid].out_len <= 400} <= stopped
    assert stopped == {rid for rid, rec in c.recs.items()
                       if rec.done_t is not None}
    assert all(c.recs[rid].seen == c.recs[rid].out_len for rid in stopped)


class Pieces:
    """A family whose needed work is counted in keys of its own."""

    @staticmethod
    def prefill_work(m, start, end, commit):
        return {"piece_tokens": end - start, "commits": int(commit)}

    @staticmethod
    def decode_work(m, pos):
        return {"expert_rows": m["top_k"], "decode_flops": pos}


def test_client_adds_the_family_work_by_name():
    mix = spec.traffic("offline_long_output")
    c = Client(stream("offline_long_output"), mix, batch=2,
               dims={"top_k": 6}, family=Pieces)
    b = FakeBatcher(2, per_step=3)
    c.start(0.0)
    c.pump(b)
    c.account = True
    b.step()              # admits two, prompts resident, 3 tokens each
    c.pump(b)
    recs = [c.recs[r] for r in b.slot_rid]
    # tokens 1 and 2 of each request come from decode steps at positions
    # prompt_len and prompt_len + 1; token 0 from the join
    assert dict(c.ledger) == {
        "piece_tokens": sum(r.prompt_len for r in recs), "commits": 2,
        "prefill_tokens": sum(r.prompt_len for r in recs),
        "expert_rows": 6 * 4, "decode_tokens": 4,
        "decode_flops": sum(2 * r.prompt_len + 1 for r in recs)}
    assert c.ledger["decode_attn_bytes"] == 0     # a name it never gave
