"""The benchmark's client: it drives a ``Batcher`` from outside.

The program has no arrival API, so the client rides the batcher's
``chaos=`` hook, whose ``on_round`` runs at the top of every scheduling
round.  At each round (and between ``run()`` calls, when the batcher has
gone idle) :meth:`Client.pump`

* reads every live request's output and stamps the host-clock time at
  which each new token first became visible there (the earliest a caller
  of this program can see a token);
* stops a request once it has its drawn number of tokens
  (``cancel(rid, "client")``; tokens past that point are not counted);
* submits the requests that are due (open loop) or tops the queue up to
  its backlog (closed);
* while the traced stretch is open, adds up the needed work of the
  prompt pieces and tokens that became visible, as the configuration's
  family (``bench/harness/families``) names and counts it.

``pick_victim`` returns None, so the hook never changes the scheduler's
own preemption policy.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time


@dataclasses.dataclass
class Rec:
    """One request as the client saw it (times on ``time.perf_counter``)."""
    rid: int
    prompt_len: int
    out_len: int
    due: float                 # when it was due (open loop) or submitted
    submit_t: float
    first_t: float | None = None
    last_t: float | None = None
    seen: int = 0              # tokens seen, capped at out_len
    filled: int = 0            # prompt tokens resident, as last seen
    done_t: float | None = None


class Client:
    def __init__(self, stream, mix: dict, batch: int, dims: dict, family,
                 clock=time.perf_counter):
        self.stream, self.mix, self.m, self.clock = stream, mix, dims, clock
        self.family = family                # prefill_work, decode_work
        arr = mix["arrivals"]
        self.closed = arr["kind"] == "closed"
        self.backlog = (math.ceil(arr.get("queued_per_slot", 1.0) * batch)
                        if self.closed else 0)
        self.t0: float | None = None       # schedule start
        self.t_open = self.t_close = math.inf
        self.counting = False               # the measured window is open
        self.rounds = 0                     # scheduling rounds seen
        self.t_phase_end: float | None = None   # set by ``drive``
        self.stopped = False                # no more submissions
        self.next = 0                       # next request index
        self.recs: dict[int, Rec] = {}
        self.live: dict[int, Rec] = {}      # submitted, not yet finished
        self.window_tokens = 0
        self.lateness: list[float] = []     # submit - due, open loop
        self.on_tick = None                 # called after each pump:
        self.on_phase = None                # the tracer, then ``drive``
        self.account = False                # add up needed work
        # needed work by name; a name no piece or token gave reads 0
        self.ledger: collections.Counter = collections.Counter()

    # the batcher's chaos hook -------------------------------------------
    def on_round(self, batcher) -> None:
        self.rounds += 1
        self.pump(batcher)

    def pick_victim(self, batcher, candidates):
        return None

    # ---------------------------------------------------------------------
    def start(self, now: float) -> None:
        self.t0 = now

    def next_due(self) -> float | None:
        """Absolute time the next request is due (open loop)."""
        if self.closed or self.stopped:
            return None
        return self.t0 + self.stream.due(self.next)

    def pump(self, b) -> None:
        now = self.clock()
        self._observe(b, now)
        if not self.stopped:
            self._submit(b, now)
        if self.on_tick is not None:
            self.on_tick(now)
        if self.on_phase is not None:
            self.on_phase(now)

    def _observe(self, b, now: float) -> None:
        in_window = self.counting
        acct = self.account
        led, fam, m = self.ledger, self.family, self.m
        for slot, rid in enumerate(b.slot_rid):
            rec = self.live.get(rid)
            if rec is None:
                continue
            filled = b.slot_filled[slot]
            if filled > rec.filled:
                if acct:
                    led.update(fam.prefill_work(m, rec.filled, filled,
                                                filled == rec.prompt_len))
                    led["prefill_tokens"] += filled - rec.filled
                rec.filled = filled
        outputs = b.outputs
        for rid, rec in list(self.live.items()):
            out = outputs.get(rid)
            if not out:
                continue
            n = min(len(out), rec.out_len)
            if n > rec.seen:
                if rec.first_t is None:
                    rec.first_t = now
                rec.last_t = now
                if in_window:
                    self.window_tokens += n - rec.seen
                if acct:
                    # output token i >= 1 comes from the decode step of the
                    # token at position prompt_len + i - 1
                    for i in range(max(rec.seen, 1), n):
                        led.update(fam.decode_work(m, rec.prompt_len + i - 1))
                    led["decode_tokens"] += n - max(rec.seen, 1)
                rec.seen = n
            if len(out) >= rec.out_len:
                rec.done_t = now
                del self.live[rid]
                if rid not in b.results:
                    b.cancel(rid, "client")

    def _submit(self, b, now: float) -> None:
        if self.closed:
            while len(b.queue) < self.backlog:
                self._send(b, now, now)
            return
        while self.t0 + self.stream.due(self.next) <= now:
            due = self.t0 + self.stream.due(self.next)
            self.lateness.append(now - due)
            self._send(b, now, due)

    def _send(self, b, now: float, due: float) -> None:
        req = self.stream.get(self.next)
        self.next += 1
        rec = Rec(req.rid, len(req.prompt), req.out_len, due, now)
        self.recs[req.rid] = self.live[req.rid] = rec
        b.submit(req.rid, req.prompt)

    def stop_all(self, b) -> None:
        """End of the run: submit nothing more, cancel what is left."""
        self.stopped = True
        for rid, _ in list(b.queue):
            b.cancel(rid, "client")
        for rid in b.slot_rid:
            if rid is not None:
                b.cancel(rid, "client")
        self.live.clear()
