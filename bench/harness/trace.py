"""From a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` keeps what the reduction needs from the ``.xplane.pb``
that ``jax.profiler`` writes: for every device plane the operations
(line ``XLA Ops``) and the programs (line ``XLA Modules``), each as
``[name, start_ns, duration_ns]``, the harness's own host spans
(``bench.*``, same form) and the program's (``serve.*``, under
``program_spans``, each with its profiler arguments as a fourth item,
``{arg: value}``).  :class:`Reduced` works on that plain form, which the
tests also feed with small recorded traces (the older ones hold no
``program_spans``).

* busy time: the union of the operation intervals inside the traced
  stretch (the host span ``bench.traced``), averaged over the devices;
* kernel or program time: the summed durations of the events whose name
  matches a pattern (each metric file holds its own pattern);
* program spans: the program's spans that lie inside the stretch, with
  their arguments, for the readers of the program's own counts;
* idle gaps: the stretches of the traced window in which no operation
  ran, each labelled by the innermost span, the harness's or the
  program's, open at its middle.
"""
from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PREFIX = "/device:"
# operations that contain others on the same line (a scan over layers is
# a while loop): left out of the top-operation list, not out of busy time
CONTAINERS = ("while", "conditional", "call")
OPS, MODULES = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench.traced"
HOST_PREFIX, PROGRAM_PREFIX = "bench.", "serve."


def load_xplane(directory: str) -> dict:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(sorted(files)[-1])
    out = {"devices": [], "host_spans": [], "program_spans": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS: "ops", MODULES: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events]
            if dev["ops"] or dev["modules"]:
                out["devices"].append(dev)
        else:
            for line in plane.lines:
                for e in line.events:
                    span = [e.name, int(e.start_ns), int(e.duration_ns)]
                    if e.name.startswith(HOST_PREFIX):
                        out["host_spans"].append(span)
                    elif e.name.startswith(PROGRAM_PREFIX):
                        out["program_spans"].append(span + [dict(e.stats)])
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class Reduced:
    """The reduction of one trace (see module doc)."""

    def __init__(self, t: dict):
        self.t = t
        win = [s for s in t["host_spans"] if s[0] == WINDOW_SPAN]
        devs = t["devices"]
        if win:
            self.lo, self.hi = win[0][1], win[0][1] + win[0][2]
        else:
            evs = [(s, s + d) for dv in devs for _, s, d in dv["ops"]]
            self.lo = min((s for s, _ in evs), default=0)
            self.hi = max((e for _, e in evs), default=0)
        self.window_s = (self.hi - self.lo) / 1e9
        self.n_dev = max(1, len(devs))
        self._busy = [union(clip([(s, s + d) for _, s, d in dv["ops"]],
                                 self.lo, self.hi)) for dv in devs]
        self.busy_s = sum(e - s for b in self._busy for s, e in b) \
            / 1e9 / self.n_dev

    @property
    def has_device(self) -> bool:
        return bool(self.t["devices"]) and self.busy_s > 0

    def _sum(self, key: str, pattern: str) -> float:
        rx = re.compile(pattern)
        tot = 0
        for dv in self.t["devices"]:
            for name, s, d in dv[key]:
                if rx.search(name):
                    a, b = max(s, self.lo), min(s + d, self.hi)
                    tot += max(0, b - a)
        return tot / 1e9 / self.n_dev

    def op_s(self, pattern: str) -> float:
        """Device seconds of the operations whose name matches."""
        return self._sum("ops", pattern)

    def module_s(self, pattern: str) -> float:
        """Device seconds of the programs whose name matches."""
        return self._sum("modules", pattern)

    def program_spans(self, name: str) -> list:
        """The program's spans called ``name`` that lie inside the traced
        stretch, as ``[name, start_ns, duration_ns, {arg: value}]``; none
        for a trace that kept no program spans."""
        return [s for s in self.t.get("program_spans", [])
                if s[0] == name and self.lo <= s[1]
                and s[1] + s[2] <= self.hi]

    def idle_gaps(self) -> list[tuple[str, float]]:
        """Every idle stretch of the first device, longest first, labelled
        by the innermost span (the harness's or the program's) open at its
        middle."""
        busy = self._busy[0] if self._busy else []
        gaps, at = [], self.lo
        for s, e in busy + [(self.hi, self.hi)]:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        spans = [(n, s, s + d) for n, s, d, *_ in
                 self.t["host_spans"] + self.t.get("program_spans", [])
                 if n != WINDOW_SPAN]
        out = []
        for a, b in gaps:
            mid = (a + b) // 2
            inner = [(s, n) for n, s, e in spans if s <= mid < e]
            label = max(inner)[1] if inner else "outside any span"
            out.append((label, (b - a) / 1e9))
        return sorted(out, key=lambda g: -g[1])

    def breakdown(self, k: int = 10) -> dict:
        agg: collections.Counter = collections.Counter()
        for dv in self.t["devices"]:
            for name, s, d in dv["ops"]:
                label, kind = short_name(name)
                if kind in CONTAINERS:
                    continue
                agg[label] += max(0, min(s + d, self.hi) - max(s, self.lo))
        ops = [[n, v / 1e9 / self.n_dev] for n, v in agg.most_common(k)]
        return {"device_ops": ops,
                "idle_gaps": [[n, v] for n, v in self.idle_gaps()[:k]]}


def short_name(hlo: str) -> tuple[str, str]:
    """``"%fusion.7 = bf16[4,8]{1,0:T(8,128)} fusion(...), ..."`` ->
    (``"%fusion.7 fusion bf16[4,8]"``, ``"fusion"``); a tuple-typed
    result is shown as ``(...)``."""
    instr, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo[:120], ""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "(...)", rest[i + 1:]
    else:
        shape, _, rest = rest.partition(" ")
        shape = re.sub(r"\{.*", "", shape)
    kind = rest.strip().split("(", 1)[0]
    return f"{instr} {kind} {shape}", kind
