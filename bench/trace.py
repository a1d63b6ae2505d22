"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes one ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. On a TPU each chip is a plane ``/device:TPU:<i>`` with the lines
``XLA Modules`` (one event per program run; they do not nest) and
``XLA Ops`` (one event per operation; a loop's event holds those of its
body). Host spans that the benchmark records with
``jax.profiler.TraceAnnotation`` sit on the plane ``/host:CPU`` on the same
clock.

- busy: the union of a chip's ``XLA Modules`` intervals inside the window;
  the window is the host span ``window`` (else the extent of the device
  events);
- collective: the union of a chip's operations whose names are
  collectives;
- top operations: self time (an event's time less the events nested in it)
  by program and operation, averaged over the chips;
- idle gaps: the holes in the busy union of the most idle chip, each named
  by the innermost host span that covers its middle.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

WINDOW = "window"
COLLECTIVE = re.compile(r"all-gather|all-reduce|collective-permute|"
                        r"reduce-scatter|all-to-all|collective-broadcast")
_HASH = re.compile(r"\(\d+\)$")


@dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over the chips
    idle_frac: list                    # per chip
    collective_s: list                 # per chip
    top_ops: list                      # [[name, seconds]], most first
    idle_gaps: list                    # [[name, seconds]], longest first
    op_s: dict = field(default_factory=dict)   # self seconds by op name

    def kernel_s(self, prefix: str) -> float:
        """Mean seconds a chip spent in operations named ``prefix...``."""
        return sum(s for name, s in self.op_s.items()
                   if name.split("/", 1)[-1].startswith(prefix))


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def _op_name(full: str) -> str:
    head = full.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def _module_name(full: str) -> str:
    return _HASH.sub("", full.strip())


def _events(line):
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in line.events]


def _self_times(ops):
    """Self time of each (nested) event: its length less its children's."""
    ops = sorted(ops, key=lambda e: (e[0], -e[1]))
    selfs = [e[1] - e[0] for e in ops]
    stack = []
    for i, (a, b, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= ops[stack[-1]][1]:
            selfs[stack[-1]] -= b - a
        stack.append(i)
    return ops, selfs


def reduce_file(path: str, devices: int | None = None) -> Summary:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    chips, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            chips.append((plane.name, lines))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                spans.extend(_events(ln))
    chips.sort(key=lambda c: int(c[0].rsplit(":", 1)[1]))
    if devices is not None:
        chips = chips[:devices]
    if not chips:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    win = [s for s in spans if s[2] == WINDOW]
    if win:
        lo, hi = win[0][0], win[0][1]
    else:
        ext = [e for _, ls in chips for e in ls.get("XLA Modules", [])]
        lo, hi = min(e[0] for e in ext), max(e[1] for e in ext)
    window = hi - lo

    busy, idle, coll, op_s, holes = [], [], [], {}, []
    for _, ls in chips:
        mods = ls.get("XLA Modules", [])
        u = _union(_clip([(a, b) for a, b, _ in mods], lo, hi))
        busy.append(_total(u))
        idle.append(1.0 - busy[-1] / window)
        ops = _clip_named(ls.get("XLA Ops", []), lo, hi)
        coll.append(_total(_union([(a, b) for a, b, n in ops
                                   if COLLECTIVE.search(_op_name(n))])) / 1e9)
        ops, selfs = _self_times(ops)
        mods_sorted = sorted(mods)
        for (a, b, n), s in zip(ops, selfs):
            key = f"{_module_at(mods_sorted, a)}/{_op_name(n)}"
            op_s[key] = op_s.get(key, 0.0) + s / 1e9 / len(chips)
        gaps, prev = [], lo
        for a, b in u:
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if hi > prev:
            gaps.append((prev, hi))
        holes.append(gaps)
    worst = max(range(len(chips)), key=lambda i: idle[i])
    longest = sorted(holes[worst], key=lambda g: g[0] - g[1])[:10]
    named = [(_span_at(spans, (a + b) / 2), (b - a) / 1e9)
             for a, b in longest]
    top = sorted(op_s.items(), key=lambda kv: -kv[1])
    return Summary(window_s=window / 1e9,
                   busy_s=sum(busy) / len(busy) / 1e9,
                   idle_frac=idle, collective_s=coll,
                   top_ops=[[k, v] for k, v in top[:10]],
                   idle_gaps=[[n, s] for n, s in named], op_s=op_s)


def _clip_named(events, lo, hi):
    return [(max(a, lo), min(b, hi), n) for a, b, n in events
            if b > lo and a < hi]


def _module_at(mods, t) -> str:
    """The program running at ``t`` (``mods`` sorted; they do not nest)."""
    i = bisect.bisect_right(mods, (t, float("inf"))) - 1
    if i >= 0 and mods[i][0] <= t < mods[i][1]:
        return _module_name(mods[i][2])
    return "?"


def _span_at(spans, t) -> str:
    """The innermost benchmark span (no ``$`` source-line events of the
    Python tracer) that covers ``t``."""
    best = None
    for a, b, n in spans:
        if a <= t <= b and not n.startswith("$") and n != WINDOW:
            if best is None or b - a < best[1] - best[0]:
                best = (a, b, n)
    return best[2] if best else "none"


def reduce_dir(trace_dir: str, devices: int | None = None) -> Summary:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(max(paths, key=os.path.getmtime), devices)
