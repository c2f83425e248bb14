"""From a profiler trace to the numbers the per-layer metrics read.

The harness records its own host spans (`SpanRecorder`: "window", and per
round "round", "submit", "pump", "record") on the wall clock, the clock the
profiler stamps its events with; the profiler's host tracer stays off,
because collecting host events slowed the traced window about fourfold.
`read_xplane` keeps the device operations of each chip (the "XLA Ops" line
of every /device:TPU:<n> plane: HLO instruction text, start, duration) and
moves the harness's spans onto the trace's time base (nanoseconds from the
profile's start, "Task Environment" plane).  Everything after that works on
plain lists, so the tests check it on a small recorded trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import re
import time
from pathlib import Path
from typing import Dict, List, Tuple

CUSTOM_CALL = "tpu_custom_call"

Interval = Tuple[int, int]


class SpanRecorder:
    """Host spans (name, start, duration) in wall-clock nanoseconds."""

    def __init__(self):
        self.spans: List[Tuple[str, int, int]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        t = time.time_ns()
        try:
            yield
        finally:
            self.spans.append((name, t, time.time_ns() - t))


@dataclasses.dataclass
class Trace:
    window: Interval
    ops: Dict[str, List[Tuple[str, int, int]]]    # chip -> (name, start, dur)
    spans: List[Tuple[str, int, int]]             # (name, start, dur)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(window=tuple(d["window"]),
                   ops={k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                   spans=[tuple(s) for s in d["spans"]])

    @classmethod
    def load(cls, path) -> "Trace":
        return cls.from_json(json.loads(Path(path).read_text()))


def read_xplane(path, spans) -> Trace:
    """The device operations of the trace at `path`, with the harness's
    wall-clock `spans` (one of them "window") on the trace's time base."""
    from jax.profiler import ProfileData

    ops: Dict[str, List[Tuple[str, int, int]]] = {}
    start = None
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "Task Environment":
            start = int(dict(plane.stats)["profile_start_time"])
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                           for e in line.events]
                    if evs:
                        ops[plane.name] = evs
    if start is None:
        raise ValueError(f"{path}: no profile start time")
    moved = sorted((n, s - start, d) for n, s, d in spans)
    windows = [s for s in moved if s[0] == "window"]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} window spans, want 1")
    _, t0, d = windows[0]
    return Trace(window=(t0, t0 + d), ops=ops,
                 spans=[s for s in moved if s[0] != "window"])


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------

def union(intervals, lo: int, hi: int) -> List[Interval]:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: List[Interval], lo: int, hi: int) -> int:
    """Length of [lo, hi) that the merged intervals cover."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def _intervals(events, keep=lambda name: True):
    return [(s, s + d) for name, s, d in events if keep(name)]


def is_kernel(name: str) -> bool:
    return CUSTOM_CALL in name


def window_s(t: Trace) -> float:
    return (t.window[1] - t.window[0]) * 1e-9


def busy(t: Trace) -> Dict[str, List[Interval]]:
    """Per chip, the merged intervals in which an operation ran."""
    return {chip: union(_intervals(evs), *t.window)
            for chip, evs in t.ops.items()}


def busy_s(t: Trace) -> float:
    """Device busy seconds in the window, averaged over the chips traced."""
    b = busy(t)
    if not b:
        return 0.0
    return sum(covered(m, *t.window) for m in b.values()) / len(b) * 1e-9


def idle_share(t: Trace) -> float:
    return 1.0 - busy_s(t) / window_s(t)


def glue_share(t: Trace) -> float:
    """Device time in operations other than Pallas kernels over busy time."""
    glue = [covered(union(_intervals(evs, lambda n: not is_kernel(n)),
                          *t.window), *t.window) for evs in t.ops.values()]
    total = [covered(m, *t.window) for m in busy(t).values()]
    return sum(glue) / sum(total)


def op_count(t: Trace) -> float:
    """Device operations that start in the window, averaged over chips."""
    lo, hi = t.window
    n = [sum(1 for _, s, _ in evs if lo <= s < hi) for evs in t.ops.values()]
    return sum(n) / len(n)


def spans_named(t: Trace, name: str) -> List[Interval]:
    return [(s, s + d) for n, s, d in t.spans if n == name]


def idle_in_spans(t: Trace, name: str) -> List[int]:
    """For each span of this name, its nanoseconds with no device operation
    running (first chip in the trace)."""
    chip = sorted(t.ops)[0]
    merged = busy(t)[chip]
    return [(e - s) - covered(merged, s, e) for s, e in spans_named(t, name)]


def gaps(t: Trace) -> List[Interval]:
    """Idle intervals of the first chip inside the window."""
    lo, hi = t.window
    chip = sorted(t.ops)[0]
    out, at = [], lo
    for s, e in busy(t)[chip]:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def label(t: Trace, at: int) -> str:
    """The innermost harness span (not "round") covering time `at`."""
    best = None
    for n, s, d in t.spans:
        if n != "round" and s <= at < s + d and (best is None or
                                                    d < best[1]):
            best = (n, d)
    return best[0] if best else "harness"


def idle_pieces(t: Trace) -> List[Tuple[str, int, int]]:
    """The idle gaps cut at the edges of the harness's spans, each piece
    named by the span it lies in: (label, start, end)."""
    edges = sorted({x for n, s, d in t.spans if n != "round"
                    for x in (s, s + d)})
    out = []
    for lo, hi in gaps(t):
        cuts = [lo] + [x for x in edges if lo < x < hi] + [hi]
        for a, b in zip(cuts, cuts[1:]):
            out.append((label(t, (a + b) // 2), a, b))
    return out


def idle_gaps(t: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """The longest idle pieces, each named by the host span it lies in."""
    p = sorted(idle_pieces(t), key=lambda x: x[1] - x[2])[:top]
    return [(n, (b - a) * 1e-9) for n, a, b in p]


def idle_by_label(t: Trace) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for n, a, b in idle_pieces(t):
        out[n] = out.get(n, 0.0) + (b - a) * 1e-9
    return out


_OP = re.compile(r"^(%?[\w.\-]+) = ([a-z]\w*\[[\d,]*\])?\S*\s+([\w\-]+)")


def short_name(name: str) -> str:
    """"%fusion.3 = s8[112,64]{...} fusion(...)" -> "%fusion.3 s8[112,64]
    fusion", with the kernel target added for custom calls."""
    m = _OP.match(name)
    if not m:
        return name[:80]
    parts = [p for p in m.groups() if p]
    if is_kernel(name):
        parts.append(CUSTOM_CALL)
    return " ".join(parts)


def top_ops(t: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """Device operations by total time in the window (first chip)."""
    lo, hi = t.window
    chip = sorted(t.ops)[0]
    tot: Dict[str, int] = {}
    for name, s, d in t.ops[chip]:
        c = max(0, min(s + d, hi) - max(s, lo))
        if c:
            k = short_name(name)
            tot[k] = tot.get(k, 0) + c
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [(k, v * 1e-9) for k, v in best]
