"""Reduction of a JAX profiler trace (``*.xplane.pb``) to numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.  What
a TPU trace holds (looked at by hand on ``TPU v5 lite``, jax 0.9.0; the
recorded trace beside the tests is such a one):

- one plane ``/device:TPU:<n>`` per chip.  Its line ``XLA Ops`` has one
  event per executed HLO op (name = the op's HLO text, ``%name = ...``),
  control-flow ops (``%while``) enclosing their bodies' events; its line
  ``XLA Modules`` has one event per executed program, named
  ``jit_<function>(<fingerprint>)``.
- the plane ``/host:CPU`` has one line per host thread; a
  ``jax.profiler.TraceAnnotation`` is an event on its thread's line.
- all events carry ``start_ns``/``duration_ns`` on one time base, but the
  device's clock is aligned to the host's only to about a millisecond
  (the recorded trace shows a program starting 0.7 ms before the host
  span that dispatched it).  Gap attribution is therefore sound for
  gaps of several milliseconds and a lead for shorter ones.

Busy time is the union of the op intervals (an enclosing op adds nothing
to a union); an op's own time is its duration less what its enclosed
ops cover.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # seconds on the trace's time base
Event = Tuple[float, float, str]  # start, end, name

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: Host spans a gap may be attributed to: the benchmark's own and the
#: program's annotations.
SPAN_PREFIXES = ("bench.", "ddl.")
#: Gaps shorter than this are launch latency between back-to-back ops.
MIN_GAP_S = 20e-6


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]  # chip -> op events, sorted by start
    modules: Dict[int, List[Event]]  # chip -> program executions
    spans: List[Event]  # attributable host spans, sorted by start


def find_trace_file(trace_dir: str) -> Optional[str]:
    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    return files[-1] if files else None


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%").strip()


def op_family(name: str) -> str:
    """``convolution_add_fusion.12`` -> ``convolution_add_fusion``: the
    layers of an unrolled model are one family, not 32 names."""
    return re.sub(r"(\.\d+)+$", "", name)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = sorted(
                    (
                        ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9,
                        ev.name,
                    )
                    for ev in line.events
                )
                (ops if line.name == OPS_LINE else modules)[chip] = events
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((
                            ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9,
                            ev.name,
                        ))
    spans.sort()
    return Trace(ops=ops, modules=modules, spans=spans)


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [
        (max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi
    ]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The complement of disjoint sorted ``busy`` inside ``window``."""
    out, cursor = [], window[0]
    for a, b in busy:
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if window[1] > cursor:
        out.append((cursor, window[1]))
    return out


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """(name, own seconds) per event: duration less the enclosed events'.
    ``events`` sorted by start; an event encloses the later ones that
    end inside it."""
    out: List[List] = []
    stack: List[int] = []  # indices into out, innermost last
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    ends: List[float] = []
    for i in order:
        a, b, name = events[i]
        while stack and ends[stack[-1]] < b:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= b - a
        out.append([name, b - a])
        ends.append(b)
        stack.append(len(out) - 1)
    return [(n, max(0.0, s)) for n, s in out]


def attribute(gap: Interval, spans: Sequence[Event]) -> str:
    """The innermost host span that covers at least half of ``gap``."""
    a, b = gap
    best, best_len = "unattributed", None
    for s, e, name in spans:
        if s >= b:
            break
        if min(e, b) - max(s, a) >= 0.5 * (b - a):
            if best_len is None or e - s < best_len:
                best, best_len = name, e - s
    return best


def step_programs(trace: Trace, step_program: str) -> Dict[int, List[Event]]:
    return {
        chip: [m for m in mods if m[2].startswith(step_program)]
        for chip, mods in trace.modules.items()
    }


def window_of(trace: Trace, step_program: str = "jit__run",
              marker: str = "bench.window_hook") -> Interval:
    """A whole number of windows on the device's own clock: from the
    first execution of the step program in the trace to the start of the
    last one.  With fewer than two executions, from the first ``marker``
    span's start to the last one's; without two of those, the extent of
    the device events."""
    starts = sorted(
        m[0] for mods in step_programs(trace, step_program).values() for m in mods
    )
    if len(starts) >= 2 and starts[-1] > starts[0]:
        return starts[0], starts[-1]
    marks = [s for s, _, name in trace.spans if name == marker]
    if len(marks) >= 2:
        return marks[0], marks[-1]
    evs = [e for chip in trace.ops.values() for e in chip]
    if not evs:
        raise ValueError("the trace holds no device operation")
    return min(e[0] for e in evs), max(e[1] for e in evs)


def reduce(trace: Trace, window: Optional[Interval] = None,
           step_program: str = "jit__run", top: int = 10) -> dict:
    """Everything the per-layer readers and the ``breakdown`` need."""
    if not trace.ops:
        raise ValueError("the trace holds no device plane with XLA ops")
    if window is None:
        window = window_of(trace, step_program)
    window_s = window[1] - window[0]
    per_chip = {}
    for chip, events in trace.ops.items():
        busy = clip(merge([(a, b) for a, b, _ in events]), window)
        per_chip[chip] = {"busy": busy, "busy_s": total(busy)}
    worst = min(per_chip, key=lambda c: per_chip[c]["busy_s"])
    busy_s = sum(c["busy_s"] for c in per_chip.values()) / len(per_chip)

    # Own time by op family, averaged over the chips.
    fam: Dict[str, float] = {}
    for chip, events in trace.ops.items():
        # Clipped to the window, so that an op straddling its edge and
        # the ops it encloses are cut alike.
        inside = [
            (max(a, window[0]), min(b, window[1]), name)
            for a, b, name in events if b > window[0] and a < window[1]
        ]
        for name, secs in self_times(inside):
            key = op_family(op_name(name))
            fam[key] = fam.get(key, 0.0) + secs / len(trace.ops)
    device_ops = sorted(fam.items(), key=lambda kv: -kv[1])[:top]

    # Idle gaps of the idlest chip, by what the host was doing.
    by_span: Dict[str, float] = {}
    longest = 0.0
    for gap in gaps(per_chip[worst]["busy"], window):
        if gap[1] - gap[0] < MIN_GAP_S:
            name = "launch gaps under 20 us"
        else:
            name = attribute(gap, trace.spans)
        by_span[name] = by_span.get(name, 0.0) + gap[1] - gap[0]
        longest = max(longest, gap[1] - gap[0])
    idle_gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]

    # The step program: busy seconds inside each execution, per chip.
    step_busy: List[float] = []
    step_starts: Dict[int, List[float]] = {}
    for chip, mods in step_programs(trace, step_program).items():
        merged = merge([(a, b) for a, b, _ in trace.ops.get(chip, [])])
        for a, b, _ in mods:
            step_starts.setdefault(chip, []).append(a)
            if a >= window[0] and b <= window[1] + 1e-6:
                step_busy.append(total(clip(merged, (a, b))))
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share_worst": 1.0 - per_chip[worst]["busy_s"] / window_s,
        "idle_share_by_chip": {
            c: 1.0 - v["busy_s"] / window_s for c, v in per_chip.items()
        },
        "longest_gap_s": longest,
        "ops_own_time_s": sum(fam.values()),
        "device_ops": [[n, s] for n, s in device_ops],
        "idle_gaps": [[n, s] for n, s in idle_gaps],
        "step_program_busy_s": sorted(step_busy),
        "step_program_starts": step_starts,
    }
