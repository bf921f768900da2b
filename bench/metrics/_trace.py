"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps,
inside the host span ``bench.window``, each device's operations (the
``XLA Ops`` line of its plane) and the host spans the harness put around the
program's calls (``bench.*``). All times are nanoseconds on the trace's
clock. ``Trace`` round-trips through JSON, so the reduction is tested on a
small recorded trace.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import json
import os
import re

OPS_LINE = "XLA Ops"
WINDOW = "bench.window"


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]  # (start, end)
    ops: dict  # device id -> [(name, start, duration)]
    spans: list  # [(name, start, duration)], host spans inside the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def to_json(self) -> str:
        return json.dumps({"window": list(self.window),
                           "ops": {str(k): v for k, v in self.ops.items()},
                           "spans": self.spans})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(tuple(d["window"]),
                   {int(k): [tuple(e) for e in v] for k, v in d["ops"].items()},
                   [tuple(e) for e in d["spans"]])


def load(log_dir: str, device_ids: list[int]) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    spans, ops = [], {}
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if m is not None and line.name == OPS_LINE and int(m.group(1)) in device_ids:
                ops[int(m.group(1))] = device_ops(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events)
            elif plane.name.startswith("/host:"):
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events if e.name.startswith("bench.")]
    windows = [s for s in spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found {len(windows)}")
    lo, hi = windows[0][1], windows[0][1] + windows[0][2]
    return Trace(
        (lo, hi),
        {d: [e for e in evs if lo <= e[1] and e[1] + e[2] <= hi] for d, evs in ops.items()},
        [s for s in spans if s[0] != WINDOW and lo <= s[1] <= hi],
    )


def device_ops(events) -> list:
    """A device's ``(op text, start, duration)`` events as the chip's trace
    gives them, reduced to named top-level operations."""
    return top_level([(op_name(text), start, dur) for text, start, dur in events])


def op_name(text: str) -> str:
    """The HLO instruction's name, from the op's text (``%name = ...``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def top_level(events: list) -> list:
    """The operations not nested in another (a loop's body runs inside it)."""
    out = []
    for e in sorted(events, key=lambda e: (e[1], -e[2])):
        if out and e[1] + e[2] <= out[-1][1] + out[-1][2]:
            continue
        out.append(e)
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged (start, end) of ``(start, duration)`` intervals."""
    out: list[list[float]] = []
    for start, dur in sorted(intervals):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [tuple(x) for x in out]


def busy_ns(trace: Trace, device: int) -> float:
    return sum(b - a for a, b in union((e[1], e[2]) for e in trace.ops.get(device, [])))


def mean_busy_s(trace: Trace) -> float | None:
    if not trace.ops:
        return None
    return sum(busy_ns(trace, d) for d in trace.ops) / len(trace.ops) * 1e-9


def kernel_events(trace: Trace, prefix: str) -> list:
    return [e for evs in trace.ops.values() for e in evs if e[0].startswith(prefix)]


def idle_gaps(trace: Trace, device: int) -> list[tuple[float, float]]:
    """(start, end) of the window's stretches with no operation on ``device``."""
    gaps, cursor = [], trace.window[0]
    for a, b in union((e[1], e[2]) for e in trace.ops.get(device, [])):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if trace.window[1] > cursor:
        gaps.append((cursor, trace.window[1]))
    return gaps


class HostSpans:
    """Which host span was open when: the innermost one at a time."""

    def __init__(self, spans: list):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.longest = max((s[2] for s in self.spans), default=0)

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t)
        best = None
        while i > 0:
            i -= 1
            name, start, dur = self.spans[i]
            if start < t - self.longest:
                break
            if t < start + dur and (best is None or dur < best[2]):
                best = self.spans[i]
        return "host_other" if best is None else best[0].removeprefix("bench.")

    def split(self, a: float, b: float) -> list[tuple[str, float]]:
        """``[a, b)`` cut where a span opens or closes, each piece named."""
        i = bisect.bisect_left(self.starts, a - self.longest)
        cuts = {a, b}
        for name, start, dur in self.spans[i:]:
            if start >= b:
                break
            cuts |= {x for x in (start, start + dur) if a < x < b}
        cuts = sorted(cuts)
        return [(self.at((x + y) / 2), y - x) for x, y in zip(cuts, cuts[1:])]


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time, and the idle time by what
    the host was doing in it; seconds per chip."""
    n = max(len(trace.ops), 1)
    host = HostSpans(trace.spans)
    by_op: dict[str, float] = collections.Counter()
    idle: dict[str, float] = collections.Counter()
    for d, evs in trace.ops.items():
        for name, _, dur in evs:
            by_op[name] += dur * 1e-9 / n
        for a, b in idle_gaps(trace, d):
            for name, dur in host.split(a, b):
                idle[name] += dur * 1e-9 / n
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in gaps]}
