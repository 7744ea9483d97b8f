"""Reduction of a profiler trace to numbers.

A trace is a flat list of events ``(plane, line, name, start_ns, dur_ns)``:
what ``jax.profiler.ProfileData`` holds, and what the small recorded trace
beside the tests holds as JSON. Device planes are named ``/device:TPU:<n>``;
their operations sit on the line ``XLA Ops``. Host planes hold one line per
thread, with the program's ``distmlip/*`` annotations and the harness's own
``bench/*`` spans on them.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# annotations that count as naming what the host was doing in an idle gap
HOST_SPAN = re.compile(r"^(distmlip|bench)/")
# a device operation's event name is its whole HLO line:
#   %fusion.7 = bf16[32768,40,128]{2,1,0:T(8,128)(2,1)} fusion(...), kind=...
HLO_LINE = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")


def short_name(hlo: str) -> str:
    """``fusion.7_bf16[32768,40,128]`` from an operation's HLO line: the
    instruction, its (first) result type, and ``_tpu_custom_call`` where
    the line calls a Pallas kernel. A name that is no HLO line is kept."""
    m = HLO_LINE.match(hlo)
    if not m:
        return hlo[:120]
    name = m.group(1) + ("_" + m.group(2) if m.group(2) else "")
    if 'custom_call_target="tpu_custom_call"' in hlo:
        name += "_tpu_custom_call"
    return name


class Trace:
    def __init__(self, events):
        self.events = [(str(p), str(l), str(n), int(s), int(d))
                       for p, l, n, s, d in events]
        self._ops: dict = {}      # plane -> sorted operations
        self._merged_: dict = {}  # plane -> merged busy intervals

    # ---- loading ----
    @classmethod
    def from_xplane(cls, logdir: str) -> "Trace":
        """The newest ``*.xplane.pb`` under ``logdir``."""
        import jax

        files = sorted(glob.glob(os.path.join(
            logdir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no xplane.pb under {logdir}")
        data = jax.profiler.ProfileData.from_file(files[-1])
        events = []
        for plane in data.planes:
            device = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if device and line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    if device:
                        events.append((plane.name, line.name,
                                       short_name(ev.name), ev.start_ns,
                                       ev.duration_ns))
                    elif HOST_SPAN.match(ev.name):
                        events.append((plane.name, line.name, ev.name,
                                       ev.start_ns, ev.duration_ns))
        return cls(events)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with open(path) as f:
            return cls(json.load(f)["events"])

    def to_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({"events": self.events}, f)

    # ---- devices ----
    def device_planes(self) -> list[str]:
        return sorted({p for p, *_ in self.events if DEVICE_PLANE.match(p)},
                      key=lambda p: int(DEVICE_PLANE.match(p).group(1)))

    def ops(self, plane: str) -> list[tuple[str, int, int]]:
        """(name, start, dur) of the device operations of one plane, by
        start."""
        if plane not in self._ops:
            self._ops[plane] = sorted(
                ((n, s, d) for p, l, n, s, d in self.events
                 if p == plane and l == OPS_LINE), key=lambda e: e[1])
        return self._ops[plane]

    def window(self) -> tuple[int, int]:
        """First start and last end over every device operation."""
        spans = [(s, s + d) for p, l, _, s, d in self.events
                 if l == OPS_LINE and DEVICE_PLANE.match(p)]
        if not spans:
            raise ValueError("the trace holds no device operation")
        return min(s for s, _ in spans), max(e for _, e in spans)

    def busy_ns(self, plane: str) -> int:
        """Length of the union of the plane's operation intervals."""
        return sum(e - s for s, e in self._merged(plane))

    def _merged(self, plane: str) -> list[tuple[int, int]]:
        if plane not in self._merged_:
            merged = []
            for _, s, d in self.ops(plane):
                if merged and s <= merged[-1][1]:
                    merged[-1][1] = max(merged[-1][1], s + d)
                else:
                    merged.append([s, s + d])
            self._merged_[plane] = [(s, e) for s, e in merged]
        return self._merged_[plane]

    def busiest_plane(self) -> str:
        return max(self.device_planes(), key=self.busy_ns)

    def self_times(self, plane: str) -> dict[str, int]:
        """Summed duration by operation name, each operation less the
        operations nested inside it (a ``while`` holds its body's)."""
        total = defaultdict(int)
        stack = []  # (name, end, self)
        def close(until):
            while stack and stack[-1][1] <= until:
                name, _, own = stack.pop()
                total[name] += own
        for name, s, d in self.ops(plane):
            close(s)
            if stack and s + d <= stack[-1][1]:  # nested, not overlapping
                stack[-1][2] -= d
            stack.append([name, s + d, d])
        close(float("inf"))
        return dict(total)

    def sum_matching(self, plane: str, pattern: str) -> tuple[int, int]:
        """(count, summed ns) of the plane's operations whose name matches."""
        rx = re.compile(pattern)
        hits = [d for n, _, d in self.ops(plane) if rx.search(n)]
        return len(hits), sum(hits)

    # ---- idle gaps by what the host was doing ----
    def idle_gaps(self, plane: str, floor_ns: int = 5000) -> dict[str, int]:
        """Idle time of ``plane`` inside the traced window, by the
        innermost host span covering each gap's middle. Gaps under
        ``floor_ns`` are pooled as the pauses between operations."""
        merged = self._merged(plane)
        t0, t1 = self.window()
        edges = [t0] + [t for s, e in merged for t in (s, e)] + [t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        host = [(n, s, s + d) for p, _, n, s, d in self.events
                if not DEVICE_PLANE.match(p) and HOST_SPAN.match(n)]
        out = defaultdict(int)
        for s, e in gaps:
            if e - s < floor_ns:
                out[f"between_device_ops_under_{floor_ns // 1000}_us"] += e - s
                continue
            mid = (s + e) // 2
            cover = [h for h in host if h[1] <= mid < h[2]]
            name = (min(cover, key=lambda h: h[2] - h[1])[0] if cover
                    else "unattributed")
            out[name] += e - s
        return dict(out)


def top(table: dict[str, int], n: int = 10) -> list[list]:
    """The ``n`` largest entries as [[name, seconds], ...]."""
    ranked = sorted(table.items(), key=lambda kv: -kv[1])[:n]
    return [[re.sub(r"[\s,/]+", "_", name)[:120], ns / 1e9]
            for name, ns in ranked]
