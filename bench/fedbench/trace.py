"""From a profiler trace to per-layer numbers.

A :class:`Trace` holds, on one clock in seconds, what the device ran (its
ops and its program executions, per chip) and the benchmark's own host
spans (``bench.*`` annotations). The reductions are plain interval
arithmetic, so they are checked on synthetic traces as well as real ones.
"""
from __future__ import annotations

import dataclasses
import glob
import os

Event = tuple[str, float, float]          # (name, start s, end s)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = "/device:TPU:"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: list[list[Event]]        # per chip, device ops
    modules: list[list[Event]]    # per chip, program executions
    spans: list[Event]            # the benchmark's host spans

    @property
    def window(self) -> tuple[float, float]:
        """From the first benchmark span's start to whichever ends last:
        the spans or the device work they dispatched."""
        start = min(s for _, s, _ in self.spans)
        ends = [e for _, _, e in self.spans]
        ends += [e for chip in self.ops for _, _, e in chip]
        return start, max(ends)

    @property
    def window_s(self) -> float:
        a, b = self.window
        return b - a


def _clip(events: list[Event], lo: float, hi: float) -> list[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union_s(events: list[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which some op ran, inside the window, mean over chips."""
    lo, hi = trace.window
    per = [union_s(_clip(chip, lo, hi)) for chip in trace.ops]
    return sum(per) / len(per) if per else 0.0


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def op_time_s(trace: Trace, names: tuple[str, ...]) -> float:
    """Summed device time of the ops whose name contains one of ``names``,
    inside the window, mean over chips."""
    lo, hi = trace.window
    per = [sum(e - s for n, s, e in _clip(chip, lo, hi)
               if any(k in n for k in names)) for chip in trace.ops]
    return sum(per) / len(per) if per else 0.0


def self_times(events: list[Event]) -> list[tuple[str, float]]:
    """Each event's duration less that of the events nested in it (a
    loop op's event spans its body's ops on the same line)."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((top[0], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    out += [(t[0], t[2] - t[1] - t[3]) for t in stack]
    return out


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ops that took the most device self time (first chip), by
    name."""
    lo, hi = trace.window
    tot: dict[str, float] = {}
    for name, t in self_times(_clip(trace.ops[0], lo, hi)
                              if trace.ops else []):
        tot[name] = tot.get(name, 0.0) + t
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            ][:n]


def _covering_span(spans: list[Event], s: float, e: float) -> str:
    """The benchmark span that overlaps [s, e] most, or 'no span'."""
    best, best_len = "no span", 0.0
    for name, a, b in spans:
        ov = min(b, e) - max(a, s)
        if ov > best_len:
            best, best_len = name, ov
    return best


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The longest device-idle intervals inside the window (first chip),
    each named by the host span that covers it."""
    lo, hi = trace.window
    ops = sorted(_clip(trace.ops[0], lo, hi), key=lambda ev: ev[1]) \
        if trace.ops else []
    gaps, end = [], lo
    for _, s, e in ops:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if hi > end:
        gaps.append((end, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_covering_span(trace.spans, a, b), b - a] for a, b in gaps[:n]]


def round_boundary_gaps(trace: Trace, program: str | None = None
                        ) -> list[tuple[float, str]]:
    """Device idle between consecutive executions of the round program (the
    module that ran longest in total, unless ``program`` names it) on the
    first chip: for each boundary, the idle seconds between the last op of
    one execution and the first op of the next, and the host span that
    covers that interval."""
    if not trace.modules or not trace.modules[0]:
        return []
    mods = trace.modules[0]
    if program is None:
        tot: dict[str, float] = {}
        for name, s, e in mods:
            tot[name] = tot.get(name, 0.0) + (e - s)
        program = max(tot, key=tot.get)
    runs = sorted((ev for ev in mods if ev[0] == program),
                  key=lambda ev: ev[1])
    out = []
    for (_, _, end), (_, start, _) in zip(runs, runs[1:]):
        inside = _clip(trace.ops[0], end, start)
        idle = (start - end) - union_s(inside)
        out.append((idle, _covering_span(trace.spans, end, start)))
    return out


def load(profile_dir: str) -> Trace:
    """Read the ``.xplane.pb`` a ``jax.profiler`` trace wrote under
    ``profile_dir``: device planes' op and module lines, and every host
    event whose name starts with ``bench.``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {profile_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {ln.name: ln for ln in plane.lines}
            ops.append(_events(lines.get(OPS_LINE)))
            modules.append(_events(lines.get(MODULES_LINE)))
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                spans += [ev for ev in _events(ln)
                          if ev[0].startswith(SPAN_PREFIX)]
    return Trace(ops=ops, modules=modules, spans=spans)


def _events(line) -> list[Event]:
    if line is None:
        return []
    return [(ev.name, ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]
