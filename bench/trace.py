"""Reduce a profiler trace (``.xplane.pb``) to the device figures the
benchmark reports.

- **busy**: on each device plane, the union of the intervals of its
  operation events, clipped to the measured window; averaged over devices.
- **idle share**: 1 - busy / window.
- **top ops**: device self time by operation name inside the window (an
  operation's duration less that of the operations nested in it, as a
  loop's body is in the loop).
- **idle gaps**: the stretches of the window in which a device ran nothing,
  each attributed to the innermost host span of the harness (names starting
  with ``bench.``) that covers most of it, else to the window itself, summed
  by span name.

The window is the harness's own ``bench.window`` host span. A trace taken
without host events (the serving path stalls under them) has no spans: it
covers the window alone, every device event counts, its length is given by
the caller and all idle time goes to the window. Device planes are those
named ``/device:<PLATFORM>:<n>``; their operations are the events of the
line named ``XLA Ops``.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over devices
    n_devices: int
    top_ops: list  # [[name, seconds], ...] summed over devices, descending
    idle_gaps: list  # [[span name, seconds], ...] mean over devices, descending

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union_length(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Total length of a union of [start, end) intervals, and the merged list."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def _clip(s: float, e: float, lo: float, hi: float) -> tuple[float, float]:
    return max(s, lo), min(e, hi)


def self_times(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Seconds by name of each event's duration less its direct children's
    (events nested inside it on the same line)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, name, child time]

    def close(entry):
        end, name, start, child = entry
        out[name] = out.get(name, 0.0) + ((end - start) - child) * 1e-9

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, s, 0.0])
    while stack:
        close(stack.pop())
    return out


def attribute(gap: tuple[float, float], spans: list[tuple[float, float, str]]) -> str:
    """Name of the span that covers most of ``gap`` (the shortest, i.e.
    innermost, among equals), or the window's where none does."""
    best, best_cover, best_len = WINDOW_SPAN, 0.0, float("inf")
    for s, e, name in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        if cover > best_cover or (cover == best_cover and e - s < best_len):
            best, best_cover, best_len = name, cover, e - s
    return best


def reduce_events(devices: dict[str, list[tuple[float, float, str]]],
                  host_spans: list[tuple[float, float, str]], top: int = 10,
                  window_ns: float | None = None) -> Reduced:
    """devices: plane name -> [(start_ns, end_ns, op name)]; host_spans:
    [(start_ns, end_ns, name)] of the harness, one of them the window, or
    none and ``window_ns`` the traced window's length."""
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
        window_ns = hi - lo
    elif window_ns is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span and no window length was given")
    else:
        lo, hi = min(s for ev in devices.values() for s, _, _ in ev), float("inf")
    spans = [sp for sp in host_spans if sp[2] != WINDOW_SPAN]
    busy, ops, gaps = [], {}, {}
    for events in devices.values():
        clipped = [(s, e, name) for s, e, name in ((*_clip(s, e, lo, hi), n) for s, e, n in events) if e > s]
        for name, sec in self_times(clipped).items():
            ops[name] = ops.get(name, 0.0) + sec
        length, merged = union_length([(s, e) for s, e, _ in clipped])
        busy.append(length)
        if not windows:
            gaps[WINDOW_SPAN] = gaps.get(WINDOW_SPAN, 0.0) + (window_ns - length) * 1e-9 / len(devices)
            continue
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                who = attribute((g0, g1), spans)
                gaps[who] = gaps.get(who, 0.0) + (g1 - g0) * 1e-9 / len(devices)
    n = len(devices)
    if not n:
        raise ValueError("trace holds no device plane")
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return Reduced(window_ns * 1e-9, sum(busy) / n * 1e-9, n, rank(ops), rank(gaps))


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read(path: str, platform: str, window_s: float | None = None) -> Reduced:
    """Read one trace file with ``jax.profiler.ProfileData`` and reduce it."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: dict[str, list] = {}
    spans: list[tuple[float, float, str]] = []
    prefix = f"/device:{platform.upper()}:"
    for plane in data.planes:
        if plane.name.startswith(prefix):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return reduce_events(devices, spans, window_ns=None if window_s is None else window_s * 1e9)
