"""Reduction of a profiler trace of the measured window to metrics.

On a TPU the trace has one plane per chip (``/device:TPU:<n>``); its
``XLA Ops`` line holds one event per HLO instruction run, named by the
instruction's text (``%fusion.3 = f32[...] fusion(...)``). Busy time is
the union of those intervals; idle gaps are the holes between them, each
named by what the host was doing in it, from the harness's own phase
records (host clock) moved onto the trace's clock by one anchor
annotation whose host time the harness also took.

A fused kernel is a ``tpu_custom_call`` instruction. Its name is not in
the trace (every Pallas body here is called ``fn``), so a call is named
by the kernel of the query pipeline that was running when it ran, as the
program reports it (``PipelineReport.kernel_runs``). Its roofline time
comes from ``peaks`` with the result and operand shapes the instruction
names.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import peaks

ANCHOR = "bench.anchor"
_INSTR = re.compile(r"%([A-Za-z_][\w\-]*)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_CALL = re.compile(r"= (.*?) custom-call\((.*?)\), custom_call_target")
KERNEL_TARGET = "tpu_custom_call"


@dataclasses.dataclass
class Op:
    text: str          # the HLO instruction as traced
    start_ns: float
    dur_ns: float

    @property
    def kind(self) -> str:
        """``fusion``, ``copy-start``, ``while``...; a custom call by its
        target (``tpu_custom_call``, ``X64SplitLow``)."""
        t = _TARGET.search(self.text)
        if t:
            return t.group(1)
        m = _INSTR.match(self.text)
        return m.group(1) if m else self.text[:40]

    def arrays(self) -> list:
        """Result and operand shapes of a custom call."""
        m = _CALL.search(self.text)
        return peaks.shapes(m.group(1)) + peaks.shapes(m.group(2)) \
            if m else []


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except Exception:  # noqa: BLE001 - a stat the binding cannot decode
        return {}


def load(log_dir: str) -> list:
    """The planes of the one trace under ``log_dir``, read once into
    lists: every device event with its stats, and the anchor from the
    host planes."""
    from types import SimpleNamespace as NS
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    planes = []
    for plane in ProfileData.from_file(paths[0]).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [NS(name=e.name, start_ns=e.start_ns,
                         duration_ns=e.duration_ns,
                         stats=list(_stats(e).items()) if device else [])
                      for e in line.events if device or e.name == ANCHOR]
            lines.append(NS(name=line.name, events=events))
        planes.append(NS(name=plane.name, lines=lines))
    return planes


def device_ops(planes) -> dict[str, list[Op]]:
    """TPU plane name → its ops, in start order."""
    out = {}
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = [Op(e.name, float(e.start_ns), float(e.duration_ns))
               for line in plane.lines if line.name == "XLA Ops"
               for e in line.events]
        out[plane.name] = sorted(ops, key=lambda o: o.start_ns)
    return out


def anchor_ns(planes) -> float | None:
    """Trace-clock start of the harness's anchor annotation."""
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == ANCHOR:
                    return float(e.start_ns)
    return None


def union(intervals, lo: float, hi: float) -> tuple[float, list]:
    """(covered length of [lo, hi], the uncovered gaps as (start, end))."""
    busy, gaps, cur = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return busy, gaps


def phase_at(phases, t: float) -> str:
    """The innermost phase (latest start) that covers time ``t``."""
    best = None
    for name, s, e in phases:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "between queries"


def kernel_at(kernel_spans, t: float) -> str:
    """The one kernel whose pipeline covers time ``t``, else ""."""
    names = {k for s, e, k in kernel_spans if s <= t < e}
    return names.pop() if len(names) == 1 else ""


def reduce(ops_by_plane: dict[str, list[Op]], lo: float, hi: float,
           phases, kernel_spans, device_kind: str) -> dict:
    """Metrics of the window [lo, hi) on the trace clock.

    ``phases`` are (name, start, end) and ``kernel_spans`` (start, end,
    kernel) on the same clock."""
    planes = [p for p, ops in ops_by_plane.items() if ops]
    if not planes:
        return {}
    busy_total, op_time, gaps_all = 0.0, {}, []
    k_time, k_roof, k_calls = {}, {}, {}
    for plane in planes:
        ops = [o for o in ops_by_plane[plane]
               if o.start_ns < hi and o.start_ns + o.dur_ns > lo]
        busy, gaps = union([(o.start_ns, o.start_ns + o.dur_ns)
                            for o in ops], lo, hi)
        busy_total += busy
        gaps_all += gaps
        for o in ops:
            kind = o.kind
            if kind == KERNEL_TARGET:
                kernel = kernel_at(kernel_spans, o.start_ns) or kind
                kind = f"{kind}:{kernel}"
                arrays = o.arrays()
                if arrays and o.dur_ns > 0:
                    t, _ = peaks.roofline_s(kernel, arrays, device_kind)
                    k_time[kernel] = k_time.get(kernel, 0.0) + o.dur_ns
                    k_roof[kernel] = k_roof.get(kernel, 0.0) + t * 1e9
                    k_calls[kernel] = k_calls.get(kernel, 0) + 1
            op_time[kind] = op_time.get(kind, 0.0) + o.dur_ns
    n = len(planes)
    gaps_all.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_total / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "op_s": {k: v / 1e9 for k, v in op_time.items()},
        "kernel_s": {k: v / 1e9 for k, v in k_time.items()},
        "kernel_roofline_s": {k: v / 1e9 for k, v in k_roof.items()},
        "kernel_calls": k_calls,
        "idle_gaps": [(phase_at(phases, (s + e) / 2), (e - s) / 1e9)
                      for s, e in gaps_all[:10]],
    }


def excerpt(planes, max_events: int = 200) -> dict:
    """A small copy of the trace: every plane and line with its event
    count and its first events with their stats (``from_excerpt`` reads
    it back)."""
    out = []
    for plane in planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            keep = max_events if plane.name.startswith("/device:") else 5
            lines.append({"name": line.name, "events": len(events), "first": [
                {"name": e.name, "start_ns": e.start_ns,
                 "dur_ns": e.duration_ns,
                 "stats": {k: str(v)[:400] for k, v in _stats(e).items()}}
                for e in events[:keep]]})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


def from_excerpt(doc: dict) -> list:
    """The planes of an ``excerpt``, shaped like ``load``'s."""
    from types import SimpleNamespace as NS
    return [NS(name=p["plane"], lines=[
        NS(name=ln["name"], events=[
            NS(name=e["name"], start_ns=e["start_ns"],
               duration_ns=e["dur_ns"], stats=list(e["stats"].items()))
            for e in ln["first"]])
        for ln in p["lines"]]) for p in doc["planes"]]
