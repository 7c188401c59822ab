"""Reduce a profiler trace to device busy time, kernel time and a breakdown.

A trace is held as plain data: ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}``.  ``load`` makes it
from the ``.xplane.pb`` that ``jax.profiler`` writes, keeping only the lines
the reduction reads; the tests feed it a small recorded one.

* Device planes are ``/device:TPU:<n>``; their operations are the events of
  the ``XLA Ops`` line, named by the HLO instruction (``%fused_expand_pallas.7``;
  ``load`` keeps the name and drops the instruction's text).  Busy time is
  the union of those intervals, so a ``while`` op and the ops inside it
  count once.
* Kernel time is the summed duration of the op events whose name, without
  its ``%`` and numeric suffix, is the kernel's.
* The breakdown's device ops are the leaf ops, by name without the numeric
  suffix: an op that encloses others (a ``while`` loop) is left out of that
  ranking, since its children are in it.
* Each idle gap between busy intervals of ``GAP_NS`` or more is labelled
  with the innermost host event open at its midpoint (on any host thread:
  the one that started last among those open), and the gaps are summed by
  label; shorter gaps, between the ops of one program, sum under
  ``SHORT_GAPS``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
TOP = 10
GAP_NS = 10_000
SHORT_GAPS = "between ops (< 10 us)"
WALK_BACK = 256


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain data."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            lines.append({"name": line.name, "events": [
                [ev.name.split(" = ", 1)[0] if device else ev.name, ev.start_ns,
                 ev.duration_ns] for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_name(name: str) -> str:
    """``%fused_expand_pallas.7`` -> ``fused_expand_pallas``."""
    return re.sub(r"\.\d+$", "", name).lstrip("%")


def union(intervals) -> list:
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _device_ops(trace: dict) -> dict:
    """{device plane name: [[name, start_ns, dur_ns], ...]} of XLA ops."""
    return {p["name"]: [ev for ln in p["lines"] if ln["name"] == OPS_LINE
                        for ev in ln["events"]]
            for p in trace["planes"] if DEVICE_PLANE.match(p["name"])}


class HostSpans:
    """Innermost open host event at a time, per host thread line."""

    def __init__(self, trace: dict):
        self.lines = []
        for p in trace["planes"]:
            if p["name"] != HOST_PLANE:
                continue
            for ln in p["lines"]:
                evs = sorted((ev for ev in ln["events"] if ev[2] > 0),
                             key=lambda ev: ev[1])
                if evs:
                    self.lines.append(([ev[1] for ev in evs], evs))

    def label(self, t: float) -> str:
        best = None
        for starts, evs in self.lines:
            i = bisect.bisect_right(starts, t) - 1
            for j in range(i, max(-1, i - WALK_BACK), -1):
                name, s, d = evs[j]
                if s + d >= t:
                    if best is None or s > best[1]:
                        best = (name, s)
                    break
        return best[0] if best else "no host span"


def reduce(trace: dict, kernels: dict) -> dict | None:
    """Busy seconds (mean over devices), device span, kernel seconds by
    name, and the breakdown; None where the trace holds no device op.

    ``kernels`` maps a kernel's name to the op name its events carry.
    """
    ops = {k: v for k, v in _device_ops(trace).items() if v}
    if not ops:
        return None
    host = HostSpans(trace)
    busy, by_name, gaps = 0.0, defaultdict(float), defaultdict(float)
    kernel_s = defaultdict(float)
    lo, hi = float("inf"), float("-inf")
    for events in ops.values():
        merged = union([(s, s + d) for _, s, d in events])
        busy += sum(e - s for s, e in merged) * 1e-9
        lo, hi = min(lo, merged[0][0]), max(hi, merged[-1][1])
        ordered = sorted(events, key=lambda ev: (ev[1], -ev[2]))
        for i, (name, s, d) in enumerate(ordered):
            op = op_name(name)
            for kname, kernel_op in kernels.items():
                if op == kernel_op:
                    kernel_s[kname] += d * 1e-9
            if i + 1 < len(ordered) and ordered[i + 1][1] < s + d:
                continue            # encloses the next op: a container
            by_name[op] += d * 1e-9
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            label = host.label((e0 + s1) / 2) if s1 - e0 >= GAP_NS else SHORT_GAPS
            gaps[label] += (s1 - e0) * 1e-9
    n = len(ops)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "devices": n,
        "busy_s": busy / n,
        "device_span_s": (hi - lo) * 1e-9,
        "kernel_s": {k: v / n for k, v in kernel_s.items()},
        "breakdown": {"device_ops": [[k, v / n] for k, v in top],
                      "idle_gaps": [[k, v / n] for k, v in idle]},
    }
