"""Profiler trace -> device busy time, per-op and per-module device time,
and idle time by what the host was doing.

The benchmark wraps its measured window in the host annotation
``pb.window`` and each step of it in ``pb.<phase>`` annotations
(``generate``, ``call``, ``block``, ``sample``).  From the trace:

* busy = the union of the device's op intervals inside the window;
* an op's time = the sum of its events' durations; an op belongs to the
  program (``XLA Modules`` event) whose interval holds its start;
* each idle stretch of the window is split over the host phases that
  overlap it, so an idle share comes with what the host was doing;
  idle time that no phase covers is ``other``.

The reduction works on plain :class:`Event` records, so its tests build
small traces by hand; :func:`load` reads a real ``.xplane.pb``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

#: a TPU chip's plane in the trace (one per chip)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: a device plane's line of executed XLA ops (each named by its HLO text)
#: and its line of executed programs (``jit_<name>(<fingerprint>)``)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "pb.window"
PHASE_PREFIX = "pb."
#: a Pallas kernel's op: its HLO text names the Mosaic custom call.  The
#: program's kernels carry no ``name=``; which kernel it is follows from
#: the program that runs it (``combine_fn``: packed_agg, ``fold_fn``:
#: axpy_fold).
PALLAS_KERNEL = r'custom_call_target="tpu_custom_call"'
_FINGERPRINT = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")


def phase(name: str):
    """``with phase("call"):`` -- the host annotation ``pb.call``."""
    import jax
    return jax.profiler.TraceAnnotation(PHASE_PREFIX + name)


def short_op(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...), ...`` -> ``fusion.3 fusion``."""
    inst, sep, rest = name.partition(" = ")
    if not sep:
        return name
    m = _OPCODE.search(" " + rest)
    return inst.lstrip("%") + (" " + m.group(1) if m else "")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {paths}")
    return paths[0]


def load(path: str) -> list:
    """Every event of a trace that the reduction reads: the device
    planes' op lines and the host planes' annotations."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not device and not e.name.startswith(PHASE_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def _union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(events) -> dict:
    """The summary the per-layer readers take their numbers from.

    ``window_s``: length of ``pb.window``; ``busy_s``: device busy time in
    it, averaged over the chips in the trace; ``ops``: ``[(module, op,
    seconds, count)]`` summed over chips, ``module`` being the program
    the op ran in (``jit_combine_fn``); ``modules``: ``{module:
    seconds}`` of op time; ``idle_by_phase``: ``{phase: seconds}``.
    """
    windows = [e for e in events if e.name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} annotation, found "
                           f"{len(windows)}")
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    # each op runs inside one program on its chip: find it by time
    runs = collections.defaultdict(list)
    for e in events:
        if DEVICE_PLANE.match(e.plane) and e.line == MODULES_LINE:
            runs[e.plane].append((e.start_ns, e.end_ns,
                                  _FINGERPRINT.sub("", e.name)))
    starts = {}
    for chip in runs:
        runs[chip].sort()
        starts[chip] = [r[0] for r in runs[chip]]

    def module_of(e):
        # by the op's start: the trace rounds an op's end past its
        # program's end by a few nanoseconds
        i = bisect.bisect_right(starts.get(e.plane, []), e.start_ns) - 1
        if i >= 0 and e.start_ns < runs[e.plane][i][1]:
            return runs[e.plane][i][2]
        return ""

    by_chip = collections.defaultdict(list)
    ops = {}
    modules = collections.Counter()
    for e in events:
        if not DEVICE_PLANE.match(e.plane) or e.line != OPS_LINE:
            continue
        s, t = max(e.start_ns, w0), min(e.end_ns, w1)
        if t <= s:
            continue
        by_chip[e.plane].append((s, t))
        module = module_of(e)
        rec = ops.setdefault((module, e.name), [0.0, 0])
        rec[0] += (t - s) * 1e-9
        rec[1] += 1
        modules[module] += (t - s) * 1e-9
    busy = {chip: _union(iv) for chip, iv in by_chip.items()}
    n_chips = max(len(busy), 1)
    busy_ns = sum(e - s for iv in busy.values() for s, e in iv) / n_chips

    # idle stretches of the first chip, split over overlapping host phases
    idle_by_phase = collections.Counter()
    merged = busy[min(busy)] if busy else []
    gaps, cursor = [], w0
    for s, e in merged:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < w1:
        gaps.append((cursor, w1))
    phases = sorted((e.start_ns, e.end_ns, e.name[len(PHASE_PREFIX):])
                    for e in events
                    if e.name.startswith(PHASE_PREFIX) and e.name != WINDOW)
    j = 0
    for g0, g1 in gaps:
        while j < len(phases) and phases[j][1] <= g0:
            j += 1
        covered = 0.0
        k = j
        while k < len(phases) and phases[k][0] < g1:
            ov = _overlap(g0, g1, phases[k][0], phases[k][1])
            idle_by_phase[phases[k][2]] += ov * 1e-9
            covered += ov
            k += 1
        rest = (g1 - g0) - covered
        if rest > 0:
            idle_by_phase["other"] += rest * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "n_chips": len(busy),
        "ops": [(m, n, sec, cnt) for (m, n), (sec, cnt) in ops.items()],
        "modules": dict(modules),
        "idle_by_phase": dict(idle_by_phase),
    }


def breakdown(summary: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most
    time, and idle time by host phase, each at most ``top`` entries."""
    agg = collections.Counter()
    for m, n, sec, _ in summary["ops"]:
        agg[f"{m}/{short_op(n)}" if m else short_op(n)] += sec
    ops = agg.most_common(top)
    idle = sorted(summary["idle_by_phase"].items(), key=lambda x: -x[1])
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle[:top]]}


def module_seconds(summary: dict, pattern: str) -> float:
    """Device seconds of the modules whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(s for m, s in summary["modules"].items() if rx.search(m))


def op_seconds(summary: dict, module: str, op: str) -> tuple:
    """``(seconds, count)`` of the ops whose program matches ``module``
    and whose HLO text matches ``op``."""
    rm, ro = re.compile(module), re.compile(op)
    sec = cnt = 0
    for m, n, s, c in summary["ops"]:
        if rm.search(m) and ro.search(n):
            sec += s
            cnt += c
    return sec, cnt
