"""The program's own spans in a profiler trace, and the device's idle
time by the innermost span over it.

``repro.obs.span`` holds the profiler annotation ``obs.<stage>`` for
each stage it times (``round``, ``round.stack``, ``round.spec``, ...,
``submit.validate``, ``fold.dispatch``), nested on the calling thread
inside the benchmark's ``pb.<phase>`` annotations.  :func:`load` reads
them beside :func:`tracing.load`'s events; :func:`idle_by_span` splits
the first chip's idle time in ``pb.window`` over them, so an idle share
under ``pb.call`` comes with the program stage that held the host.
:func:`tracing.reduce` reads ``pb.*`` names and device planes only, so
its summary is the same with these events or without them.
"""
from __future__ import annotations

import collections

import tracing

#: a span's annotation; a trace may suffix the name with its metadata,
#: ``#key=value,...#``
SPAN_PREFIX = "obs."


def load(path: str) -> list:
    """:func:`tracing.load`'s events and the program's span annotations
    (host events named ``obs.*``, metadata stripped)."""
    from jax.profiler import ProfileData
    out = tracing.load(path)
    for plane in ProfileData.from_file(path).planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append(tracing.Event(
                        plane.name, line.name, e.name.split("#", 1)[0],
                        float(e.start_ns), float(e.duration_ns)))
    return out


def _idle_stretches(events) -> tuple:
    """``(w0, w1, gaps)``: the window and the first chip's idle
    stretches in it, as :func:`tracing.reduce` finds them."""
    windows = [e for e in events if e.name == tracing.WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {tracing.WINDOW} annotation, "
                           f"found {len(windows)}")
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    by_chip = collections.defaultdict(list)
    for e in events:
        if (tracing.DEVICE_PLANE.match(e.plane)
                and e.line == tracing.OPS_LINE):
            s, t = max(e.start_ns, w0), min(e.end_ns, w1)
            if t > s:
                by_chip[e.plane].append((s, t))
    gaps, cursor = [], w0
    for s, e in tracing._union(by_chip[min(by_chip)]) if by_chip else []:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < w1:
        gaps.append((cursor, w1))
    return w0, w1, gaps


def idle_by_span(events) -> dict:
    """The first chip's idle time in the window by the innermost program
    span over it: ``{"<phase>/<stage>": seconds}``, ``<phase>`` being
    the ``pb.`` phase over the same stretch (``other`` where none is):
    ``call/round.spec``, ``call/submit.validate``.  A stretch that no
    program span covers counts under its phase alone (``block``).  The
    values sum to the window's idle time."""
    w0, w1, gaps = _idle_stretches(events)
    host = sorted((e.start_ns, e.end_ns, e.name) for e in events
                  if e.name.startswith(SPAN_PREFIX)
                  or (e.name.startswith(tracing.PHASE_PREFIX)
                      and e.name != tracing.WINDOW))
    # the window cut at every host boundary: between two cuts the same
    # annotations are open, so each piece has one label
    cuts = sorted({w0, w1} | {t for s, e, _ in host for t in (s, e)
                              if w0 < t < w1})
    labels, active, i = [], [], 0
    for c in cuts[:-1]:
        while i < len(host) and host[i][0] <= c:
            active.append(host[i])
            i += 1
        active = [h for h in active if h[1] > c]
        # the innermost span: the latest start, then the earliest end
        inner = min(((-s, e, n) for s, e, n in active
                     if n.startswith(SPAN_PREFIX)), default=None)
        phases = [n for _, _, n in active if not n.startswith(SPAN_PREFIX)]
        key = phases[-1][len(tracing.PHASE_PREFIX):] if phases else "other"
        if inner is not None:
            key += "/" + inner[2][len(SPAN_PREFIX):]
        labels.append(key)
    out = collections.Counter()
    k = 0
    for g0, g1 in gaps:
        while cuts[k + 1] <= g0:
            k += 1
        j = k
        while j < len(labels) and cuts[j] < g1:
            out[labels[j]] += tracing._overlap(g0, g1, cuts[j],
                                               cuts[j + 1]) * 1e-9
            j += 1
    return dict(out)

