"""Device-idle gaps named by the program span the host was in.

The program enters a ``jax.profiler.TraceAnnotation`` named ``sd/<span>``
for each of its thread-bound spans, so a profile holds them on the host
plane, one line per Python thread, on the clock of the device's ops.
``host_spans`` reads them from the ``.xplane.pb``; ``name_gaps`` names
each gap ``<job>/<span>`` after the span that covers the most of it.

Only the innermost spans of a thread vote: those that hold no other span
of that thread. An enclosing span's own time is mostly waiting (the job
thread inside ``pipeline.run`` waits on the stage queues), and would
otherwise cover every gap of its job. A name's cover is the wall time of
the gap under at least one of its spans, on any thread, so four shards
gathering at once count once. A gap that no span covers keeps its job's
name. Gaps and their lengths pass through as they are.
"""

from __future__ import annotations

import glob
from collections import defaultdict

from .trace import union_ns

#: the program's namespace on the host plane
PREFIX = "sd/"

Span = tuple[float, float, str]  # (start_s, end_s, name), from the mark


def host_spans(logdir: str, t0_ns: float) -> dict[tuple[str, int], list[Span]]:
    """``{(plane, line index): [(start_s, end_s, name)]}`` of the ``sd/``
    events of the profile under ``logdir``, in seconds from ``t0_ns`` and
    without the prefix. A line is one thread (every Python thread's line
    is named ``python``, so its index is the key)."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    out: dict[tuple[str, int], list[Span]] = defaultdict(list)
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for idx, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = (ev.start_ns - t0_ns) / 1e9
                    out[(plane.name, idx)].append(
                        (s, s + ev.duration_ns / 1e9, ev.name[len(PREFIX):]))
    return dict(out)


def leaves(spans: list[Span]) -> list[Span]:
    """The spans of one thread that hold no other span of it. Spans on a
    thread nest, so in (start, longest first) order a span holds another
    exactly when the next one starts before it ends."""
    order = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    return [sp for sp, nxt in zip(order, order[1:] + [None])
            if nxt is None or nxt[0] >= sp[1]]


def cover(leaf_spans: list[Span], g0: float, g1: float) -> dict[str, float]:
    """Seconds of ``[g0, g1]`` under each span name, over ``leaf_spans``
    of any thread."""
    by_name: dict[str, list[tuple[float, float]]] = defaultdict(list)
    for s, e, name in leaf_spans:
        if s < g1 and e > g0:
            by_name[name].append((max(s, g0), min(e, g1)))
    return {name: union_ns(iv) for name, iv in by_name.items()}


def name_gaps(gaps: list[tuple[float, float]], jobs: list[str],
              threads: dict[object, list[Span]]) -> list[list]:
    """``[[name, seconds]]`` for ``gaps`` (seconds from the mark), where
    ``jobs[i]`` is the job-level name of gap ``i`` and ``threads`` is
    ``host_spans``'s output."""
    leaf_spans = [sp for spans in threads.values() for sp in leaves(spans)]
    named = []
    for (g0, g1), job in zip(gaps, jobs):
        got = cover(leaf_spans, g0, g1)
        best = max(got, key=lambda n: (got[n], n), default=None)
        named.append([f"{job}/{best}" if best else job, g1 - g0])
    return named
