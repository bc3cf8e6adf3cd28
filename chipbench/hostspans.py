"""The program's own spans, read two ways, and the device's idle gaps named by them.

While a ``jax.profiler`` session is active the program's tracer
(``accelerate_tpu/tracing.py``) enters a ``TraceAnnotation`` for every span it
opens, with the span's id (``span``), its parent's (``parent``) and its scalar
attributes as stats. The profiler puts those on the plane ``/host:CPU``, on the
line of the thread that opened them and on the clock the device planes use:

* :func:`read` takes them from an ``.xplane.pb``, and :func:`name_gaps` names
  each idle gap of a :class:`chipbench.trace.TraceSummary` by what the host was
  doing when the device fell idle;
* :func:`session_spans` takes the same spans in process, from the list the tracer
  keeps of the newest session: the interval every ``device_trace`` metric is
  computed over, with attributes set after a span opened as well.

``python chipbench/hostspans.py <dir or file>`` prints the ten longest gaps of a
trace so named. A program without the bridge (a commit before it) has no such
span in its trace and no such list: both readers then find nothing, and say so
by ``[]`` and ``None``.
"""

from __future__ import annotations

import dataclasses
import os
import sys

if __name__ == "__main__":
    # run as a script: the script's own directory leaves the path (its trace.py
    # would hide the standard library's) and the checkout takes its place
    _here = os.path.dirname(os.path.abspath(__file__))
    _root = os.path.dirname(_here)
    sys.path[:] = [_root] + [p for p in sys.path if os.path.abspath(p or ".") not in (_here, _root)]

from chipbench import trace

HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class HostSpan:
    name: str
    start_ns: float
    end_ns: float
    thread: str  # the profiler's name of the line, and its place among the lines
    stats: dict  # span, parent, trace_id and the span's scalar attributes


def read(path: str) -> list:
    """The program's spans in a trace file, oldest first. An event of the host
    plane is one of the program's if it carries the ids the bridge gives it."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(trace.find_xplane(path)).planes:
        if plane.name != HOST_PLANE:
            continue
        for index, line in enumerate(plane.lines):
            for event in line.events:
                stats = dict(event.stats)
                if "span" in stats and "parent" in stats:
                    spans.append(HostSpan(event.name, event.start_ns,
                                          event.start_ns + event.duration_ns,
                                          f"{line.name}#{index}", stats))
    spans.sort(key=lambda s: s.start_ns)
    return spans


def name_gaps(gaps: list, spans: list) -> list:
    """``[name, seconds]`` for each ``(seconds, start_ns)`` gap: the innermost
    span that covers the gap's start (of those open then, the one opened last),
    else ``after:<name>`` of the span that closed last before it, else
    ``no_span``."""
    named = []
    for seconds, start_ns in gaps:
        open_then = [s for s in spans if s.start_ns <= start_ns < s.end_ns]
        closed = [s for s in spans if s.end_ns <= start_ns]
        if open_then:
            name = max(open_then, key=lambda s: s.start_ns).name
        elif closed:
            name = "after:" + max(closed, key=lambda s: s.end_ns).name
        else:
            name = "no_span"
        named.append([name, seconds])
    return named


def session_spans(name: str | None = None):
    """The spans the program's tracer kept of the newest profiler session, oldest
    first, or None: where the program has no such list, or the list dropped
    spans at its bound and so is not the whole session."""
    try:
        from accelerate_tpu import tracing

        tracer = tracing.get_tracer()
        if tracer.session_dropped:
            return None
        return tracer.session_spans(name)
    except (ImportError, AttributeError):
        return None


def main(argv) -> int:
    summary = trace.summarize(trace.read_devices(argv[1]))
    spans = read(argv[1])
    print(f"{len(spans)} program span(s) in {HOST_PLANE}; window_s {summary.window_s:.6f} "
          f"idle {100 * summary.idle_share:.3f}%")
    first = min((s.start_ns for s in spans), default=0.0)
    for (name, seconds), (_, start_ns) in zip(name_gaps(summary.gaps, spans), summary.gaps):
        print(f"gap {1e3 * seconds:10.4f} ms at {(start_ns - first) / 1e6:12.3f} ms  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
