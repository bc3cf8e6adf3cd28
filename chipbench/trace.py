"""From the profiler's ``.xplane.pb`` to busy and idle time, time per device
operation and time per compiled program.

A TPU's plane is named ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one event
for every operation that ran, named by its whole HLO instruction (a ``while`` holds
its body's operations inside its own interval, so times are self times: an event's
length less its children's),
and its line ``XLA Modules`` one event for every execution of a compiled program.
Everything is on the device's clock; the window is from the first operation's
start to the last one's end.

``python chipbench/trace.py <dir or file>`` prints what a trace holds, for a look
by hand before a pattern is written against it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.duration_ns


@dataclasses.dataclass
class DeviceTrace:
    """One device's operations and program executions."""

    ops: list
    modules: list


@dataclasses.dataclass
class TraceSummary:
    devices: int
    window_s: float  # first operation's start to the last one's end, mean over devices
    busy_s: float  # union of the operations' intervals, mean over devices
    op_self_s: dict  # operation name -> summed self seconds, mean over devices
    op_count: dict  # operation name -> events, mean over devices
    program_s: dict  # program name -> list of execution seconds (device 0)
    gaps: list  # (seconds, start_ns) of the longest idle gaps on device 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def read_devices(path: str) -> list:
    """The device planes of a trace file as :class:`DeviceTrace`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    devices = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        devices.append(DeviceTrace(
            ops=[Event(e.name, e.start_ns, e.duration_ns)
                 for e in lines[OPS_LINE].events] if OPS_LINE in lines else [],
            modules=[Event(e.name, e.start_ns, e.duration_ns)
                     for e in lines[MODULES_LINE].events] if MODULES_LINE in lines else [],
        ))
    return devices


def program_name(event_name: str) -> str:
    """``jit_fused(1234567)`` -> ``jit_fused``."""
    return event_name.split("(", 1)[0]


def union_and_gaps(events: list) -> tuple:
    """Seconds covered by the union of the events' intervals, and the gaps
    between them as ``(seconds, start_ns)``."""
    busy_ns, gaps = 0.0, []
    edge = None
    for event in sorted(events, key=lambda e: e.start_ns):
        if edge is None:
            busy_ns += event.duration_ns
            edge = event.end_ns
        elif event.start_ns >= edge:
            if event.start_ns > edge:
                gaps.append(((event.start_ns - edge) / 1e9, edge))
            busy_ns += event.duration_ns
            edge = event.end_ns
        elif event.end_ns > edge:
            busy_ns += event.end_ns - edge
            edge = event.end_ns
    return busy_ns / 1e9, gaps


def self_times(events: list) -> list:
    """``(event, self_ns)``: an event's length less that of the events nested
    directly inside it."""
    ordered = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    own = [e.duration_ns for e in ordered]
    stack: list = []
    for index, event in enumerate(ordered):
        while stack and ordered[stack[-1]].end_ns <= event.start_ns:
            stack.pop()
        if stack:
            own[stack[-1]] -= event.duration_ns
        stack.append(index)
    return [(event, max(0.0, ns)) for event, ns in zip(ordered, own)]


def summarize(devices: list) -> TraceSummary:
    if not devices or not any(d.ops for d in devices):
        raise ValueError("the trace holds no operation on a TPU")
    n = len(devices)
    window = busy = 0.0
    op_self: dict = {}
    op_count: dict = {}
    for device in devices:
        start = min(e.start_ns for e in device.ops)
        end = max(e.end_ns for e in device.ops)
        window += (end - start) / 1e9
        busy += union_and_gaps(device.ops)[0]
        for event, own_ns in self_times(device.ops):
            op_self[event.name] = op_self.get(event.name, 0.0) + own_ns / 1e9
            op_count[event.name] = op_count.get(event.name, 0) + 1
    programs: dict = {}
    for event in devices[0].modules:
        programs.setdefault(program_name(event.name), []).append(event.duration_ns / 1e9)
    gaps = sorted(union_and_gaps(devices[0].ops)[1], reverse=True)[:10]
    return TraceSummary(
        devices=n, window_s=window / n, busy_s=busy / n,
        op_self_s={k: v / n for k, v in op_self.items()},
        op_count={k: v / n for k, v in op_count.items()},
        program_s=programs, gaps=gaps,
    )


def short_name(event_name: str, width: int = 140) -> str:
    """An operation's event is named by its whole HLO instruction; the breakdown
    keeps the instruction's name and the start of what follows."""
    head, _, rest = event_name.partition(" = ")
    return (head.lstrip("%") + " " + rest)[:width].rstrip()


def time_matching(summary: TraceSummary, pattern: str) -> tuple:
    """Summed self seconds and events of the operations whose name matches."""
    rx = re.compile(pattern)
    names = [name for name in summary.op_self_s if rx.search(name)]
    return (sum(summary.op_self_s[n] for n in names),
            sum(summary.op_count[n] for n in names))


def programs_matching(summary: TraceSummary, pattern: str) -> list:
    """Execution seconds of the compiled programs whose name matches."""
    rx = re.compile(pattern)
    out = []
    for name, seconds in summary.program_s.items():
        if rx.search(name):
            out.extend(seconds)
    return out


def breakdown(summary: TraceSummary) -> dict:
    """The contract's ``breakdown``: the ten operations that took most device
    time, and the ten longest idle gaps, longest first. A gap is named here by
    its rank alone; where the trace holds the program's spans, ``run.py`` puts
    ``hostspans.name_gaps`` in their place, which names each by what the host
    was doing when the device fell idle (same gaps, same seconds)."""
    top = sorted(summary.op_self_s.items(), key=lambda kv: kv[1], reverse=True)[:10]
    return {
        "device_ops": [[short_name(name), seconds] for name, seconds in top],
        "idle_gaps": [
            [f"unattributed_gap_{index}", seconds]
            for index, (seconds, _start) in enumerate(summary.gaps)
        ],
    }


def main(argv) -> int:
    summary = summarize(read_devices(argv[1]))
    print(f"devices {summary.devices} window_s {summary.window_s:.6f} "
          f"busy_s {summary.busy_s:.6f} idle {100 * summary.idle_share:.2f}%")
    for name, seconds in sorted(summary.program_s.items()):
        print(f"program {name}: {len(seconds)} executions, {sum(seconds):.6f} s")
    top = sorted(summary.op_self_s.items(), key=lambda kv: kv[1], reverse=True)
    for name, seconds in top[: int(argv[2]) if len(argv) > 2 else 60]:
        print(f"op {seconds:10.6f} s {summary.op_count[name]:8.0f} x  {short_name(name, 400)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
