"""One run of one cell of the benchmark.

``python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``

A new process per run. It needs the TPU chips the cell asks for and has no other
branch: without them it says so, exits non-zero and prints no result. It finds
the cell's file, its configuration's file, its driver and its per-layer metrics
by name, builds the model on the device from ``--seed``, warms up the cell's own
shapes (set-up, ``setup_s``: process start to window start), measures for
``--seconds``, frees the program's state, decides ``correct`` against the plain
reference and prints one JSON object as its last line. Facts worth keeping go on
earlier lines; the numbers compared, each beside its limit, are the last lines of
standard error and the last key of the result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse
import dataclasses
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the script's own directory leaves the path (its trace.py would hide the
# standard library's) and the checkout takes its place
sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".") not in (HERE, ROOT)]

from chipbench import lib
from chipbench.lib import BenchError

OUT_DIR = os.path.join(ROOT, ".chipbench_out")


@dataclasses.dataclass
class Context:
    name: str
    workload: dict
    config: dict
    seed: int
    chips: int
    peaks: dict | None = None
    memory_bytes: int | None = None  # one chip's memory, as the device states it


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader is given."""

    ctx: Context
    driver: object
    result: dict  # what the driver's window returned
    summary: object  # trace.TraceSummary of the traced window


def say(**facts) -> None:
    print(json.dumps(facts), flush=True)


def find_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(
            f"chipbench: needs a TPU, JAX found {devices[0].platform!r} "
            f"({len(devices)} device(s)); nothing was run"
        )
    if len(devices) != chips:
        raise BenchError(
            f"chipbench: the cell asks for {chips} chip(s), JAX found {len(devices)}; "
            "nothing was run"
        )
    return devices


def start_trace(directory: str) -> None:
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=options)


class WindowHooks:
    """What a driver calls from inside its window: ``tick(elapsed)`` as often as
    it looks at the clock, ``close()`` the moment the window closes, before
    anything it does afterwards. A traced run traces only the window's last
    ``trace_seconds`` (traces are large, and stopping one takes about a second,
    which so falls after the close); everything else is measured over the
    whole window."""

    def __init__(self, watch, trace_dir=None, trace_from: float = 0.0):
        self.watch, self.trace_dir, self.trace_from = watch, trace_dir, trace_from
        self.tracing = False
        self.built_before = watch.builds
        self.built_in_window = None

    def tick(self, elapsed: float) -> None:
        if self.trace_dir and not self.tracing and elapsed >= self.trace_from:
            start_trace(self.trace_dir)
            self.tracing = True

    def close(self) -> None:
        import jax

        self.built_in_window = self.watch.builds - self.built_before
        if self.tracing:
            jax.profiler.stop_trace()
        elif self.trace_dir:
            raise BenchError("chipbench: the window closed before its trace began")


def enable_cache() -> str:
    """The program's own compile cache (``JAX_COMPILATION_CACHE_DIR`` or
    ``.jax_cache/`` in the checkout), with small programs cached too, so that a
    warm run builds nothing."""
    import jax

    try:
        from accelerate_tpu.utils.environment import enable_compile_cache
    except ImportError as error:
        raise BenchError(f"chipbench: the program under test is not in this checkout: {error}")
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, {name: [value, limit]})``. Every number the driver compares
    has a limit in the cell's file, and every limit a number."""
    compared = {k: v for k, v in numbers.items() if not k.startswith("_")}
    if set(compared) != set(limits):
        raise BenchError(
            f"chipbench: the driver compares {sorted(compared)}, the cell's file "
            f"limits {sorted(limits)}"
        )
    table = {name: [float(compared[name]), float(limits[name])] for name in sorted(compared)}
    correct = all(value == value and value <= limit for value, limit in table.values())
    return correct, table


def execute(args) -> dict:
    """The whole run; returns the result that ``main`` prints last."""
    benchmark = lib.load_benchmark()
    workload, config = lib.load_cell(args.workload)
    end_to_end, per_layer = lib.cell_metrics(benchmark, args.workload)
    ctx = Context(args.workload, workload, config, int(args.seed), int(workload["chips"]))
    devices = find_devices(ctx.chips)
    ctx.peaks = lib.peaks_for(devices[0].device_kind)
    ctx.memory_bytes = (devices[0].memory_stats() or {}).get("bytes_limit")

    cache_dir = enable_cache()
    watch = lib.CompileWatch.get()

    driver = lib.load_module("drivers", workload["driver"])
    state = driver.setup(ctx)
    trace_dir = os.path.join(OUT_DIR, "trace-" + args.workload) if args.trace else None
    hooks = WindowHooks(
        watch, trace_dir, max(0.0, float(args.seconds) - float(workload["trace_seconds"]))
    )
    hooks.tick(0.0)  # a window no longer than the trace is traced from its start
    setup_s = time.perf_counter() - _T0

    result = driver.window(ctx, state, float(args.seconds), hooks)

    device = lib.device_facts(devices)
    if hooks.built_in_window != 0:
        raise BenchError(
            f"chipbench: {hooks.built_in_window} program(s) were compiled inside the measured window"
        )
    say(workload=args.workload, seed=ctx.seed, setup_s=setup_s, window=result["window_s"],
        cache_dir=os.path.relpath(cache_dir, ROOT), compiles=watch.facts(),
        setup=getattr(state, "facts", {}), facts=result.get("facts", {}))
    if "requests" in result:
        say(requests=result["requests"])

    closed = time.perf_counter()
    driver.release(state)
    released = time.perf_counter()
    numbers = driver.check(ctx, state)
    correct, table = judge(numbers, workload["limits"])
    say(check_facts={k: v for k, v in numbers.items() if k.startswith("_")},
        release_s=released - closed, check_s=time.perf_counter() - released)

    values = dict(result["end_to_end"], setup_s=setup_s)
    metrics = {}
    out = {"correct": correct, "attempted": int(result["attempted"]),
           "failed": int(result["failed"]), "metrics": metrics, "device": device}
    if args.trace:
        from chipbench import hostspans
        from chipbench import trace as trace_lib

        summary = trace_lib.summarize(trace_lib.read_devices(trace_dir))
        host_spans = hostspans.read(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = summary.busy_s, summary.window_s
        run = Run(ctx, driver, result, summary)
        for entry in per_layer:
            value = lib.load_module("metrics", entry["name"]).read(run)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
        out["breakdown"] = trace_lib.breakdown(summary)
        if host_spans:  # a program without the bridge keeps unattributed_gap_<n>
            out["breakdown"]["idle_gaps"] = hostspans.name_gaps(summary.gaps, host_spans)
        top = sorted(summary.op_self_s.items(), key=lambda kv: kv[1], reverse=True)[:40]
        say(trace_programs={k: [len(v), sum(v)] for k, v in summary.program_s.items()},
            trace_ops=[[trace_lib.short_name(name, 400), seconds, summary.op_count[name]]
                       for name, seconds in top])
    else:
        for entry in end_to_end:
            metrics[entry["name"]] = {"value": float(values[entry["name"]]), "unit": entry["unit"]}
    out["check"] = table
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        out = execute(args)
    except BenchError as error:
        print(str(error), file=sys.stderr)
        return 3
    for name, (value, limit) in out["check"].items():
        verdict = "ok" if value <= limit else "OVER"
        print(f"check {name} {value:.6g} limit {limit:.6g} {verdict}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
