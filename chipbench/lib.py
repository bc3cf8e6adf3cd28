"""What every part of the benchmark shares: files found by name, the table of
peaks, order statistics, the watch for compiles, and device facts.

Nothing here imports the program under test.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(RuntimeError):
    """The run cannot give a result: it ends non-zero and prints none."""


# where files are looked for by name; a test adds a directory of tiny cells
DATA_DIRS = [HERE]


def _find(*parts: str) -> str:
    for base in DATA_DIRS:
        path = os.path.join(base, *parts)
        if os.path.isfile(path):
            return path
    raise BenchError(f"chipbench: no file {os.path.join('chipbench', *parts)}")


# ------------------------------------------------------------------ files by name
def load_json(*parts: str) -> dict:
    with open(_find(*parts)) as fh:
        return json.load(fh)


def load_benchmark() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise BenchError("chipbench: no BENCHMARK.json at the root of the checkout")
    with open(path) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module, found by its file name: a
    driver, a reference, a family's counts or a per-layer metric that a later
    PR drops in is found the same way as those that are here."""
    path = _find(kind, name + ".py")
    module_name = f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if module_name in sys.modules:
        return sys.modules[module_name]
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module
    spec.loader.exec_module(module)
    return module


def load_cell(workload_name: str) -> tuple:
    """The cell's file and its configuration's file, by the names in them."""
    workload = load_json("workloads", workload_name + ".json")
    config = load_json("configs", workload["config"] + ".json")
    return workload, config


def counts_name(config: dict) -> str:
    """Which ``counts/<name>.py`` holds the configuration's counts: its ``counts``
    key where it has one (a hybrid brings its own), else its ``family``."""
    return config.get("counts") or config["family"]


def find_count(config: dict, function: str):
    """``function`` of the configuration's counts file, or None where the file
    has no such function: what a per-layer metric's reader asks, which then
    returns None itself."""
    return getattr(load_module("counts", counts_name(config)), function, None)


def count(config: dict, function: str):
    """The same for whoever cannot go on without (a driver): a missing function
    ends the run, never another family's formula in its place."""
    found = find_count(config, function)
    if found is None:
        raise BenchError(
            f"chipbench: chipbench/counts/{counts_name(config)}.py has no {function}(), "
            f"which this cell asks of it"
        )
    return found


def cell_metrics(benchmark: dict, workload_name: str) -> tuple:
    """The end-to-end and the per-layer entries of ``BENCHMARK.json`` that
    this cell reports: those that list it, and those that list no cell."""

    def mine(entries):
        return [m for m in entries if workload_name in m.get("workloads", [workload_name])]

    return mine(benchmark["end_to_end"]), mine(benchmark["per_layer"])


def peaks_for(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table:
        raise BenchError(
            f"chipbench: no peaks for device kind {device_kind!r} in chipbench/peaks.json"
        )
    return table[device_kind]


# ---------------------------------------------------------------- order statistics
def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    order statistics, as numpy's default does it."""
    data = sorted(float(v) for v in values)
    if not data:
        raise ValueError("percentile of nothing")
    rank = (len(data) - 1) * q / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    return data[lo] + (data[hi] - data[lo]) * (rank - lo)


def median(values) -> float:
    return percentile(values, 50.0)


# ------------------------------------------------------------------ compile watch
class CompileWatch:
    """Counts the programs JAX builds or fetches from its persistent cache.
    JAX's listeners cannot be taken off again, so a process has one watch."""

    _instance = None

    def __init__(self):
        import jax

        self.builds = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    @classmethod
    def get(cls) -> "CompileWatch":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.builds += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def facts(self) -> dict:
        return {
            "programs_built": self.builds, "compile_s": round(self.seconds, 2),
            "cache_hits": self.cache_hits, "cache_misses": self.cache_misses,
        }


# -------------------------------------------------------------------- the device
def device_facts(devices) -> dict:
    peak = limit = 0
    for device in devices:
        stats = device.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        limit = max(limit, int(stats.get("bytes_limit", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
        "memory_limit_bytes": limit,
    }


def free_device_memory() -> None:
    """After the caller has dropped its own references: compiled programs,
    their constants and whatever the collector still holds."""
    import jax

    jax.clear_caches()
    gc.collect()


def seed_key(seed: int, stream: int = 0):
    """A threefry key from a seed of up to 64 bits (``--seed`` passes 2**31,
    which an int32 does not hold) and a stream number."""
    import jax
    import jax.numpy as jnp

    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    data = jnp.array([(seed >> 32) ^ (stream * 0x9E3779B9 & 0xFFFFFFFF), seed & 0xFFFFFFFF],
                     dtype=jnp.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")
