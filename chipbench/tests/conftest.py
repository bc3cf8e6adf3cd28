"""CPU self-tests of the harness at tiny sizes: ``pytest chipbench/tests -q``.
Not part of the repository's tier-1 tests."""

import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest

from chipbench import lib, run

TINY = {
    "end_to_end": [
        {"name": "train_tokens_per_s_chip", "unit": "tokens/s/chip", "workloads": ["tiny-llama.train"]},
        {"name": "serve_tokens_per_s", "unit": "tokens/s",
         "workloads": ["tiny-gpt2.serve", "tiny-hybrid.serve"]},
        {"name": "norm_latency_p50_ms", "unit": "ms",
         "workloads": ["tiny-gpt2.serve", "tiny-hybrid.serve"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}


@pytest.fixture
def tiny_cells(monkeypatch):
    """The tiny cells of ``tests/data`` beside the real ones, a benchmark file
    that lists them, and the CPU in the chip's place: past the harness's look
    for a chip, with no peaks (a per-layer metric is never read here)."""
    import jax

    monkeypatch.setattr(lib, "DATA_DIRS", [lib.HERE, os.path.join(HERE, "data")])
    monkeypatch.setattr(lib, "load_benchmark", lambda: TINY)
    monkeypatch.setattr(run, "find_devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(lib, "peaks_for", lambda kind: None)


def args(workload, seed=7, seconds=1.0, trace=0):
    return types.SimpleNamespace(workload=workload, seed=seed, seconds=seconds, trace=trace)
