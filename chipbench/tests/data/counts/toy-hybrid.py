"""Counts of a made-up hybrid, for the tests: of its two layers one keeps keys and
values in the paged pool (the other would hold a convolution's state), and a
token costs ``TOKEN_FLOPS`` wherever it stands, so that a test tells these counts
from a real family's at sight. The tiny configuration that names this file runs
the program of another family: the test is of where the harness looks, not of
what the program holds."""

from chipbench import work

TOKEN_FLOPS = 1000.0


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    return TOKEN_FLOPS


def decode_flops(cfg: dict, context_len: float) -> float:
    return TOKEN_FLOPS


def kv_layers(cfg: dict) -> int:
    return 1


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return kv_layers(cfg) * work.kv_bytes_per_token_per_layer(cfg, itemsize)
