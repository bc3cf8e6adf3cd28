"""ctypes bridge to the native (C++) host-side kernels in csrc/.

Builds ``libaccel_packing-<hash of csrc/packing.cpp>.so`` on demand with
g++ -O3 under ``.native_cache`` at the root of the checkout
(``ACCELERATE_TPU_CACHE`` names another directory); every entry point has a
NumPy fallback so the framework works on toolchain-less machines.
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import subprocess
from typing import Optional

import numpy as np

from .environment import REPO_ROOT

__all__ = [
    "get_packing_lib",
    "pack_ffd",
    "pack_contiguous",
    "fill_packed",
    "pack_dataset",
    "collate_padded",
    "collate_padded_flat",
]

_CACHE_DIR = os.path.expanduser(
    os.environ.get("ACCELERATE_TPU_CACHE") or os.path.join(REPO_ROOT, ".native_cache")
)


def _source_path() -> str:
    return os.path.join(REPO_ROOT, "csrc", "packing.cpp")


@functools.lru_cache(maxsize=1)
def get_packing_lib() -> Optional[ctypes.CDLL]:
    """Compile (once) and load the native library; None on any failure."""
    src = _source_path()
    if not os.path.exists(src):
        return None
    # the library's name carries the hash of its source, so a build of any
    # other packing.cpp can never be loaded in its place
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(_CACHE_DIR, f"libaccel_packing-{digest}.so")
    try:
        if not os.path.exists(out):
            os.makedirs(_CACHE_DIR, exist_ok=True)
            # build beside the target and rename: a killed or concurrent
            # build never leaves a half-written library under the final name
            tmp = f"{out}.{os.getpid()}.tmp"
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp],
                check=True,
                capture_output=True,
            )
            os.replace(tmp, out)
        lib = ctypes.CDLL(out)
    except (OSError, subprocess.CalledProcessError):
        return None
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    lib.pack_ffd.restype = ctypes.c_int64
    lib.pack_ffd.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p]
    lib.pack_contiguous.restype = ctypes.c_int64
    lib.pack_contiguous.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p]
    lib.fill_packed.restype = None
    lib.fill_packed.argtypes = [
        i32p, i64p, i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, i32p, i32p,
    ]
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    lib.collate_padded.restype = None
    lib.collate_padded.argtypes = [
        i32p, i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, i32p, f32p,
    ]
    return lib


def _pack_ffd_py(lengths: np.ndarray, capacity: int, bin_ids: np.ndarray) -> int:
    order = np.argsort(-lengths, kind="stable")
    remaining: list[int] = []
    for doc in order:
        ln = int(lengths[doc])
        if ln > capacity:
            bin_ids[doc] = -1
            continue
        for b, rem in enumerate(remaining):
            if rem >= ln:
                remaining[b] -= ln
                bin_ids[doc] = b
                break
        else:
            remaining.append(capacity - ln)
            bin_ids[doc] = len(remaining) - 1
    return len(remaining)


def pack_ffd(lengths, capacity: int):
    """First-fit-decreasing packing → (bin_ids, n_bins)."""
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    bin_ids = np.empty_like(lengths)
    lib = get_packing_lib()
    if lib is not None:
        n_bins = int(lib.pack_ffd(lengths, len(lengths), capacity, bin_ids))
    else:
        n_bins = _pack_ffd_py(lengths, capacity, bin_ids)
    return bin_ids, n_bins


def pack_contiguous(lengths, capacity: int):
    """Order-preserving greedy packing → (bin_ids, n_bins)."""
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    bin_ids = np.empty_like(lengths)
    lib = get_packing_lib()
    if lib is not None:
        n_bins = int(lib.pack_contiguous(lengths, len(lengths), capacity, bin_ids))
        return bin_ids, n_bins
    bin_id = 0
    used = 0
    n_bins = 0
    for i, ln in enumerate(lengths):
        if ln > capacity:
            bin_ids[i] = -1
            continue
        if used + ln > capacity:
            bin_id += 1
            used = 0
        bin_ids[i] = bin_id
        used += int(ln)
        n_bins = bin_id + 1
    return bin_ids, n_bins


def fill_packed(tokens, doc_starts, bin_ids, capacity: int, n_bins: int, pad_id: int = 0):
    """Materialize (n_bins, capacity) token + segment-id matrices."""
    tokens = np.ascontiguousarray(tokens, dtype=np.int32)
    doc_starts = np.ascontiguousarray(doc_starts, dtype=np.int64)
    bin_ids = np.ascontiguousarray(bin_ids, dtype=np.int64)
    out_tokens = np.full((n_bins, capacity), pad_id, dtype=np.int32)
    out_segments = np.zeros((n_bins, capacity), dtype=np.int32)
    lib = get_packing_lib()
    if lib is not None:
        lib.fill_packed(
            tokens, doc_starts, bin_ids, len(bin_ids), capacity, n_bins,
            out_tokens.reshape(-1), out_segments.reshape(-1),
        )
        return out_tokens, out_segments
    cursor = np.zeros(n_bins, dtype=np.int64)
    seg = np.zeros(n_bins, dtype=np.int32)
    for i, b in enumerate(bin_ids):
        if b < 0:
            continue
        ln = int(doc_starts[i + 1] - doc_starts[i])
        if cursor[b] + ln > capacity:
            continue
        seg[b] += 1
        sl = slice(int(cursor[b]), int(cursor[b]) + ln)
        out_tokens[b, sl] = tokens[doc_starts[i] : doc_starts[i + 1]]
        out_segments[b, sl] = seg[b]
        cursor[b] += ln
    return out_tokens, out_segments


def collate_padded_flat(flat, offsets, seq_len: int, pad_id: int = 0):
    """Padded collation straight from a FLAT token buffer + offsets — the hot
    path for tokenized memmap corpora, where building per-doc arrays would
    copy everything once extra. flat: (total,) int32; offsets: (n+1,) int64;
    returns ((n, S) int32 tokens, (n, S) f32 mask)."""
    flat = np.ascontiguousarray(flat, dtype=np.int32)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = len(offsets) - 1
    out_tokens = np.empty((n, seq_len), dtype=np.int32)
    out_mask = np.empty((n, seq_len), dtype=np.float32)
    lib = get_packing_lib()
    if lib is not None:
        lib.collate_padded(
            flat, offsets, n, seq_len, pad_id,
            out_tokens.reshape(-1), out_mask.reshape(-1),
        )
        return out_tokens, out_mask
    out_tokens.fill(pad_id)
    out_mask.fill(0.0)
    for i in range(n):
        ln = min(int(offsets[i + 1] - offsets[i]), seq_len)
        out_tokens[i, :ln] = flat[offsets[i] : offsets[i] + ln]
        out_mask[i, :ln] = 1.0
    return out_tokens, out_mask


def collate_padded(docs, seq_len: Optional[int] = None, pad_id: int = 0):
    """Ragged list of 1-D int sequences → ((n, S) int32 tokens, (n, S) f32
    mask). The threaded C++ kernel plays torch's C++ pad_sequence/collate
    role; NumPy fallback inside :func:`collate_padded_flat`."""
    n = len(docs)
    arrays = [np.asarray(d, dtype=np.int32).ravel() for d in docs]
    lengths = np.asarray([a.size for a in arrays], dtype=np.int64)
    if seq_len is None:
        seq_len = int(lengths.max()) if n else 0
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    flat = np.concatenate(arrays) if n else np.zeros(0, np.int32)
    return collate_padded_flat(flat, offsets, seq_len, pad_id)


def pack_dataset(documents, seq_len: int, pad_id: int = 0, preserve_order: bool = False):
    """Pack a list of variable-length token sequences into fixed (N, seq_len)
    training rows + segment ids (for segment-masked attention)."""
    lengths = np.asarray([len(d) for d in documents], dtype=np.int64)
    doc_starts = np.zeros(len(documents) + 1, dtype=np.int64)
    np.cumsum(lengths, out=doc_starts[1:])
    tokens = np.concatenate([np.asarray(d, dtype=np.int32) for d in documents]) if documents else np.zeros(0, np.int32)
    packer = pack_contiguous if preserve_order else pack_ffd
    bin_ids, n_bins = packer(lengths, seq_len)
    return fill_packed(tokens, doc_starts, bin_ids, seq_len, n_bins, pad_id=pad_id)


def packed_loss_mask(segment_ids: np.ndarray) -> np.ndarray:
    """(N, S) segment ids → (N, S) f32 loss mask for next-token training on
    packed rows: position i trains only when tokens i and i+1 belong to the
    same (nonzero) document — boundary targets (the next document's first
    token) and padding never contribute loss. Matches the loss_mask
    convention of models/llama.py `_mask_of` (mask index i ↔ label
    input[i+1])."""
    seg = np.asarray(segment_ids, dtype=np.int32)
    mask = np.zeros(seg.shape, dtype=np.float32)
    mask[:, :-1] = ((seg[:, :-1] == seg[:, 1:]) & (seg[:, :-1] > 0)).astype(np.float32)
    return mask


def packed_position_ids(segment_ids: np.ndarray) -> np.ndarray:
    """(N, S) segment ids → (N, S) int32 within-document positions (RoPE /
    learned-position indices restart at every packed document; padding gets
    0). Feed as ``batch["position_ids"]`` next to ``segment_ids``."""
    seg = np.asarray(segment_ids, dtype=np.int32)
    n, s = seg.shape
    idx = np.arange(s, dtype=np.int32)[None, :].repeat(n, axis=0)
    # each position's segment-start index: the running max of boundary
    # positions (fully vectorized — this runs per dataset build)
    change = np.ones((n, s), dtype=bool)
    change[:, 1:] = seg[:, 1:] != seg[:, :-1]
    start = np.maximum.accumulate(np.where(change, idx, 0), axis=1)
    pos = idx - start
    pos[seg == 0] = 0
    return pos
