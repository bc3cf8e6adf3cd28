"""Continuous-batching decode engine: slot-based KV arena + iteration-level
scheduling state (Orca-style, the technique behind vLLM-class serving
throughput).

The static serving path (:class:`~accelerate_tpu.serving.InferenceServer`
``mode="static"``) batches whole ``generate()`` calls at admission time:
requests only coalesce when they share a group key (prompt length, token
budget, sampling-branch flags, seed for sampled traffic), and every batch
then runs its full fused prefill+decode scan to ``max_new_tokens`` even if
every row hit EOS at step 3. This module removes all three costs at once:

* **Slot-based KV store** — per-slot ``pos/done/budget/token`` vectors and
  per-slot sampling params (temperature, top_k, top_p, eos id, PRNG key)
  over a :mod:`~accelerate_tpu.kvcache` backend: ``dense`` (a fixed
  ``(layers, slots, max_len, kv_heads, head_dim)`` arena), ``paged``
  (shared block pool + per-slot block tables + copy-on-write prefix
  caching — admission gated on free *blocks*, so HBM stops reserving every
  slot's worst case), or ``paged_int8`` (int8 pool with per-block scales).
  Mixed greedy/sampled/any-seed traffic shares ONE compiled decode
  program: sampling params are per-row traced operands, not compile keys,
  so the seed and ``max_new_tokens`` group-key fragmentation of the static
  path disappears entirely.
* **Two jitted program families** per (slots, max_len) configuration:
  ``prefill_insert`` (bucketed prompt forward via the models'
  ``*_prefill_at``, then scatter its KV rows into a free arena slot with
  ``lax.dynamic_update_slice``; one program a rung of
  :func:`prefill_rungs`: the bucket, and half of it where that is at least
  128 positions of whole blocks) and ``decode_step`` (one fused step over
  ALL slots — finished/vacant slots ride along masked). The KV arena and
  per-slot position/PRNG state are donated across calls, so steady-state
  decode performs zero reallocation of the arena. Speculative decoding
  (``spec="ngram"``) adds exactly ONE more: ``verify_step``, a fused
  multi-token forward over a fixed-``spec_draft_len`` padded draft window
  for every slot at once (actual per-slot draft lengths are traced mask
  operands, never compile keys), bounding the engine at three programs
  per (slots, max_len, spec_draft_len) config.
* **Prompt-lookup speculative decoding** — a host-side per-slot n-gram
  drafter matches the last tokens of a slot's history (prompt + emitted)
  against earlier occurrences and proposes the continuation, no second
  model needed (strongest on code/RAG-style repetitive traffic). One
  ``verify_step`` scores all drafts, accepts each slot's longest matching
  prefix (exact for greedy; standard rejection sampling against the
  verifier's filtered distribution for ``temperature>0``), and commits
  ONLY accepted tokens' KV columns — a rejected suffix "rewinds" by never
  being committed, so paged block tables/refcounts have no rollback path.
  A per-slot acceptance-rate EWMA stops drafting for incompressible
  traffic, and a step where nobody drafted falls back to the plain
  ``decode_step`` program (the k=0 path costs nothing extra).
* **Iteration-level scheduling state** — the host (the serving worker)
  retires finished slots, admits queued requests into freed slots with an
  interleaved prefill, and enforces per-slot token budgets exactly. The
  done-mask readback is deferred ``readback_lag`` programs (the same
  deferred-ring trick as telemetry's :class:`DeferredReadbackRing`), so
  retirement decisions never force a synchronous device round-trip on the
  decode hot path.

The engine is deliberately server-agnostic: occupants carry an opaque
``tag`` (the server's request object) and the engine only speaks tokens.
Scheduling policy — deadlines, backpressure, degradation, drain — lives in
:mod:`accelerate_tpu.serving`.
"""

from __future__ import annotations

import collections
import threading
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import perfwatch, tracing
from .logging import get_logger
from .ops.paged_decode import decode_copy_plan, decode_walked_positions
from .utils.fault import (
    EngineCapacityError,
    EngineInvariantError,
    TransferStaleEpochError,
)

logger = get_logger(__name__)

__all__ = ["ContinuousBatchingEngine", "SlotOccupant", "RemotePrefill", "prefill_rungs"]

# the MXU's row tile: a prefill of fewer rows is bound by its weights' bytes,
# so a narrower program than this buys nothing
_MIN_RUNG = 128


def prefill_rungs(prompt_bucket: int, block_size: int) -> Tuple[int, ...]:
    """The widths a prompt is padded to for its prefill, ascending: the
    bucket, and half of it where that half is at least :data:`_MIN_RUNG`
    positions and whole blocks of ``block_size``."""
    half = prompt_bucket // 2
    if half >= _MIN_RUNG and half % block_size == 0:
        return (half, prompt_bucket)
    return (prompt_bucket,)


# ------------------------------------------------------------------ occupants
@dataclass
class SlotOccupant:
    """Host-side record of one request living in an arena slot."""

    slot: int
    tag: Any  # opaque (the server's request); the engine never inspects it
    prompt: np.ndarray  # (prompt_len,) int32, UNpadded
    budget: int  # exact number of new tokens owed (post-degradation clamp)
    pad_id: int
    eos_id: Optional[int]
    inserted_s: float
    tokens: List[int] = field(default_factory=list)  # emitted new tokens
    finished: bool = False
    first_token_s: Optional[float] = None  # host clock at first popped token
    # chunked-prefill state (long prompts only): PREFILLING slots ride every
    # decode step masked (done=True on device) until their last chunk
    # commits; ``prefill_pos`` is the next chunk's start offset and
    # ``chunk_args`` the stashed request params the deferred last chunk
    # needs (key data, sampling operands)
    prefilling: bool = False
    prefill_pos: int = 0
    chunk_args: Optional[dict] = None
    # speculative-decoding state: per-slot acceptance EWMA (starts above
    # the gate floor so fresh occupants draft immediately, but low enough
    # that a few rejected drafts gate an incompressible slot off fast), a
    # cooldown counter for re-probing after the EWMA gates the slot, and
    # the current cooldown length (doubles on every all-rejected verify up
    # to _SPEC_COOLDOWN_MAX, resets once a draft lands — exponential
    # backoff so hopeless slots probe rarely)
    spec_ewma: float = 0.3
    spec_skips: int = 0
    spec_cooldown: int = 8
    # request trace ID (copied from the tag at insert) and the number of
    # fused programs that emitted tokens for this occupant — the
    # ``ServingResult.decode_steps`` span-summary source
    trace_id: Optional[str] = None
    decode_steps: int = 0
    # the paged decode kernel's copies over one leaf of one layer, by the
    # slot's live blocks (`decode_copy_plan` of its table row, made once at
    # admission); None where that kernel does not walk the slot
    copies: Optional[np.ndarray] = None

    def output_row(self) -> np.ndarray:
        """prompt + emitted tokens, padded with ``pad_id`` to the full
        budget — byte-compatible with the static ``generate()`` row shape
        (prompt_len + max_new_tokens,) so static/continuous outputs compare
        directly."""
        out = np.full(len(self.prompt) + self.budget, self.pad_id, dtype=np.int32)
        out[: len(self.prompt)] = self.prompt
        out[len(self.prompt) : len(self.prompt) + len(self.tokens)] = self.tokens
        return out


def _held_positions(occ: Optional[SlotOccupant]) -> int:
    """Positions of keys and values a slot holds that still count: the prompt
    (as far as its chunks have got) and the tokens emitted; 0 for a slot that
    is vacant or whose request has finished."""
    if occ is None or occ.finished:
        return 0
    return (occ.prefill_pos if occ.prefilling else len(occ.prompt)) + len(occ.tokens)


@dataclass
class RemotePrefill:
    """A prompt forward computed OFF the decode loop (prefill/decode
    disaggregation): the bucketed prefill's KV window, first sampled token,
    and advanced PRNG key, ready for :meth:`ContinuousBatchingEngine
    .insert_prefilled` to scatter into an arena slot with a cheap
    commit-only program. Produced by :meth:`ContinuousBatchingEngine
    .prefill_remote` — safe to call from dedicated prefill worker threads
    because it touches no arena or slot state. The split is bitwise
    equivalent to :meth:`~ContinuousBatchingEngine.insert`: same forward,
    same key discipline, same first-token sample."""

    prompt: np.ndarray  # (prompt_len,) int32, UNpadded
    max_new_tokens: int
    temperature: float
    top_k: Optional[int]
    top_p: Optional[float]
    eos_token_id: Optional[int]
    pad_token_id: Optional[int]
    seed: int
    cache: Any  # the forward's max_len-wide KV window (device pytree)
    t0: Any  # first sampled token (device scalar)
    next_key: Any  # advanced per-slot PRNG key data (device)
    # structural compatibility stamp: a RemotePrefill may only be committed
    # into an engine with the same model config, prompt bucket, and arena
    # length it was computed against (failover recomputes instead)
    engine_config: Any = None
    prompt_bucket: int = 0
    max_len: int = 0
    # wire-transfer fence (accelerate_tpu.kvtransfer): ``(slot, epoch)``
    # minted by the receiving engine's ``reserve_slot`` when this prefill
    # arrived over a transport. ``insert_prefilled`` refuses to commit a
    # reservation whose epoch the engine has since bumped (the slot was
    # released/recycled mid-transfer) — TransferStaleEpochError, and the
    # caller falls back to a local prefill. None for the by-reference
    # same-process hand-off.
    reservation: Optional[Tuple[int, int]] = None

    def to_bytes(self) -> bytes:
        """Versioned wire encoding of this prefill (magic + header + raw
        leaf bytes) — see :func:`accelerate_tpu.kvtransfer
        .encode_remote_prefill`. ``from_bytes`` on an engine with the
        same structural stamp round-trips to a prefill whose
        ``insert_prefilled`` output is bitwise identical to handing this
        object over by reference."""
        from .kvtransfer import encode_remote_prefill

        return encode_remote_prefill(self)

    @classmethod
    def from_bytes(cls, data: bytes, *, engine=None) -> "RemotePrefill":
        """Decode a :meth:`to_bytes` payload. ``engine`` (the receiving
        decode engine) re-binds ``engine_config`` by identity after
        verifying the structural stamp (prompt bucket / arena length)
        matches — the compatibility check in ``accepts_prefill`` is an
        ``is`` comparison, which raw bytes cannot carry across a wire."""
        from .kvtransfer import decode_remote_prefill

        return decode_remote_prefill(data, engine=engine)


def _filter_logits(logits, temp, top_k, top_p):
    """The filtering half of :func:`_sample_rows`: per-row temperature
    scaling, top-k and top-p over (N, V) logits → filtered scaled logits
    (suppressed entries at ``-inf``), the distribution ``categorical``
    samples from. Split out so speculative verify can score draft tokens
    against EXACTLY the distribution plain decode would have sampled from
    (rejection sampling is only exact against the same filtered dist)."""
    n, v = logits.shape
    safe_t = jnp.where(temp > 0, temp, jnp.float32(1.0))
    scaled = logits / safe_t[:, None]
    sorted_l = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_on = (top_k > 0) & (top_k < v)
    k_eff = jnp.clip(top_k, 1, v)
    rank = jnp.arange(v)[None, :]
    # top-k: drop everything below the kth-largest (rank view keeps sort
    # order, so the top-p pass below sees the k-filtered distribution — the
    # same k-then-p order as the static sampler)
    sorted_f = jnp.where(~k_on[:, None] | (rank < k_eff[:, None]), sorted_l, -jnp.inf)
    kth = jnp.take_along_axis(sorted_l, (k_eff - 1)[:, None], axis=-1)
    filtered = jnp.where(k_on[:, None] & (scaled < kth), -jnp.inf, scaled)
    # top-p (nucleus): smallest prefix with cumulative probability >= p; the
    # cumsum is exclusive so the top token always survives, and p >= 1
    # degenerates to keep-everything
    probs = jax.nn.softmax(sorted_f, axis=-1)
    cum = jnp.cumsum(probs, axis=-1) - probs
    p_eff = jnp.where(top_p < 1.0, top_p, jnp.float32(1.0))
    cutoff_idx = jnp.maximum(
        jnp.sum((cum < p_eff[:, None]).astype(jnp.int32), axis=-1) - 1, 0
    )
    cutoff = jnp.take_along_axis(sorted_f, cutoff_idx[:, None], axis=-1)
    return jnp.where(filtered < cutoff, -jnp.inf, filtered)


@jax.named_scope("engine.sample")
def _sample_rows(logits, subkeys, temp, top_k, top_p):
    """Per-row sampling over (N, V) logits: per-row temperature (0 = greedy
    argmax), per-row top-k (0 or >= V = off) and top-p (>= 1 = off) via ONE
    descending sort — both filters are dynamic per-row operands, so a
    greedy row, a seeded nucleus row and a top-k row share this one traced
    body (no structural sampling branches, unlike the static ``generate()``
    whose top_k width is a compile key)."""
    final = _filter_logits(logits, temp, top_k, top_p)
    sampled = jax.vmap(jax.random.categorical)(subkeys, final).astype(jnp.int32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


# --------------------------------------------------------------------- engine
class ContinuousBatchingEngine:
    """Persistent slot-based decode state for one model.

    Host API (all single-threaded — the serving worker owns the engine):

    * :meth:`insert` — admit one request into a free slot (bucketed prompt
      prefill + KV scatter; raises when no slot is free).
    * :meth:`step` — one fused decode step over every slot.
    * :meth:`poll` — pop matured deferred-readback entries, collect tokens,
      retire finished occupants (returned so the caller can reply).
    * :meth:`cancel` — force-retire an occupant (deadline shed); its slot
      frees immediately, stale in-flight ring tokens are ignored.
    * :meth:`drain` — step until every occupant retires.
    * :meth:`reset` — drop all state after a device failure; returns the
      orphaned occupants so the caller can fail their futures.

    ``readback_lag`` defers the host materialization of each program's
    (token, done) outputs by that many subsequent programs, keeping the
    decode loop free of synchronous device round-trips; ``0`` reads back
    every step (deterministic scheduling for tests).

    ``spec="ngram"`` turns on prompt-lookup speculative decoding: a host
    drafter proposes up to ``spec_draft_len`` continuation tokens per slot
    from n-gram matches in the slot's own history, and one fused
    ``verify_step`` program scores/accepts them (see the module
    docstring). Drafting needs each slot's true current history, so
    spec-mode steps materialize pending ring payloads to host before
    drafting — retirement still happens at :meth:`poll` with unchanged
    ``readback_lag`` semantics.
    """

    # speculative acceptance-EWMA gate: a slot whose EWMA falls below the
    # floor stops drafting (its traffic is incompressible — every wasted
    # draft costs a k×-wider forward) and re-probes after the cooldown
    _SPEC_EWMA_ALPHA = 0.2
    _SPEC_MIN_ACCEPT = 0.1
    _SPEC_COOLDOWN = 8
    _SPEC_COOLDOWN_MAX = 128

    def __init__(
        self,
        model,
        *,
        slots: int = 8,
        max_len: int = 256,
        prompt_bucket: Optional[int] = None,
        readback_lag: int = 2,
        kv_cache: str = "dense",
        block_size: int = 16,
        pool_blocks: Optional[int] = None,
        attention_impl: str = "reference",
        prefill_chunk: Optional[int] = None,
        host_tier_bytes: int = 0,
        spec: Optional[str] = None,
        spec_draft_len: int = 4,
        spec_ngram: int = 3,
        spec_ngram_min: int = 2,
        clock: Callable[[], float] = time.monotonic,
    ):
        from .kvcache import make_kv_backend

        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        if readback_lag < 0:
            raise ValueError(f"readback_lag must be >= 0, got {readback_lag}")
        if spec not in (None, "ngram"):
            raise ValueError(f"spec must be None or 'ngram', got {spec!r}")
        if spec is not None and spec_draft_len < 1:
            raise ValueError(
                f"spec_draft_len must be >= 1 when spec is enabled, got "
                f"{spec_draft_len}"
            )
        if spec is not None and spec_ngram < 1:
            raise ValueError(f"spec_ngram must be >= 1, got {spec_ngram}")
        if spec is not None and not 1 <= spec_ngram_min <= spec_ngram:
            raise ValueError(
                f"spec_ngram_min must be in [1, spec_ngram], got "
                f"{spec_ngram_min} (spec_ngram={spec_ngram})"
            )
        self.model = model
        self.config = model.config
        # the family's door: its step functions and what state a slot keeps
        # (models/family.py). Nothing below asks which family it is.
        self._family = self.config.serving_family()
        if self._family.verify_step is None and (spec is not None or prefill_chunk is not None):
            raise ValueError(
                f"{'speculative decoding' if spec is not None else 'chunked prefill'} "
                f"needs a verify_step, which {type(self.config).__name__}'s family "
                "does not hand over: " + (
                    "a window of tokens over the cache would have "
                    "to leave the family's recurrent state as of the accepted prefix "
                    "(the missing piece: a recurrent-state snapshot to rewind to)"
                    if self._family.recurrent_layers else
                    "no window of tokens attends its cache yet (the missing piece for "
                    "a latent cache: kvcache.attend_window and "
                    "ops/paged_decode.py::paged_flash_verify over one-leaf rows)"
                )
            )
        self.slots = slots
        self.max_len = max_len
        self.prompt_bucket = prompt_bucket if prompt_bucket is not None else max(1, max_len // 2)
        if not 1 <= self.prompt_bucket <= max_len - 1:
            raise ValueError(
                f"prompt_bucket must be in [1, max_len-1], got "
                f"{self.prompt_bucket} (max_len={max_len})"
            )
        # a prompt's prefill is padded to the smallest rung that holds it
        # (_padded_prompt); the bucket stays the top rung and every limit
        self.rungs = prefill_rungs(self.prompt_bucket, block_size)
        self._rung_dispatches = dict.fromkeys(self.rungs, 0)
        # chunked prefill (docs/serving.md "Long-context serving"): when
        # enabled, prompts LONGER than the bucket are admitted and fed one
        # `prefill_chunk`-wide chunk per scheduler tick through the
        # prefill_insert program family, interleaved with other slots'
        # decode steps. None keeps the legacy hard rejection.
        if prefill_chunk is not None and not 1 <= prefill_chunk <= max_len - 1:
            raise ValueError(
                f"prefill_chunk must be None or in [1, max_len-1], got "
                f"{prefill_chunk} (max_len={max_len})"
            )
        self.prefill_chunk = prefill_chunk
        self.readback_lag = readback_lag
        self._clock = clock
        if attention_impl not in ("reference", "pallas"):
            raise ValueError(
                f"attention_impl must be 'reference' or 'pallas', got "
                f"{attention_impl!r}"
            )
        if (
            attention_impl == "pallas"
            and getattr(self.config, "sliding_window", None) is not None
        ):
            # the paged flash kernels walk the FULL live block table; a
            # sliding-window mask would need per-block skip logic the kernel
            # doesn't implement — downgrade up-front and say so (the cache
            # seam, kvcache._kernel_attends, applies the same rule where the
            # attend path is chosen)
            warnings.warn(
                "attention_impl='pallas' does not support sliding-window "
                "configs; falling back to the reference paged attention op",
                stacklevel=2,
            )
            attention_impl = "reference"
        self.attention_impl = attention_impl
        self._backend = make_kv_backend(
            kv_cache, config=self.config, slots=slots, max_len=max_len,
            prompt_bucket=self.prompt_bucket, block_size=block_size,
            pool_blocks=pool_blocks, attention_impl=attention_impl,
            host_tier_bytes=host_tier_bytes,
        )
        # bytes one position holds in the store over all layers, as allocated
        self._kv_row_bytes = self._backend.row_bytes()
        if hasattr(self._backend, "bind_cache_reader"):
            # spill gathers read the engine's CURRENT donated cache: after
            # any dispatch self._donated is rebound to the program's output
            # arrays, so this closure always sees the live pool
            self._backend.bind_cache_reader(lambda: self._donated["cache"])
        self._key_width = jax.random.key_data(jax.random.key(0)).shape[-1]

        self.spec = spec
        self.spec_draft_len = spec_draft_len if spec is not None else 0
        self.spec_ngram = spec_ngram
        # precision floor: 1-gram fallback matches are noise on
        # incompressible traffic (any repeated token sparks a draft), and
        # every wrong draft costs a full k-wide verify forward
        self.spec_ngram_min = spec_ngram_min
        # host-side draft clamp, adjustable at runtime WITHOUT recompiling:
        # the verify program is always padded to spec_draft_len, so any
        # limit in [0, spec_draft_len] reuses the same compiled program
        # (0 = every step takes the plain decode path)
        self._spec_limit = self.spec_draft_len
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_wasted = 0
        self.spec_verify_steps = 0
        self.spec_emitted = 0
        self.spec_slot_steps = 0
        self.spec_ewma = 1.0  # engine-wide acceptance EWMA (optimistic)

        self._donated, self._carried = self._init_state()
        # donate only argument 0 (the arena + per-slot pos/PRNG): the ring
        # must keep reading the PREVIOUS carried token/done arrays after the
        # next program dispatches, so carried state is small and undonated
        self._decode_jit = jax.jit(self._decode_impl, donate_argnums=(0,))
        self._prefill_jit = jax.jit(self._prefill_impl, donate_argnums=(0,))
        self._verify_jit = jax.jit(self._verify_impl, donate_argnums=(0,))
        # prefill/decode disaggregation split (docs/serving.md fleet
        # section): the forward half is UNdonated and arena-free so
        # dedicated prefill worker threads can run it concurrently with the
        # decode loop; the commit half donates the arena like every other
        # arena program. Neither compiles unless prefill_remote is used.
        self._prefill_fwd_jit = jax.jit(self._prefill_forward_impl)
        self._prefill_commit_jit = jax.jit(
            self._prefill_commit_impl, donate_argnums=(0,), static_argnames=("width",)
        )
        # chunked-prefill members of the prefill_insert program family:
        # `_chunk_jit` runs one prompt chunk as a verify-style window
        # forward at the slot's offset (teacher forcing — commit every
        # window column, emit nothing until the last chunk samples t0);
        # `_restore_jit` scatters host-tier block payloads into the pool
        # ahead of the first chunk. Neither compiles unless long prompts
        # are actually served.
        self._chunk_jit = jax.jit(self._chunk_impl, donate_argnums=(0,))
        self._restore_jit = jax.jit(self._restore_impl, donate_argnums=(0,))
        # round-robin queue of PREFILLING occupants; the per-tick dispatch
        # clamp is a host-side operand knob (no recompile), the degradation
        # ladder's long-context rung
        self._prefill_queue: collections.deque = collections.deque()
        self._prefill_chunk_limit = 1
        self.prefill_chunks = 0  # lifetime chunk programs dispatched
        self.kv_restores = 0  # lifetime restore programs dispatched

        self._occupants: List[Optional[SlotOccupant]] = [None] * slots
        self._free: List[int] = list(range(slots))
        # slot-epoch fence for wire-shipped prefills (kvtransfer): every
        # return of a slot to the free list bumps its epoch, and a
        # reservation minted for an in-flight transfer is honored only
        # while its epoch is still current — a late/duplicate COMMIT can
        # never land in a recycled slot. The lock covers ONLY this
        # free-list/epoch/reservation bookkeeping (transfer receiver
        # threads reserve/release concurrently with the serving worker's
        # admissions and retirements); no device work ever runs under it.
        self._admission_lock = threading.Lock()
        self._epochs: List[int] = [0] * slots
        self._reservations: dict[int, float] = {}  # slot -> expiry time
        self.peak_live = 0
        # deferred-readback ring: (tick, kind, payload) — the same
        # K-programs-late trick as telemetry's DeferredReadbackRing, here
        # over (token, done) vectors instead of health verdicts
        self._ring: collections.deque = collections.deque()
        self._step_counters: dict = {}  # tick -> a step's counters (_ride_along)
        self._tick = 0
        self.inserted = 0
        self.remote_prefills = 0
        self.steps = 0
        self.retired = 0
        # distinct (program, operand-shape) signatures actually dispatched —
        # the compiled-programs acceptance stat (one prefill signature a
        # rung dispatched + one decode signature)
        self._programs: dict[str, set] = {}
        # perf observatory (docs/observability.md): wall time is only read
        # at poll() — the deferred-readback ring's synchronizing point —
        # and split across the programs that retired in the window. The
        # dispatch path never gains a clock read or a readback (G101).
        self._perfwatch = perfwatch.get_watch()
        self._pw_mark = self._clock()

    # ----------------------------------------------------------- state init
    def _init_state(self):
        s = self.slots
        keys = jax.random.split(jax.random.key(0), s)
        donated = {
            # dense: the (L, S, max_len, kvh, hd) arena; paged: the shared
            # block pool (+ per-block scales when int8) — either way donated
            # across programs so steady-state decode reallocates nothing
            "cache": self._backend.init_device_state(),
            "pos": jnp.zeros((s,), jnp.int32),
            "key": jax.random.key_data(keys),  # (S, key_width) uint32
        }
        carried = {
            # vacant slots are permanently "done": they ride every decode
            # step masked (pad token, no budget burn, pos frozen)
            "token": jnp.zeros((s,), jnp.int32),
            "done": jnp.ones((s,), bool),
            "budget": jnp.zeros((s,), jnp.int32),
            "temp": jnp.zeros((s,), jnp.float32),
            "top_k": jnp.zeros((s,), jnp.int32),
            "top_p": jnp.ones((s,), jnp.float32),
            "eos": jnp.full((s,), -1, jnp.int32),
            "pad": jnp.zeros((s,), jnp.int32),
        }
        return donated, carried

    # ------------------------------------------------------------- programs
    def _decode_impl(self, donated, carried, params, tables):
        cache, pos, key_data = donated["cache"], donated["pos"], donated["key"]
        token, done = carried["token"], carried["done"]
        # tables are traced OPERANDS (shape static per config): paged table
        # churn — admissions, retirements, COW sharing — never recompiles,
        # preserving the exactly-two-programs discipline
        layout = self._backend.make_layout(tables)
        # a family with step counters returns them third; they ride the
        # readback ring beside the tokens (no transfer or sync of their own).
        # layout None: the family consumes the dense arena directly
        logits, cache, *counters = self._family.decode_step(
            self.config, params, cache, token[:, None], pos,
            kv_layout=layout,
        )
        pairs = jax.vmap(jax.random.split)(jax.random.wrap_key_data(key_data))
        next_kd = jax.random.key_data(pairs[:, 0])
        subs = pairs[:, 1]
        if self.attention_impl == "pallas":
            # fused sampling epilogue kernel: bitwise the same draw as
            # _sample_rows (categorical == argmax(filtered + gumbel), and
            # the kernel's sort-free filter matches _filter_logits exactly).
            # Gumbel noise is generated outside the kernel — pltpu.prng is
            # unavailable in CPU interpret mode, and this keeps the PRNG
            # stream byte-identical to the reference path.
            from .ops.paged_decode import fused_sample

            v = logits.shape[-1]
            with jax.named_scope("engine.sample"):
                noise = jax.vmap(
                    lambda kk: jax.random.gumbel(kk, (v,), jnp.float32)
                )(subs)
                nxt = fused_sample(
                    logits, noise, carried["temp"], carried["top_k"], carried["top_p"]
                )
        else:
            nxt = _sample_rows(logits, subs, carried["temp"], carried["top_k"], carried["top_p"])
        emitting = ~done
        nxt = jnp.where(emitting, nxt, carried["pad"])
        budget = carried["budget"] - emitting.astype(jnp.int32)
        hit_eos = (carried["eos"] >= 0) & (nxt == carried["eos"])
        new_done = done | (emitting & (hit_eos | (budget <= 0)))
        new_pos = pos + emitting.astype(jnp.int32)
        new_donated = {"cache": cache, "pos": new_pos, "key": next_kd}
        new_carried = {**carried, "token": nxt, "done": new_done, "budget": budget}
        return new_donated, new_carried, (counters[0] if counters else None)

    def _verify_impl(self, donated, carried, params, tables, draft, draft_len):
        """The third jitted program: verify a fixed-k padded draft window
        for every slot at once. ``draft`` (S, k) / ``draft_len`` (S,) are
        traced operands — actual per-slot match lengths are MASKS, never
        compile keys, so mixed draft lengths share this one program.

        Window token j of slot b is ``[token_b, draft_b]`` at absolute
        position ``pos_b + j``. Acceptance walks the longest matching
        prefix: greedy rows accept a draft iff it equals the argmax of the
        verifier's logits at its position (exactness — the emitted
        sequence is bitwise what sequential decode would produce); sampled
        rows run standard rejection sampling against the verifier's
        FILTERED distribution (a deterministic drafter is a delta
        proposal: accept ``d`` w.p. ``p(d)``, on rejection sample the
        residual = ``p`` with ``d`` masked out, on full acceptance sample
        the bonus position normally). Only the accepted tokens' KV columns
        commit back to the store (``commit_window``); a rejected suffix
        simply never existed.

        PRNG discipline: exactly one split per program, same as decode —
        a slot's key stream advances identically whether a tick ran
        ``decode_step`` or ``verify_step``, and a ``draft_len=0`` row's
        final sample consumes ``subkey`` on the window-0 logits, bitwise
        identical to plain decode (alone-vs-packed reproducibility cannot
        be broken by OTHER slots' drafts flipping the dispatch kind).
        Acceptance uniforms draw from ``fold_in(subkey, 1+i)`` and the
        post-rejection sample from ``fold_in(subkey, 1000+a)`` — disjoint
        derived streams, never the raw subkey consumed twice."""
        cache, pos, key_data = donated["cache"], donated["pos"], donated["key"]
        token, done = carried["token"], carried["done"]
        s, k = draft.shape
        w = k + 1
        layout = self._backend.make_layout(tables)
        tokens = jnp.concatenate([token[:, None], draft], axis=1)  # (S, W)
        logits, win_kv = self._family.verify_step(
            self.config, params, cache, tokens, pos,
            kv_layout=layout,
        )
        # logits: (S, W, V) f32 — logits[:, j] is the next-token dist after
        # consuming window token j (position pos+j)
        v = logits.shape[-1]
        temp, top_k, top_p = carried["temp"], carried["top_k"], carried["top_p"]
        finals = _filter_logits(
            logits.reshape(s * w, v),
            jnp.repeat(temp, w), jnp.repeat(top_k, w), jnp.repeat(top_p, w),
        ).reshape(s, w, v)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (S, W)

        pairs = jax.vmap(jax.random.split)(jax.random.wrap_key_data(key_data))
        next_kd = jax.random.key_data(pairs[:, 0])
        subs = pairs[:, 1]

        # longest accepted prefix a ∈ [0, draft_len]
        idx_k = jnp.arange(k, dtype=jnp.int32)

        def row_uniforms(sk):
            ks = jax.vmap(lambda i: jax.random.fold_in(sk, 1 + i))(idx_k)
            return jax.vmap(jax.random.uniform)(ks)

        u = jax.vmap(row_uniforms)(subs)  # (S, k)
        probs = jax.nn.softmax(finals[:, :k], axis=-1)
        p_draft = jnp.take_along_axis(probs, draft[..., None], axis=-1)[..., 0]
        acc = jnp.where(temp[:, None] > 0, u < p_draft, draft == greedy[:, :k])
        acc = acc & (idx_k[None, :] < draft_len[:, None])
        a = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)  # (S,)

        # final token at window index a: greedy argmax, or a categorical on
        # the filtered dist with the rejected draft masked out (residual of
        # rejection sampling against a delta proposal); full acceptance
        # (a == draft_len) keeps the distribution unmasked (bonus sample)
        finals_a = jnp.take_along_axis(finals, a[:, None, None], axis=1)[:, 0]
        draft_ext = jnp.concatenate([draft, draft[:, :1]], axis=1)  # (S, W)
        d_rej = jnp.take_along_axis(draft_ext, a[:, None], axis=1)[:, 0]
        is_rej = a < draft_len
        vocab = jnp.arange(v, dtype=jnp.int32)
        resid = jnp.where(
            is_rej[:, None] & (vocab[None, :] == d_rej[:, None]), -jnp.inf, finals_a
        )
        folded = jax.vmap(jax.random.fold_in)(subs, 1000 + a)
        kd_final = jnp.where(
            (a == 0)[:, None],
            jax.random.key_data(subs), jax.random.key_data(folded),
        )
        sampled_final = jax.vmap(jax.random.categorical)(
            jax.random.wrap_key_data(kd_final), resid
        ).astype(jnp.int32)
        greedy_final = jnp.take_along_axis(greedy, a[:, None], axis=1)[:, 0]
        t_final = jnp.where(temp > 0, sampled_final, greedy_final)

        # emitted sequence E_0..E_a = accepted drafts + the final sample;
        # truncate at the remaining budget and at the first EOS
        jw = jnp.arange(w, dtype=jnp.int32)[None, :]
        emitted = jnp.where(jw < a[:, None], draft_ext, t_final[:, None])
        emitted = jnp.where(jw == a[:, None], t_final[:, None], emitted)
        eos = carried["eos"]
        is_eos = (eos[:, None] >= 0) & (emitted == eos[:, None]) & (jw <= a[:, None])
        first_eos = jnp.min(jnp.where(is_eos, jw, w + 1), axis=1)  # (S,)
        emitting = ~done
        m = jnp.minimum(jnp.minimum(a + 1, carried["budget"]), first_eos + 1)
        m = jnp.where(emitting, m, 0)
        emitted = jnp.where(jw < m[:, None], emitted, carried["pad"][:, None])

        # commit exactly m columns (positions pos..pos+m-1): the carried
        # token + accepted drafts whose successors are now determined. The
        # LAST emitted token's KV is NOT committed — it becomes the new
        # carried token and the next program writes it, exactly like
        # decode's sampled token
        cache = self._backend.commit_window(cache, win_kv, tables, pos, m)

        last = jnp.take_along_axis(emitted, jnp.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
        new_token = jnp.where(emitting, last, carried["pad"])
        new_budget = carried["budget"] - m
        new_done = done | (emitting & ((first_eos < m) | (new_budget <= 0)))
        new_pos = pos + m
        new_donated = {"cache": cache, "pos": new_pos, "key": next_kd}
        new_carried = {
            **carried, "token": new_token, "done": new_done, "budget": new_budget,
        }
        return new_donated, new_carried, emitted, m, a

    def _prefill_impl(
        self, donated, carried, params, prompt, length, slot, key_data,
        temp, top_k, top_p, eos, pad, budget, table_row,
    ):
        # prompt forward over its rung (one program a rung: the prompt's
        # width); logits at the last REAL position. Dense: the returned
        # max_len-wide cache (zeros beyond the rung) scatters over the full
        # slot row, wiping every stale byte of the previous occupant. Paged:
        # the rung's blocks of the table row take one scatter (recycled
        # blocks rely on the write-before-attend invariant instead of a
        # wipe — kvcache.py docstring).
        logits, new_cache, *counters = self._family.prefill_at(
            self.config, params, prompt, self.max_len, (length - 1)[None]
        )
        keys = jax.random.split(jax.random.wrap_key_data(key_data), 2)
        t0 = _sample_rows(logits, keys[1:2], temp[None], top_k[None], top_p[None])[0]
        hit_eos = (eos >= 0) & (t0 == eos)
        budget_left = budget - 1
        done0 = hit_eos | (budget_left <= 0)
        cache = self._backend.prefill_write(
            donated["cache"], new_cache, slot, table_row, prompt.shape[1]
        )
        new_donated = {
            "cache": cache,
            "pos": donated["pos"].at[slot].set(length),
            "key": donated["key"].at[slot].set(jax.random.key_data(keys[0])),
        }
        new_carried = {
            "token": carried["token"].at[slot].set(t0),
            "done": carried["done"].at[slot].set(done0),
            "budget": carried["budget"].at[slot].set(budget_left),
            "temp": carried["temp"].at[slot].set(temp),
            "top_k": carried["top_k"].at[slot].set(top_k),
            "top_p": carried["top_p"].at[slot].set(top_p),
            "eos": carried["eos"].at[slot].set(eos),
            "pad": carried["pad"].at[slot].set(pad),
        }
        return new_donated, new_carried, t0, done0, (counters[0] if counters else None)

    def _prefill_forward_impl(self, params, prompt, length, key_data, temp, top_k, top_p):
        # the arena-free half of _prefill_impl: same bucketed forward, same
        # key split, same first-token sample — so prefill_remote +
        # insert_prefilled is bitwise identical to a plain insert. Nothing
        # here reads or writes slot state, which is what makes it safe off
        # the single-controller decode thread.
        # whatever state the family keeps rides in new_cache, its recurrent
        # rows included: insert_prefilled commits it like any prefill's
        logits, new_cache, *_counters = self._family.prefill_at(
            self.config, params, prompt, self.max_len, (length - 1)[None]
        )
        keys = jax.random.split(jax.random.wrap_key_data(key_data), 2)
        t0 = _sample_rows(logits, keys[1:2], temp[None], top_k[None], top_p[None])[0]
        return new_cache, t0, jax.random.key_data(keys[0])

    def _prefill_commit_impl(
        self, donated, carried, new_cache, t0, next_key, slot, length,
        temp, top_k, top_p, eos, pad, budget, table_row, *, width,
    ):
        # the arena half of _prefill_impl: scatter the precomputed KV
        # window (its forward ran over the rung ``width``) and install the
        # slot's carried state. done0 is recomputed here (not in the
        # forward) so a degradation-clamped budget at commit time behaves
        # exactly like a plain insert with that budget.
        hit_eos = (eos >= 0) & (t0 == eos)
        budget_left = budget - 1
        done0 = hit_eos | (budget_left <= 0)
        cache = self._backend.prefill_write(
            donated["cache"], new_cache, slot, table_row, width
        )
        new_donated = {
            "cache": cache,
            "pos": donated["pos"].at[slot].set(length),
            "key": donated["key"].at[slot].set(next_key),
        }
        new_carried = {
            "token": carried["token"].at[slot].set(t0),
            "done": carried["done"].at[slot].set(done0),
            "budget": carried["budget"].at[slot].set(budget_left),
            "temp": carried["temp"].at[slot].set(temp),
            "top_k": carried["top_k"].at[slot].set(top_k),
            "top_p": carried["top_p"].at[slot].set(top_p),
            "eos": carried["eos"].at[slot].set(eos),
            "pad": carried["pad"].at[slot].set(pad),
        }
        return new_donated, new_carried, t0, done0

    def _chunk_impl(
        self, donated, carried, params, tokens, offset, chunk_len, slot,
        key_data, temp, top_k, top_p, eos, pad, budget, length, tables,
    ):
        """One prompt chunk of a chunked prefill: a verify-style window
        forward (``*_verify_step`` — the cache-read-only multi-token body
        speculative decoding already compiles) at the slot's append offset,
        teacher-forced on the prompt's own tokens, committing every window
        column's KV via ``commit_window``. ``tokens`` is (S, C) with only
        ``slot``'s row real (other rows' outputs are discarded: commit
        count is a one-hot, and the window forward never writes the cache).

        The LAST chunk (``offset + chunk_len >= length``, a traced
        predicate — one compiled program regardless) reproduces
        ``_prefill_impl``'s epilogue bitwise: the same single
        ``split(key, 2)``, the same ``_sample_rows`` on the final prompt
        position's logits, the same done/budget install. Non-last chunks
        leave the slot masked (done=True, pad token, zero budget) so the
        interleaved decode steps treat it as a ghost — its unconditional
        masked write lands at the NEXT chunk's first position, which that
        chunk rewrites before anything attends it (write-before-attend)."""
        cache = donated["cache"]
        pos = donated["pos"].at[slot].set(offset)
        layout = self._backend.make_layout(tables)
        logits, win_kv = self._family.verify_step(
            self.config, params, cache, tokens, pos,
            kv_layout=layout,
        )
        count = jnp.zeros((self.slots,), jnp.int32).at[slot].set(chunk_len)
        cache = self._backend.commit_window(cache, win_kv, tables, pos, count)
        is_last = offset + chunk_len >= length
        # t0 from the logits after the final REAL prompt token — only
        # meaningful (and only consumed) on the last chunk
        last_idx = jnp.clip(length - 1 - offset, 0, tokens.shape[1] - 1)
        row_logits = lax.dynamic_slice_in_dim(logits, slot, 1, axis=0)[0]
        l_last = lax.dynamic_slice_in_dim(row_logits, last_idx, 1, axis=0)
        keys = jax.random.split(jax.random.wrap_key_data(key_data), 2)
        t0 = _sample_rows(l_last, keys[1:2], temp[None], top_k[None], top_p[None])[0]
        hit_eos = (eos >= 0) & (t0 == eos)
        budget_left = budget - 1
        done0 = hit_eos | (budget_left <= 0)
        new_donated = {
            "cache": cache,
            "pos": donated["pos"].at[slot].set(offset + chunk_len),
            # the key stream is untouched until the last chunk consumes
            # exactly one split — bitwise the single-shot discipline
            "key": jnp.where(
                is_last,
                donated["key"].at[slot].set(jax.random.key_data(keys[0])),
                donated["key"],
            ),
        }
        sel = lambda last_v, mid_v: jnp.where(is_last, last_v, mid_v)
        new_carried = {
            # mid-prefill the slot must ride decode steps as a ghost even if
            # a cancelled predecessor left done=False: force the mask here
            "token": carried["token"].at[slot].set(sel(t0, pad)),
            "done": carried["done"].at[slot].set(sel(done0, True)),
            "budget": carried["budget"].at[slot].set(sel(budget_left, 0)),
            "temp": carried["temp"].at[slot].set(temp),
            "top_k": carried["top_k"].at[slot].set(top_k),
            "top_p": carried["top_p"].at[slot].set(top_p),
            "eos": carried["eos"].at[slot].set(eos),
            "pad": carried["pad"].at[slot].set(pad),
        }
        return new_donated, new_carried, t0, done0

    def _restore_impl(self, donated, payload, ids):
        """Scatter host-tier block payloads into the pool (the restore half
        of the spill/restore plan): ``payload`` mirrors the pool's leaf
        structure with a leading restore-batch axis — f32 ``{"k","v"}`` of
        (R, L, bs, kvh * hd), int8 adds per-position scales — and ``ids``
        (R,) names the target blocks, padded with the null block (write to
        the garbage sink, never a live block). R is fixed at blocks_per_row
        so every restore shares one compiled program."""
        cache = donated["cache"]
        out = {}
        for w in ("k", "v"):
            leaf = cache[w]
            if isinstance(leaf, dict):
                out[w] = {
                    "q": leaf["q"].at[:, ids].set(
                        jnp.moveaxis(payload[w]["q"], 0, 1)
                    ),
                    "s": leaf["s"].at[:, ids].set(
                        jnp.moveaxis(payload[w]["s"], 0, 1)
                    ),
                }
            else:
                out[w] = leaf.at[:, ids].set(
                    jnp.moveaxis(payload[w], 0, 1).astype(leaf.dtype)
                )
        return {**donated, "cache": {**cache, **out}}

    def _record(self, name: str, sig: tuple) -> None:
        self._programs.setdefault(name, set()).add(sig)

    def _rung(self, prompt_len: int) -> int:
        """The width a prompt of ``prompt_len`` tokens is padded to for its
        prefill: the smallest rung that holds it. The one choice of rung,
        made alike by :meth:`insert`, :meth:`prefill_remote` and
        :meth:`insert_prefilled`, so the disaggregated path runs the
        programs a plain insert runs."""
        return next(r for r in self.rungs if r >= prompt_len)

    def _padded_prompt(self, prompt: np.ndarray) -> np.ndarray:
        padded = np.zeros((1, self._rung(len(prompt))), np.int32)
        padded[0, : len(prompt)] = prompt
        return padded

    # -------------------------------------------------------------- host API
    def free_slots(self) -> int:
        return len(self._free)

    def _pop_free_slot(self) -> int:
        with self._admission_lock:
            if not self._free:
                raise EngineCapacityError(
                    "no free arena slot (caller must gate on free_slots())"
                )
            return self._free.pop()

    def _return_slot(self, slot: int) -> None:
        """Return a slot to the free list and bump its epoch — the fence
        event: any reservation or in-flight transfer minted under the old
        epoch is now permanently stale."""
        with self._admission_lock:
            self._epochs[slot] += 1
            self._reservations.pop(slot, None)
            self._free.append(slot)

    # ------------------------------------------------ slot-epoch reservations
    def slot_epoch(self, slot: int) -> int:
        """Current epoch of ``slot`` (monotonic; bumped every time the slot
        returns to the free list). The kvtransfer receiver fences COMMIT
        frames against this."""
        with self._admission_lock:
            return self._epochs[slot]

    def reserve_slot(self, ttl_s: float = 30.0) -> Tuple[int, int]:
        """Reserve a free slot for an incoming KV transfer: the slot leaves
        the free list NOW (so admission cannot recycle it mid-stream) and
        the returned ``(slot, epoch)`` pair rides the transfer's frames.
        ``insert_prefilled`` consumes the reservation iff the epoch is
        still current; :meth:`release_reservation` (abort) or the ``ttl_s``
        reaper (leaked transfer) returns the slot with an epoch bump, which
        permanently fences the late stream. Safe from any thread."""
        with self._admission_lock:
            if not self._free:
                raise EngineCapacityError(
                    "no free arena slot to reserve for an incoming KV "
                    "transfer (slots free as occupants retire)"
                )
            slot = self._free.pop()
            self._reservations[slot] = self._clock() + ttl_s
            return slot, self._epochs[slot]

    def release_reservation(self, slot: int, epoch: int) -> bool:
        """Cancel a transfer reservation (sender aborted / stream died):
        the slot returns to the free list and its epoch bumps, so any
        late COMMIT carrying the old epoch is refused. Idempotent —
        returns False when the reservation is already gone (consumed,
        reaped, or released twice). Safe from any thread."""
        with self._admission_lock:
            if slot not in self._reservations or self._epochs[slot] != epoch:
                return False
            del self._reservations[slot]
            self._epochs[slot] += 1
            self._free.append(slot)
            return True

    def _reap_reservations(self) -> None:
        """Expire overdue transfer reservations (a sender that died after
        BEGIN never sends ABORT — the TTL is the backstop that stops a
        leaked reservation from holding a slot forever)."""
        if not self._reservations:
            return
        now = self._clock()
        with self._admission_lock:
            expired = [s for s, exp in self._reservations.items() if now >= exp]
            for slot in expired:
                del self._reservations[slot]
                self._epochs[slot] += 1
                self._free.append(slot)

    def kv_prefix_digest(self, limit: int = 512) -> dict:
        """Compact content digest of the KV prefix registry:
        ``{"block_size": B, "crcs": [crc32 of each registered
        block-aligned prefix key, capped at limit]}`` — gossiped through
        the fleet prober so placement can prefer replicas that already
        hold a request's warm prefix (KV-affinity routing). The router
        recomputes the same crc32 over a request's block-aligned prompt
        prefixes, which needs ``block_size`` to slice identically. Empty
        crcs for dense backends (no prefix registry)."""
        fn = getattr(self._backend, "prefix_digest", None)
        return {
            "block_size": getattr(self._backend, "block_size", 0),
            "crcs": fn(limit) if fn is not None else [],
        }

    @property
    def kv_host_tier(self):
        """The backend's :class:`~accelerate_tpu.kvcache.HostKVTier`
        (``None`` when spill is off or the backend is dense) — exposed for
        the fleet's hot-prefix replication, which copies MRU prefix blocks
        across replicas' tiers so a popular system prompt restores warm
        everywhere."""
        return getattr(self._backend, "host_tier", None)

    def live_count(self) -> int:
        return sum(1 for o in self._occupants if o is not None and not o.finished)

    def occupants(self) -> List[SlotOccupant]:
        """Snapshot of live (unfinished) occupants, for scheduler policy
        passes (deadline shed) over in-flight slots."""
        return [o for o in self._occupants if o is not None and not o.finished]

    def validate_request(self, prompt_len: int, max_new_tokens: int) -> None:
        """Raise ValueError when a request cannot fit this engine's arena
        (checked at admission so the typed error reaches the submitter)."""
        if prompt_len < 1:
            raise ValueError(f"prompt length must be >= 1, got {prompt_len}")
        if prompt_len > self.prompt_bucket and self.prefill_chunk is None:
            raise ValueError(
                f"prompt length {prompt_len} exceeds the engine prompt "
                f"bucket ({self.prompt_bucket}); raise "
                "ServingConfig.engine_prompt_bucket, enable chunked prefill "
                "(engine_prefill_chunk), or shorten the prompt"
            )
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if prompt_len + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the KV arena length ({self.max_len}); raise "
                "ServingConfig.engine_max_len or lower the budget"
            )
        # backend-specific structural checks (paged: the request's block
        # count must fit the pool — names engine_block_size / pool blocks)
        self._backend.validate_request(prompt_len, max_new_tokens)

    def validate_prompt(self, prompt: np.ndarray) -> None:
        """Raise ValueError for a token id the embedding does not hold: the
        gather would clamp it to a row of another token, in silence (a model
        that serves a slice of its vocabulary holds the slice's ids only)."""
        vocab = getattr(self.config, "vocab_size", None)
        if vocab is not None and prompt.size and not 0 <= prompt.min() <= prompt.max() < vocab:
            raise ValueError(
                f"prompt holds token id(s) outside the vocabulary served here "
                f"(0 .. {vocab - 1}): min {int(prompt.min())}, max {int(prompt.max())}"
            )

    def can_admit(self, prompt, max_new_tokens: int) -> bool:
        """True when a slot AND the KV capacity for this request are free
        right now. Dense backends only need the slot; paged backends also
        need ``ceil((prompt+budget)/block_size)`` blocks net of COW
        prefix hits. The scheduler gates admission here instead of on
        ``free_slots()`` alone."""
        if not self._free:
            return False
        return self._backend.can_admit(
            np.asarray(prompt, dtype=np.int32).reshape(-1), max_new_tokens
        )

    def insert(
        self,
        prompt,
        *,
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_token_id: Optional[int] = None,
        pad_token_id: Optional[int] = None,
        seed: int = 0,
        tag: Any = None,
    ) -> SlotOccupant:
        """Admit one request into a free slot: a prefill padded to the
        smallest rung that holds the prompt (:meth:`_rung`), KV scatter,
        first token sampled inside the same program. Prompts longer than
        the bucket (chunked prefill enabled) take the chunked path: the
        first chunk dispatches here, the rest interleave one per
        :meth:`step` tick."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        self.validate_request(len(prompt), max_new_tokens)
        self.validate_prompt(prompt)
        if len(prompt) > self.prompt_bucket:
            return self._insert_chunked(
                prompt, max_new_tokens=max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, eos_token_id=eos_token_id,
                pad_token_id=pad_token_id, seed=seed, tag=tag,
            )
        slot = self._pop_free_slot()
        try:
            # paged: allocate/COW-share the request's blocks and install the
            # slot's table row; raises RuntimeError when the pool is out of
            # blocks (callers gate on can_admit()). Dense: a no-op row.
            table_row, shared = self._backend.acquire(slot, prompt, max_new_tokens)
        except BaseException:
            self._return_slot(slot)
            raise
        padded = self._padded_prompt(prompt)
        rung = padded.shape[1]
        pad_id = (
            pad_token_id if pad_token_id is not None
            else (eos_token_id if eos_token_id is not None else 0)
        )
        kd = jax.random.key_data(jax.random.key(seed))
        trace_id = getattr(tag, "trace_id", None)
        self._record("prefill_insert", (rung,))
        self._rung_dispatches[rung] += 1
        # host-side dispatch span: the jitted body never sees the tracer
        # (G107) — this times the interleaved prefill on the decode thread
        with tracing.span(
            "engine.prefill", trace_id=trace_id,
            slot=slot, prompt_len=len(prompt), bucket=rung,
        ):
            self._donated, self._carried, t0, d0, counters = self._prefill_jit(
                self._donated, self._carried, self.model.params,
                jnp.asarray(padded), jnp.int32(len(prompt)), jnp.int32(slot), kd,
                jnp.float32(temperature),
                jnp.int32(top_k if top_k is not None else 0),
                jnp.float32(top_p if top_p is not None else 1.0),
                jnp.int32(eos_token_id if eos_token_id is not None else -1),
                jnp.int32(pad_id), jnp.int32(max_new_tokens),
                jnp.asarray(self._backend.prefill_row(table_row, shared)),
            )
        occ = SlotOccupant(
            slot=slot, tag=tag, prompt=prompt, budget=max_new_tokens,
            pad_id=pad_id, eos_id=eos_token_id, inserted_s=self._clock(),
            trace_id=trace_id, copies=self._copy_plan(table_row),
        )
        self._occupants[slot] = occ
        self.inserted += 1
        self.peak_live = max(self.peak_live, self.live_count())
        self._tick += 1
        self._ring.append((self._tick, "prefill", (occ, t0, d0)))
        self._ride_along(counters)
        return occ

    # ------------------------------------------------------- chunked prefill
    def _insert_chunked(
        self, prompt, *, max_new_tokens, temperature, top_k, top_p,
        eos_token_id, pad_token_id, seed, tag,
    ) -> SlotOccupant:
        """Admit a long prompt (> prompt_bucket): allocate its blocks with
        DEFERRED prefix registration (content does not exist yet), restore
        any host-tier spilled prefix with one scatter program, then
        dispatch the first chunk. Remaining chunks interleave one per
        :meth:`step` tick — the slot rides every decode step masked until
        the last chunk installs its first token."""
        slot = self._pop_free_slot()
        try:
            table_row, shared = self._backend.acquire(
                slot, prompt, max_new_tokens, defer_register=True
            )
        except BaseException:
            self._return_slot(slot)
            raise
        pad_id = (
            pad_token_id if pad_token_id is not None
            else (eos_token_id if eos_token_id is not None else 0)
        )
        trace_id = getattr(tag, "trace_id", None)
        occ = SlotOccupant(
            slot=slot, tag=tag, prompt=prompt, budget=max_new_tokens,
            pad_id=pad_id, eos_id=eos_token_id, inserted_s=self._clock(),
            trace_id=trace_id, prefilling=True, copies=self._copy_plan(table_row),
        )
        # host-tier restore: consecutive spilled blocks past the device
        # registry's shared depth scatter back in ONE program — a host hit
        # beats recomputing those chunks (the bench-longctx crossover)
        restored_tokens = 0
        if hasattr(self._backend, "restore_plan"):
            plan = self._backend.restore_plan(slot, prompt, shared, table_row)
            if plan is not None:
                n, payloads, ids = plan
                self._dispatch_restore(occ, payloads, ids)
                # restored content is the original bytes — valid now, so its
                # registrations promote immediately and serve prefix hits
                self._backend.promote_deferred(slot, n)
                restored_tokens = n * self._backend.block_size
        shared_tokens = (
            shared * getattr(self._backend, "block_size", 0) + restored_tokens
        )
        # chunks before the first offset covering unwritten content are
        # skipped entirely; the min(.., P-1) keeps the LAST position inside
        # the final chunk so t0's logits are always computed
        chunk = self.prefill_chunk
        occ.prefill_pos = (min(shared_tokens, len(prompt) - 1) // chunk) * chunk
        occ.chunk_args = dict(
            length=len(prompt),
            kd=jax.random.key_data(jax.random.key(seed)),
            temp=jnp.float32(temperature),
            top_k=jnp.int32(top_k if top_k is not None else 0),
            top_p=jnp.float32(top_p if top_p is not None else 1.0),
            eos=jnp.int32(eos_token_id if eos_token_id is not None else -1),
            pad=jnp.int32(pad_id),
            budget=jnp.int32(max_new_tokens),
        )
        self._occupants[slot] = occ
        self._prefill_queue.append(occ)
        self.inserted += 1
        self.peak_live = max(self.peak_live, self.live_count())
        # the first chunk dispatches inside the admission, installing the
        # slot's pos/ghost mask before any interleaved decode step runs
        self._dispatch_chunk(occ)
        return occ

    def _dispatch_chunk(self, occ: SlotOccupant) -> None:
        args = occ.chunk_args
        chunk = self.prefill_chunk
        length = args["length"]
        offset = occ.prefill_pos
        chunk_len = min(chunk, length - offset)
        is_last = offset + chunk_len >= length
        tokens = np.zeros((self.slots, chunk), np.int32)
        tokens[occ.slot, :chunk_len] = occ.prompt[offset: offset + chunk_len]
        self._record("prefill_insert", ("chunk", chunk))
        with tracing.span(
            "engine.prefill_chunk", trace_id=occ.trace_id,
            slot=occ.slot, offset=offset, chunk_len=chunk_len,
            # the window forward runs every slot's row, one of them real
            bucket=self.slots * chunk,
        ):
            self._donated, self._carried, t0, d0 = self._chunk_jit(
                self._donated, self._carried, self.model.params,
                jnp.asarray(tokens), jnp.int32(offset), jnp.int32(chunk_len),
                jnp.int32(occ.slot), args["kd"], args["temp"], args["top_k"],
                args["top_p"], args["eos"], args["pad"], args["budget"],
                jnp.int32(length), self._backend.device_tables(),
            )
        self.prefill_chunks += 1
        occ.prefill_pos = offset + chunk_len
        self._tick += 1
        if is_last:
            occ.prefilling = False
            occ.chunk_args = None
            try:
                self._prefill_queue.remove(occ)
            except ValueError:
                pass
            # the prompt's content now exists (the final commit is ordered
            # before any sharer's program): promote the parked prefix
            # registrations so the NEXT request with this prefix COW-shares
            if hasattr(self._backend, "promote_deferred"):
                self._backend.promote_deferred(occ.slot)
            self._ring.append((self._tick, "prefill", (occ, t0, d0)))
        else:
            self._ring.append((self._tick, "chunk", (occ,)))

    def _dispatch_restore(self, occ: SlotOccupant, payloads, ids) -> None:
        n = len(payloads)
        rows = self._backend.blocks_per_row
        ids_full = np.zeros((rows,), np.int32)  # pad -> null block (sink)
        ids_full[:n] = ids

        def assemble(w):
            first = payloads[0][w]
            if isinstance(first, dict):
                pad_q = jnp.zeros_like(first["q"])
                pad_s = jnp.zeros_like(first["s"])
                return {
                    "q": jnp.stack(
                        [p[w]["q"] for p in payloads] + [pad_q] * (rows - n)
                    ),
                    "s": jnp.stack(
                        [p[w]["s"] for p in payloads] + [pad_s] * (rows - n)
                    ),
                }
            pad = jnp.zeros_like(first)
            return jnp.stack([p[w] for p in payloads] + [pad] * (rows - n))

        payload = {"k": assemble("k"), "v": assemble("v")}
        self._record("prefill_insert", ("restore", rows))
        with tracing.span(
            "engine.kv_restore", trace_id=occ.trace_id,
            slot=occ.slot, blocks=n,
        ):
            self._donated = self._restore_jit(
                self._donated, payload, jnp.asarray(ids_full)
            )
        self.kv_restores += 1
        self._tick += 1
        self._ring.append((self._tick, "chunk", (occ,)))

    def prefill_step(self, limit: Optional[int] = None) -> bool:
        """Dispatch up to ``limit`` (default: the runtime clamp set by
        :meth:`set_prefill_chunk_limit`) pending prompt chunks, round-robin
        across PREFILLING slots. Returns True when anything dispatched."""
        n = self._prefill_chunk_limit if limit is None else limit
        dispatched = False
        for _ in range(n):
            if not self._prefill_queue:
                break
            occ = self._prefill_queue[0]
            self._prefill_queue.rotate(-1)
            self._dispatch_chunk(occ)
            dispatched = True
        return dispatched

    def set_prefill_chunk_limit(self, n: int) -> None:
        """Clamp how many prompt chunks each :meth:`step` tick may dispatch
        — a host-side scheduling knob (operands only, no recompile), the
        degradation ladder's long-context rung. 0 pauses chunked prefill
        entirely (admitted long prompts hold their slots but burn no
        compute); restore with a larger value once pressure subsides."""
        self._prefill_chunk_limit = max(0, int(n))

    @property
    def prefill_chunk_limit(self) -> int:
        return self._prefill_chunk_limit

    def prefill_chunks_pending(self) -> int:
        """Chunks still owed across all PREFILLING slots (the
        ``engine/prefill_chunks_pending`` gauge)."""
        chunk = self.prefill_chunk or self.prompt_bucket
        return sum(
            -(-(len(occ.prompt) - occ.prefill_pos) // chunk)
            for occ in self._prefill_queue
        )

    def _decoding_count(self) -> int:
        return sum(
            1 for o in self._occupants
            if o is not None and not o.finished and not o.prefilling
        )

    def prefetch(self, prompt) -> None:
        """Admission-time async prefetch: start host-tier -> device copies
        for any spilled prefix of ``prompt`` so the restore payload is in
        flight before the decode thread admits the request. Safe from any
        thread; a no-op without a host tier."""
        if hasattr(self._backend, "prefetch"):
            self._backend.prefetch(np.asarray(prompt, np.int32).reshape(-1))

    def prefill_remote(
        self,
        prompt,
        *,
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_token_id: Optional[int] = None,
        pad_token_id: Optional[int] = None,
        seed: int = 0,
        trace_id: Optional[str] = None,
    ) -> RemotePrefill:
        """Run a request's prompt forward WITHOUT admitting it: the
        compute-bound half of prefill, safe from any thread (touches no
        arena, slot, or KV-pool state). The returned :class:`RemotePrefill`
        is later scattered into a slot by :meth:`insert_prefilled` on the
        decode thread — a cheap commit-only program, so decode slots stop
        stalling behind prompt forwards (prefill/decode disaggregation;
        ``ServingResult.ttft_s`` is the metric)."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        self.validate_request(len(prompt), max_new_tokens)
        if len(prompt) > self.prompt_bucket:
            raise ValueError(
                "prefill_remote cannot disaggregate a chunked (long) prompt; "
                "admit it via insert() so chunks interleave with decode"
            )
        padded = self._padded_prompt(prompt)
        rung = padded.shape[1]
        kd = jax.random.key_data(jax.random.key(seed))
        self._record("prefill_forward", (rung,))
        self._rung_dispatches[rung] += 1
        with tracing.span(
            "engine.prefill", trace_id=trace_id,
            remote=True, prompt_len=len(prompt), bucket=rung,
        ):
            new_cache, t0, next_key = self._prefill_fwd_jit(
                self.model.params, jnp.asarray(padded), jnp.int32(len(prompt)), kd,
                jnp.float32(temperature),
                jnp.int32(top_k if top_k is not None else 0),
                jnp.float32(top_p if top_p is not None else 1.0),
            )
        self.remote_prefills += 1
        return RemotePrefill(
            prompt=prompt, max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_token_id=eos_token_id, pad_token_id=pad_token_id, seed=seed,
            cache=new_cache, t0=t0, next_key=next_key,
            engine_config=self.config, prompt_bucket=self.prompt_bucket,
            max_len=self.max_len,
        )

    def _structurally_accepts(self, pre) -> bool:
        return (
            isinstance(pre, RemotePrefill)
            and pre.engine_config is self.config
            and pre.prompt_bucket == self.prompt_bucket
            and pre.max_len == self.max_len
        )

    def accepts_prefill(self, pre) -> bool:
        """Whether :meth:`insert_prefilled` can commit this
        :class:`RemotePrefill`: it must have been computed against the same
        model config, prompt bucket, and arena length (after a failover to
        a differently-shaped replica the caller falls back to a plain
        :meth:`insert`, recomputing the forward). A wire-shipped prefill
        whose slot reservation went stale mid-queue (released by deadline
        shed, TTL reaper, or reset) is also refused here, so the serving
        admission path falls back to a local prefill instead of tripping
        the :meth:`insert_prefilled` fence."""
        if not self._structurally_accepts(pre):
            return False
        if pre.reservation is not None:
            slot, epoch = pre.reservation
            with self._admission_lock:
                if (
                    slot not in self._reservations
                    or self._epochs[slot] != epoch
                ):
                    return False
        return True

    def insert_prefilled(
        self, pre: RemotePrefill, *, max_new_tokens: Optional[int] = None,
        tag: Any = None,
    ) -> SlotOccupant:
        """Admit a remotely prefilled request into a free slot: scatter its
        precomputed KV window + first token with the commit-only program
        (no prompt forward on the decode thread). ``max_new_tokens``
        overrides (only downward — the degradation ladder clamps budgets at
        admission) the budget the prefill was computed with; the commit
        program re-derives done/budget state so the result is bitwise what
        :meth:`insert` with that budget would have produced."""
        # structural check only — reservation freshness is fenced below so
        # a stale wire transfer raises the TYPED TransferStaleEpochError,
        # not this generic mismatch
        if not self._structurally_accepts(pre):
            raise ValueError(
                "RemotePrefill is not compatible with this engine (model "
                "config / prompt_bucket / max_len mismatch) — recompute via "
                "prefill_remote or fall back to insert()"
            )
        budget = pre.max_new_tokens if max_new_tokens is None else max_new_tokens
        if budget > pre.max_new_tokens:
            raise ValueError(
                f"insert_prefilled budget ({budget}) cannot exceed the "
                f"prefill's budget ({pre.max_new_tokens})"
            )
        prompt = pre.prompt
        self.validate_request(len(prompt), budget)
        if pre.reservation is not None:
            # the slot-epoch fence: a wire-shipped prefill commits into
            # the exact slot its transfer reserved, and ONLY while the
            # epoch it was reserved under is still current — a stale
            # epoch means the slot was released (and possibly recycled)
            # mid-transfer, so the late stream must never land
            slot, epoch = pre.reservation
            with self._admission_lock:
                fresh = (
                    slot in self._reservations
                    and self._epochs[slot] == epoch
                )
                if fresh:
                    del self._reservations[slot]
            if not fresh:
                raise TransferStaleEpochError(
                    f"KV transfer reservation for slot {slot} is stale "
                    f"(transfer epoch {epoch}, current "
                    f"{self.slot_epoch(slot)}) — the slot was released "
                    "while the stream was in flight; fall back to a "
                    "local prefill"
                )
        else:
            slot = self._pop_free_slot()
        try:
            table_row, shared = self._backend.acquire(slot, prompt, budget)
        except BaseException:
            self._return_slot(slot)
            raise
        pad_id = (
            pre.pad_token_id if pre.pad_token_id is not None
            else (pre.eos_token_id if pre.eos_token_id is not None else 0)
        )
        trace_id = getattr(tag, "trace_id", None)
        rung = self._rung(len(prompt))
        self._record("prefill_commit", (rung,))
        with tracing.span(
            "engine.insert_prefilled", trace_id=trace_id,
            slot=slot, prompt_len=len(prompt),
        ):
            self._donated, self._carried, t0, d0 = self._prefill_commit_jit(
                self._donated, self._carried, pre.cache, pre.t0, pre.next_key,
                jnp.int32(slot), jnp.int32(len(prompt)),
                jnp.float32(pre.temperature),
                jnp.int32(pre.top_k if pre.top_k is not None else 0),
                jnp.float32(pre.top_p if pre.top_p is not None else 1.0),
                jnp.int32(pre.eos_token_id if pre.eos_token_id is not None else -1),
                jnp.int32(pad_id), jnp.int32(budget),
                jnp.asarray(self._backend.prefill_row(table_row, shared)), width=rung,
            )
        occ = SlotOccupant(
            slot=slot, tag=tag, prompt=prompt, budget=budget,
            pad_id=pad_id, eos_id=pre.eos_token_id, inserted_s=self._clock(),
            trace_id=trace_id, copies=self._copy_plan(table_row),
        )
        self._occupants[slot] = occ
        self.inserted += 1
        self.peak_live = max(self.peak_live, self.live_count())
        self._tick += 1
        self._ring.append((self._tick, "prefill", (occ, t0, d0)))
        return occ

    def step(self) -> bool:
        """One scheduler tick: first dispatch up to the runtime clamp of
        pending prompt chunks (chunked prefill interleaves with decode —
        each tick costs one bucket-sized forward, not the whole prompt),
        then one fused step over every DECODING slot (vacant/finished/
        PREFILLING slots ride masked): a ``verify_step`` when speculative
        drafting produced any draft this tick, the plain ``decode_step``
        otherwise. Returns False when nothing dispatched."""
        dispatched = self.prefill_step()
        if self._decoding_count() == 0:
            return dispatched
        if self.spec is not None:
            return self._step_spec() or dispatched
        return self._dispatch_decode() or dispatched

    def _dispatch_decode(self) -> bool:
        self._record("decode_step", ())
        # one span EVERY step (a few Python adds over at most `slots`
        # occupants; nothing is fetched from the device for it). It times
        # the host dispatch only — the jitted body itself never sees the
        # tracer (G107) — and carries what the step was dispatched with:
        # rows doing useful work, live against reserved KV positions, and the
        # blocks the decode kernel copies against the copies it issues
        live_blocks, copies = self.walk_copies()
        with tracing.span(
            "engine.decode_step",
            live=self.live_count(), tick=self._tick,
            decoding=self._decoding_count(), slots=self.slots,
            kv_live_tokens=self.live_tokens(),
            kv_reserved_tokens=self._backend.reserved_tokens(),
            kv_walked_tokens=self.walked_tokens(),
            kv_live_blocks=live_blocks, kv_copy_descriptors=copies,
            kv_row_bytes=self._kv_row_bytes,
        ):
            self._donated, self._carried, counters = self._decode_jit(
                self._donated, self._carried, self.model.params,
                self._backend.device_tables(),
            )
        self.steps += 1
        self._tick += 1
        self._ring.append(
            (self._tick, "decode",
             (self._ring_occupants(), self._carried["token"], self._carried["done"]))
        )
        self._ride_along(counters)
        return True

    def _ride_along(self, counters) -> None:
        """A step's counters (small device arrays, from a family that has
        ``step_summary``) wait beside its ring entry, by its tick, and are read
        where the entry is: no transfer or sync of their own."""
        if counters is not None:
            self._step_counters[self._tick] = counters

    def _summarize_step(self, tick: int, span) -> None:
        """Inside the readback span of the ring entry ``tick``: the step's
        counters as scalars on that span."""
        counters = self._step_counters.pop(tick, None)
        if counters is not None and span is not tracing.NULL_SPAN:
            # graft: sync-ok — read with the entry's tokens, the ring's readback point
            for name, value in self._family.step_summary(jax.device_get(counters)).items():
                span.set(name, value)

    def _ring_occupants(self) -> tuple:
        """Occupant snapshot for a decode/verify ring entry. A PREFILLING
        slot rode this program masked — vacant-done, pad token — so
        absorbing its row at poll would retire the request with one pad
        token; the snapshot holds None in its place instead. Snapshot-TIME
        state is the correct test (not poll-time): by the time the entry
        is popped the slot may have finished prefilling, but this entry's
        program predates that commit."""
        return tuple(
            None if (o is not None and o.prefilling) else o
            for o in self._occupants
        )

    def set_spec_draft_limit(self, n: int) -> None:
        """Clamp the host drafter's proposal length at runtime WITHOUT
        recompiling: the verify program is always padded to the configured
        ``spec_draft_len``, so any limit in [0, spec_draft_len] reuses the
        same compiled program. 0 disables drafting entirely — every step
        takes the plain ``decode_step`` path. The serving degradation
        ladder drops this before clamping budgets or shedding."""
        self._spec_limit = int(np.clip(n, 0, self.spec_draft_len))

    def _materialize_ring(self) -> None:
        """Convert every pending ring payload's device arrays to host numpy
        IN PLACE (blocking until those programs complete) so the drafter
        sees each slot's true current history. Absorption/retirement still
        happen at :meth:`poll` with unchanged ``readback_lag`` semantics —
        this only moves the host transfer earlier for spec-mode steps,
        which need fresh history before they can propose drafts."""
        for i, (tick, kind, payload) in enumerate(self._ring):
            if kind == "chunk":
                continue  # progress marker only — no tokens to materialize
            if kind == "prefill":
                occ, tok, done = payload
                if not isinstance(tok, (int, np.integer)):
                    self._ring[i] = (  # graft: sync-ok — spec drafting needs true history
                        tick, kind, (occ, int(np.asarray(tok)), bool(np.asarray(done)))
                    )
            elif kind == "decode":
                occs, toks, dones = payload
                if not isinstance(toks, np.ndarray):
                    self._ring[i] = (  # graft: sync-ok — spec drafting needs true history
                        tick, kind, (occs, np.asarray(toks), np.asarray(dones))
                    )
            else:  # verify
                occs, emitted, ms, accs, dlens, dones = payload
                if not isinstance(emitted, np.ndarray):
                    self._ring[i] = (
                        tick, kind,
                        (occs, np.asarray(emitted), np.asarray(ms),  # graft: sync-ok
                         np.asarray(accs), dlens, np.asarray(dones)),  # graft: sync-ok
                    )

    def _pending_tokens(self, occ: SlotOccupant):
        """Tokens emitted for ``occ`` that sit in the (materialized) ring
        but have not been absorbed yet, plus whether a pending entry
        already marked the slot done. Entries snapshotting a different
        (earlier) occupant of the same slot are skipped, mirroring poll."""
        toks: List[int] = []
        done = False
        for _, kind, payload in self._ring:
            if kind == "chunk":
                continue  # chunk entries emit no tokens
            if kind == "prefill":
                p_occ, tok, d = payload
                if p_occ is occ:
                    toks.append(int(tok))
                    done = done or bool(d)
            elif kind == "decode":
                occs, t_arr, d_arr = payload
                if occs[occ.slot] is occ:
                    toks.append(int(t_arr[occ.slot]))
                    done = done or bool(d_arr[occ.slot])
            else:  # verify
                occs, emitted, ms, accs, dlens, d_arr = payload
                if occs[occ.slot] is occ:
                    m = int(ms[occ.slot])
                    toks.extend(int(t) for t in emitted[occ.slot, :m])
                    done = done or bool(d_arr[occ.slot])
        return toks, done

    def _prompt_lookup(self, hist: np.ndarray, limit: int) -> np.ndarray:
        """Prompt-lookup n-gram draft: match the longest suffix n-gram of
        ``hist`` (n = spec_ngram down to spec_ngram_min) against an earlier
        occurrence and propose the tokens that followed it — preferring the
        MOST RECENT match with a full ``limit``-token continuation, else the
        earliest match (whose continuation is longest). A naive
        latest-match rule starves on cyclic histories: the latest
        occurrence ends right before the suffix, leaving a 1-token
        continuation. Deterministic, history-only — drafts depend on
        nothing outside the slot, which is what keeps per-slot streams
        reproducible alone-vs-packed."""
        n = len(hist)
        if limit <= 0 or n < 2:
            return np.zeros(0, np.int32)
        for g in range(min(self.spec_ngram, n - 1), self.spec_ngram_min - 1, -1):
            pat = hist[n - g:]
            body = hist[: n - 1]  # suffix occurrence at the very end excluded
            if len(body) < g:
                continue
            windows = np.lib.stride_tricks.sliding_window_view(body, g)
            matches = np.nonzero((windows == pat[None, :]).all(axis=1))[0]
            if len(matches) == 0:
                continue
            ends = matches + g - 1  # match end indices; n-1-end tokens follow
            full = ends[n - 1 - ends >= limit]
            end = int(full[-1]) if len(full) else int(ends[0])
            cont = hist[end + 1 : end + 1 + limit]
            if len(cont):
                return cont.astype(np.int32)
        return np.zeros(0, np.int32)

    def _step_spec(self) -> bool:
        """Draft for every live slot, then dispatch ONE program: the fused
        ``verify_step`` when anyone drafted, the plain ``decode_step`` when
        nobody did (incompressible traffic pays zero verify overhead — the
        k=0 path IS the existing program)."""
        # fast path: every live slot sits in EWMA cooldown, so nobody can
        # draft this tick — skip the blocking ring readback entirely and
        # keep the decode pipeline as deep as plain (non-spec) mode. This
        # is what makes incompressible traffic run at ~plain throughput
        # instead of paying a per-step sync it gets nothing for.
        gated = []
        for occ in self._occupants:
            if occ is None or occ.finished or occ.prefilling:
                continue
            if not (occ.spec_ewma < self._SPEC_MIN_ACCEPT
                    and occ.spec_skips + 1 < occ.spec_cooldown):
                gated = None
                break
            gated.append(occ)
        if gated:
            for occ in gated:
                occ.spec_skips += 1
            return self._dispatch_decode()
        # spec mode's own wait for the device, ahead of poll's
        with tracing.span("engine.readback", kind="materialize", popped=0):
            self._materialize_ring()
        k = self.spec_draft_len
        draft = np.zeros((self.slots, k), np.int32)
        dlen = np.zeros((self.slots,), np.int32)
        for occ in self._occupants:
            if occ is None or occ.finished or occ.prefilling:
                continue
            pending, pending_done = self._pending_tokens(occ)
            if pending_done:
                continue
            # acceptance-EWMA gate: incompressible slots stop paying the
            # k×-wider verify forward; after the cooldown the EWMA resets
            # to the floor so one probe draft can rehabilitate the slot
            if occ.spec_ewma < self._SPEC_MIN_ACCEPT:
                occ.spec_skips += 1
                if occ.spec_skips < occ.spec_cooldown:
                    continue
                occ.spec_skips = 0
                occ.spec_ewma = self._SPEC_MIN_ACCEPT
            emitted_count = len(occ.tokens) + len(pending)
            # the final budgeted token needs no draft (it is sampled by the
            # verify/decode program itself), hence the -1; this cap also
            # keeps every real window position inside prompt+budget <=
            # max_len, so commits can never overhang the arena
            limit = min(self._spec_limit, occ.budget - emitted_count - 1)
            if limit <= 0:
                continue
            hist = np.concatenate(
                [occ.prompt, np.asarray(occ.tokens + pending, np.int32)]
            )
            d = self._prompt_lookup(hist, limit)
            if len(d) == 0:
                # finding nothing to propose is itself incompressibility
                # evidence: decay the EWMA (and back off like a failed
                # probe once below the floor) so matchless slots gate off
                # and stop paying the pre-draft blocking readback on every
                # step — without this, a slot that never matches anything
                # also never updates its EWMA and drags forever
                occ.spec_ewma *= 1 - self._SPEC_EWMA_ALPHA
                if occ.spec_ewma < self._SPEC_MIN_ACCEPT:
                    occ.spec_cooldown = min(
                        2 * occ.spec_cooldown, self._SPEC_COOLDOWN_MAX
                    )
                continue
            draft[occ.slot, : len(d)] = d
            dlen[occ.slot] = len(d)
        total = int(dlen.sum())
        if total == 0:
            return self._dispatch_decode()
        self._record("verify_step", (k,))
        # numpy operands go straight to the jitted call: its C++ fast path
        # does the host->device transfer cheaper than an explicit
        # device_put, and this sits on the serial critical path (each spec
        # step blocks on the previous verify before it can draft)
        with tracing.span(
            "engine.spec_verify",
            drafted=total, live=self.live_count(),
        ):
            (self._donated, self._carried, emitted, m, a) = self._verify_jit(
                self._donated, self._carried, self.model.params,
                self._backend.device_tables(), draft, dlen,
            )
        self.steps += 1
        self.spec_verify_steps += 1
        self.spec_drafted += total
        self._tick += 1
        self._ring.append(
            (self._tick, "verify",
             (self._ring_occupants(), emitted, m, a, dlen, self._carried["done"]))
        )
        return True

    def poll(self, force: bool = False) -> List[SlotOccupant]:
        """Pop every ring entry at least ``readback_lag`` programs old
        (all of them with ``force=True``), collect tokens, and return the
        occupants retired by this poll. Entries referencing occupants that
        finished (or were cancelled) earlier are skipped — their token
        values are pad by construction."""
        self._reap_reservations()  # TTL backstop for abandoned KV transfers
        retired: List[SlotOccupant] = []
        popped: collections.Counter = collections.Counter()
        while self._ring and (
            force or self._tick - self._ring[0][0] >= self.readback_lag
        ):
            tick, kind, payload = self._ring.popleft()
            popped[kind] += 1
            if kind == "chunk":
                continue  # no tokens — the last chunk's entry carries t0
            # the np.asarray reads below are the one place this thread waits
            # for the device: engine.readback is that wait, so a tick's span
            # less the readbacks inside it is the host's own work
            if kind == "prefill":
                occ, tok, done = payload
                with tracing.span("engine.readback", kind=kind, popped=sum(popped.values())) as sp:
                    # graft: sync-ok — the ring IS the readback point (K programs late)
                    tok, done = int(np.asarray(tok)), bool(np.asarray(done))
                    self._summarize_step(tick, sp)
                self._absorb(occ, tok, done, retired)
            elif kind == "decode":
                occs, toks, dones = payload
                with tracing.span("engine.readback", kind=kind, popped=sum(popped.values())) as sp:
                    # graft: sync-ok — the ring IS the readback point (K programs late)
                    toks, dones = np.asarray(toks), np.asarray(dones)
                    self._summarize_step(tick, sp)
                for occ in occs:
                    if occ is None or occ.finished:
                        continue
                    occ.decode_steps += 1
                    self._absorb(occ, int(toks[occ.slot]), bool(dones[occ.slot]), retired)
            else:  # verify: up to W tokens per slot, done applies to the last
                occs, emitted, ms, accs, dlens, dones = payload
                # the ring IS the readback point (K programs late)
                with tracing.span("engine.readback", kind=kind, popped=sum(popped.values())):
                    emitted, ms = np.asarray(emitted), np.asarray(ms)  # graft: sync-ok
                    accs, dones = np.asarray(accs), np.asarray(dones)  # graft: sync-ok
                for occ in occs:
                    if occ is None or occ.finished:
                        continue
                    occ.decode_steps += 1
                    s = occ.slot
                    dl = int(dlens[s])
                    if dl > 0:
                        acc = int(accs[s])
                        self.spec_accepted += acc
                        self.spec_wasted += dl - acc
                        self.spec_emitted += int(ms[s])
                        self.spec_slot_steps += 1
                        rate = acc / dl
                        al = self._SPEC_EWMA_ALPHA
                        occ.spec_ewma = (1 - al) * occ.spec_ewma + al * rate
                        self.spec_ewma = (1 - al) * self.spec_ewma + al * rate
                        # exponential probe backoff: a verify that accepted
                        # nothing doubles the slot's cooldown (capped), any
                        # accepted token resets it — hopeless slots probe
                        # rarely, recovering slots re-engage immediately
                        if acc == 0:
                            occ.spec_cooldown = min(
                                2 * occ.spec_cooldown, self._SPEC_COOLDOWN_MAX
                            )
                        else:
                            occ.spec_cooldown = self._SPEC_COOLDOWN
                    m = int(ms[s])
                    d = bool(dones[s])
                    for j in range(m):
                        if occ.finished:
                            break
                        self._absorb(
                            occ, int(emitted[s, j]), d and j == m - 1, retired
                        )
        if popped:
            self._pw_flush(popped)
        elif not self._ring:
            # idle poll: move the window mark so dead time between
            # requests is never billed to the next program window
            self._pw_mark = self._clock()
        return retired

    def _pw_flush(self, popped: "collections.Counter") -> None:
        """Bill the wall time since the previous synchronizing poll to
        the programs that retired from the ring in that window (weighted
        by their committed roofline predictions — perfwatch splits)."""
        now = self._clock()
        dt, self._pw_mark = now - self._pw_mark, now
        if self.attention_impl == "pallas":
            family = "engine.paged_pallas"
        elif self.spec is not None:
            family = "engine.spec"
        elif self._backend.kind.startswith("paged"):
            family = "engine.paged"
        else:
            family = "engine.dense"
        self._perfwatch.record_window(
            family,
            {perfwatch.RING_KIND_PROGRAM[k]: n for k, n in popped.items()},
            dt,
        )

    def _absorb(self, occ: SlotOccupant, token: int, done: bool, retired: list) -> None:
        if occ.finished:
            return
        if occ.first_token_s is None:
            occ.first_token_s = self._clock()
        occ.tokens.append(token)
        # the device done mask is authoritative (EOS or budget exhausted);
        # the host-side budget guard is belt-and-braces
        if done or len(occ.tokens) >= occ.budget:
            self._retire(occ, retired)

    def _retire(self, occ: SlotOccupant, retired: list) -> None:
        with tracing.span(
            "engine.retire", trace_id=occ.trace_id, slot=occ.slot,
            tokens=len(occ.tokens), decode_steps=occ.decode_steps,
        ):
            occ.finished = True
            self._occupants[occ.slot] = None
            self._return_slot(occ.slot)  # epoch bump: fences late transfers
            # drops block refcounts AND resets the slot's table row to the
            # null block, so the ghost slot's masked decode writes (it rides
            # every step until a new prefill resets it) land in the garbage
            # sink, not in blocks recycled to someone else
            self._backend.release(occ.slot)
        self.retired += 1
        retired.append(occ)

    def cancel(self, occ: SlotOccupant) -> None:
        """Force-retire (deadline shed / external cancel): the slot frees
        immediately for reuse; the device keeps masking it until a new
        occupant's prefill resets it."""
        if occ.finished:
            return
        occ.finished = True
        if occ.prefilling:
            # mid-prefill cancel: stop burning ticks on its chunks; the
            # slot's deferred (unpromoted) registrations die with release()
            occ.prefilling = False
            occ.chunk_args = None
            try:
                self._prefill_queue.remove(occ)
            except ValueError:
                pass
        if self._occupants[occ.slot] is occ:
            self._occupants[occ.slot] = None
            self._return_slot(occ.slot)  # epoch bump: fences late transfers
            self._backend.release(occ.slot)
        self.retired += 1

    def drain(self) -> List[SlotOccupant]:
        """Step until every occupant retires (bounded by the per-slot budget
        mask: at most ~max_len + readback_lag steps)."""
        retired: List[SlotOccupant] = []
        guard = (
            2 * self.max_len + self.readback_lag + 4
            + 2 * self.prefill_chunks_pending()
        )
        while self.live_count() > 0:
            if guard <= 0:
                raise EngineInvariantError(
                    "engine drain did not converge (device done mask never "
                    "caught up with live occupants)"
                )
            guard -= 1
            # drain must converge even when the ladder paused chunked
            # prefill (limit 0): drive one chunk per iteration directly
            if self._prefill_queue and self._prefill_chunk_limit < 1:
                self.prefill_step(limit=1)
            self.step()
            retired.extend(self.poll())
        retired.extend(self.poll(force=True))
        return retired

    def reset(self) -> List[SlotOccupant]:
        """Drop all device state after a failure; fresh arena, empty ring.
        Returns the orphaned (unfinished) occupants so the caller can fail
        their futures — their tokens cannot be trusted."""
        orphans = [o for o in self._occupants if o is not None and not o.finished]
        for occ in orphans:
            occ.finished = True
        self.peak_live = 0
        self._occupants = [None] * self.slots
        with self._admission_lock:
            # every epoch bumps: any transfer reserved against the dead
            # arena is permanently fenced (its KV died with the state)
            self._epochs = [e + 1 for e in self._epochs]
            self._reservations.clear()
            self._free = list(range(self.slots))
        self._ring.clear()
        self._step_counters.clear()
        self._prefill_queue.clear()
        self._backend.reset()  # fresh pool + empty prefix registry/tables
        # the old state goes before the new one is made: a pool that fills most
        # of the chip cannot be held twice (PERF.md, PR 38)
        self._donated = self._carried = None
        self._donated, self._carried = self._init_state()
        return orphans

    def live_tokens(self) -> int:
        """Positions actually holding useful KV right now: each live
        occupant's prompt + emitted tokens (host-side, no device sync)."""
        return sum(_held_positions(o) for o in self._occupants)

    def walked_tokens(self) -> int:
        """Positions the paged decode kernel computes on, for one layer, in a
        step dispatched now: the terms of :meth:`live_tokens`, each rounded up
        to the kernel's chunk, and one chunk for every slot that holds nothing
        (vacant or finished: it rides masked and still costs its chunk; a
        retired slot's device position stays where it was until the next
        insert, which this does not see). 0 where that kernel does not run:
        without a block pool, or with the reference attention over one."""
        if not self._walks_pool():
            return 0
        block_size = self._backend.block_size
        latent = self._family.value_dim is not None  # the kernel's longer chunk
        return sum(
            decode_walked_positions(_held_positions(o), block_size, latent)
            for o in self._occupants
        )

    def _walks_pool(self) -> bool:
        """Whether the paged decode kernel walks this engine's cache."""
        return bool(getattr(self._backend, "block_size", 0)) and self.attention_impl == "pallas"

    def _copy_plan(self, table_row) -> Optional[np.ndarray]:
        if not self._walks_pool():
            return None
        latent = self._family.value_dim is not None
        return decode_copy_plan(table_row, self._backend.block_size, latent)

    def walk_copies(self) -> Tuple[int, int]:
        """``(blocks, copies)`` the paged decode kernel moves over one leaf of
        one layer in a step dispatched now: each slot's live blocks, and the
        copy descriptors they take (``decode_copy_plan`` of the slot's row);
        a slot that holds nothing rides on one block and one copy.
        ``(0, 0)`` where that kernel does not run, as for
        :meth:`walked_tokens`."""
        if not self._walks_pool():
            return 0, 0
        block_size = self._backend.block_size
        blocks = copies = 0
        for occ in self._occupants:
            held = -(-_held_positions(occ) // block_size)
            blocks += max(held, 1)
            copies += int(occ.copies[held]) if held else 1
        return blocks, copies

    def stats(self) -> dict:
        """Observability twin of ``generate_cache_stats``: how many distinct
        (program, operand-shape) signatures this engine dispatched — the
        acceptance gate asserts one decode and one prefill a rung per
        (slots, max_len) config (and one ``verify_step`` with speculative
        decoding) — plus lifetime counters (``prefill_rungs``: prompt
        forwards dispatched at each rung, plain and remote alike),
        speculative acceptance accounting (``spec``: drafted/accepted/
        wasted token counters, acceptance EWMA, emitted-tokens-per-verify;
        accepted/wasted lag drafted by up to ``readback_lag`` polls), and
        the KV store's memory economics (``kv``: pool/arena HBM bytes,
        live- vs reserved-token utilization, prefix-cache hit rate) so
        benches gate on measured memory, not inference."""
        programs = {name: len(sigs) for name, sigs in self._programs.items()}
        kv = self._backend.stats()
        live_tok = self.live_tokens()
        reserved_tok = self._backend.reserved_tokens()
        if self._backend.kind == "dense":
            # dense reserves every slot's worst case up front; utilization
            # against LIVE slots' reservation is the honest comparison
            reserved_live = self.live_count() * self.max_len
        else:
            reserved_live = reserved_tok
        kv.update(
            live_tokens=live_tok,
            utilization=(live_tok / reserved_live) if reserved_live else 0.0,
        )
        return {
            "slots": self.slots,
            "max_len": self.max_len,
            "prompt_bucket": self.prompt_bucket,
            "live": self.live_count(),
            "peak_live": self.peak_live,
            "free": len(self._free),
            "inserted": self.inserted,
            "remote_prefills": self.remote_prefills,
            "prefill_rungs": dict(self._rung_dispatches),
            "prefill_chunk": self.prefill_chunk,
            "prefill_chunk_limit": self._prefill_chunk_limit,
            "prefill_chunks": self.prefill_chunks,
            "prefill_chunks_pending": self.prefill_chunks_pending(),
            "kv_restores": self.kv_restores,
            "steps": self.steps,
            "retired": self.retired,
            "programs": programs,
            "program_count": sum(programs.values()),
            "kv": kv,
            "recurrent_state_bytes": self._backend.recurrent_state_bytes(),
            "spec": {
                "mode": self.spec or "off",
                "draft_len": self.spec_draft_len,
                "draft_limit": self._spec_limit,
                "drafted": self.spec_drafted,
                "accepted": self.spec_accepted,
                "wasted": self.spec_wasted,
                "acceptance_rate": (
                    (self.spec_accepted / self.spec_drafted)
                    if self.spec_drafted else 0.0
                ),
                "acceptance_ewma": self.spec_ewma,
                "verify_steps": self.spec_verify_steps,
                # emitted tokens per (slot, verify step) pair that drafted:
                # 1.0 = verify never beat decode, k+1 = every draft landed
                "tokens_per_step": (
                    (self.spec_emitted / self.spec_slot_steps)
                    if self.spec_slot_steps else 0.0
                ),
            },
        }
