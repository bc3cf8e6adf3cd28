"""The serving driver: ``create_<family>`` -> ``InferenceServer`` in continuous
mode -> ``submit``, under a closed loop of clients.

Each client holds one request in flight and sends its next when the last one
resolves. Set-up builds the server, sends every client one warm-up request whose
output length is staggered (that compiles and warms both engine programs and
leaves the clients out of step) and then the request that stands in its slot
when the window opens, as the loop's steady state has them; the window starts
``settle_seconds`` after the last warm-up request has resolved. After the window the requests that were in flight at its close are
waited for, while the clients go on sending as before, so that those requests
finish under the window's own load. One thread drives all clients; the server
has its own.
"""

from __future__ import annotations

import inspect
import queue
import time

import numpy as np

from chipbench import traffic
from chipbench import weights as weights_lib
from chipbench.lib import BenchError, count, free_device_memory, load_module, percentile
from chipbench.program import program_config

DRAIN_TIMEOUT_S = 120.0
POLL_S = 0.05
# what this driver asks of the configuration's counts file
COUNTS = ("prefill_flops", "decode_flops", "kv_bytes_per_token")


class State:
    """What set-up hands to the window."""


class Record:
    """One request's life as its client saw it."""

    __slots__ = ("request", "client", "sent", "done", "result", "failed")

    def __init__(self, request, client, sent):
        self.request, self.client, self.sent = request, client, sent
        self.done = self.result = None
        self.failed = False

    @property
    def new_tokens(self) -> int:
        return self.request.max_new_tokens


class ClosedLoop:
    def __init__(self, server, pool, block_size: int = 1):
        self.server, self.pool, self.block_size = server, pool, block_size
        self.finished = queue.Queue()
        self.next_index = 0
        self.in_flight = 0
        # cache positions that the requests in flight reserve, whole blocks each
        self.reserved_positions = 0

    def _positions(self, request) -> int:
        whole = -(-(len(request.prompt) + request.max_new_tokens) // self.block_size)
        return whole * self.block_size

    def send(self, client: int, request) -> None:
        record = Record(request, client, time.perf_counter())
        future = self.server.submit(
            request.prompt, max_new_tokens=request.max_new_tokens,
            temperature=request.temperature, top_k=request.top_k, top_p=request.top_p,
            seed=request.seed,
        )
        self.in_flight += 1
        self.reserved_positions += self._positions(request)
        # runs on the server's thread: stamp the time and hand over, nothing more
        future.add_done_callback(
            lambda f, record=record: self.finished.put((record, time.perf_counter(), f))
        )

    def send_next(self, client: int) -> None:
        self.send(client, self.pool[self.next_index % len(self.pool)])
        self.next_index += 1

    def take(self, timeout: float):
        """The next finished request as a :class:`Record`, or None."""
        try:
            record, done, future = self.finished.get(timeout=timeout)
        except queue.Empty:
            return None
        self.in_flight -= 1
        self.reserved_positions -= self._positions(record.request)
        record.done = done
        error = future.exception()
        if error is not None:
            record.failed = True
        else:
            record.result = future.result()
            want = len(record.request.prompt) + record.request.max_new_tokens
            record.failed = bool(record.result.degraded) or len(record.result.tokens) != want
        return record


def setup(ctx) -> State:
    from accelerate_tpu.serving import InferenceServer
    from accelerate_tpu.utils.dataclasses import ServingConfig

    workload, config = ctx.workload, ctx.config
    for function in COUNTS:  # a missing count ends the run here, not after its window
        count(config, function)
    marks = [("start", time.perf_counter())]
    family, built = program_config(config)
    reference = load_module("reference", config["reference"])
    state = State()
    state.spec = reference.weight_spec(config)
    state.dtype = built.param_dtype

    create = getattr(family, f"create_{config['family']}")
    # shapes only, where the program can: its own initial weights are thrown away
    shapes_only = {"abstract": True} if "abstract" in inspect.signature(create).parameters else {}
    model = create(built, **shapes_only)
    model.params = None  # the program's own initial weights make room for those of the seed
    marks.append(("program_init", time.perf_counter()))
    model.params = weights_lib.nest(weights_lib.make_weights(state.spec, ctx.seed, state.dtype))
    import jax

    jax.block_until_ready(model.params)
    marks.append(("weights", time.perf_counter()))
    state.server = InferenceServer(model, ServingConfig(**workload["serving"]))
    state.engine = state.server.engine
    if state.engine.attention_impl != workload["serving"].get("attention_impl", "reference"):
        raise BenchError(
            f"chipbench: the cell asks for attention_impl="
            f"{workload['serving'].get('attention_impl')!r}, the engine runs "
            f"{state.engine.attention_impl!r}"
        )
    marks.append(("server", time.perf_counter()))
    tr = workload["traffic"]
    state.loop = ClosedLoop(state.server, traffic.request_pool(tr, config["vocab_size"], ctx.seed),
                            workload["serving"].get("engine_block_size", 1))
    # the warm-up round; after its warm-up a client sends the request that stands in
    # its slot when the window opens, and after that the requests of the mix
    standing = traffic.standing_requests(tr, config["vocab_size"], ctx.seed)
    for client, request in enumerate(traffic.warmup_requests(tr, config["vocab_size"], ctx.seed)):
        state.loop.send(client, request)
    warm = tr["clients"]
    deadline = time.perf_counter() + DRAIN_TIMEOUT_S
    while warm:
        record = state.loop.take(POLL_S)
        if record is None:
            if time.perf_counter() > deadline:
                raise BenchError("chipbench: the warm-up round did not finish")
            continue
        if record.failed:
            raise BenchError("chipbench: a warm-up request failed")
        if -tr["clients"] <= record.request.index < 0:  # a warm-up request
            warm -= 1
            state.loop.send(record.client, standing[record.client])
        else:
            state.loop.send_next(record.client)
    marks.append(("warm_up_round", time.perf_counter()))
    # the standing requests went in as a burst of prefills that held the decode steps
    # up; the loop runs on until that burst lies well behind the window's start
    settled = time.perf_counter() + tr["settle_seconds"]
    while time.perf_counter() < settled:
        record = state.loop.take(POLL_S)
        if record is not None:
            if record.failed:
                raise BenchError("chipbench: a request failed while the loop settled")
            state.loop.send_next(record.client)
    marks.append(("settled", time.perf_counter()))
    state.facts = {"params": model.num_parameters, "slots": state.engine.slots,
                   "kv_hbm_bytes": state.engine.stats()["kv"].get("hbm_bytes"),
                   "setup_marks": [[n, round(t - marks[0][1], 2)] for n, t in marks[1:]]}
    return state


def window(ctx, state: State, seconds: float, hooks) -> dict:
    loop, engine = state.loop, state.engine
    records, live_tokens, live_slots, reserved = [], [], [], []
    stats0 = engine.stats()
    start = time.perf_counter()
    end = start + seconds
    while True:
        record = loop.take(POLL_S)
        now = time.perf_counter()
        hooks.tick(now - start)
        if now >= end:
            break
        live_tokens.append(engine.live_tokens())
        live_slots.append(engine.live_count())
        reserved.append(loop.reserved_positions)
        if record is not None:
            records.append(record)
            loop.send_next(record.client)
    stats1 = engine.stats()
    hooks.close()
    closed = time.perf_counter()
    # the window has closed: wait for what was in flight at the close, and keep
    # every client sending meanwhile, so that those requests finish under the load
    # they were sent into. What is sent from here on is never counted.
    waited_for = loop.in_flight
    if record is not None:  # came back as the window closed
        records.append(record)
        loop.send_next(record.client)
    deadline = closed + DRAIN_TIMEOUT_S
    while waited_for:
        record = loop.take(POLL_S)
        if record is None:
            if time.perf_counter() > deadline:
                raise BenchError(f"chipbench: {waited_for} request(s) never came back")
            continue
        if record.sent <= end:
            records.append(record)
            waited_for -= 1
        loop.send_next(record.client)
    state.records = records
    counters = {
        "decode_steps": stats1["steps"] - stats0["steps"],
        "inserted": stats1["inserted"] - stats0["inserted"],
        "programs": stats1["programs"],
        "live_tokens_mean": float(np.mean(live_tokens)) if live_tokens else 0.0,
        "live_slots_mean": float(np.mean(live_slots)) if live_slots else 0.0,
        "reserved_tokens_mean": float(np.mean(reserved)) if reserved else 0.0,
        "slots": engine.slots, "drain_s": time.perf_counter() - closed,
        "kv": {k: v for k, v in stats1["kv"].items() if isinstance(v, (int, float))},
    }
    out = account(ctx, records, start, end, counters)
    # every request's life, for whoever reads the run afterwards: sent and done
    # (seconds from the window's start), prompt, new tokens, and the server's stamps
    out["requests"] = [
        [round(r.sent - start, 3), round(r.done - start, 3), len(r.request.prompt), r.new_tokens,
         int(r.request.greedy), int(r.failed),
         *(() if r.result is None else (round(r.result.queue_wait_s, 3), round(r.result.ttft_s, 3)))]
        for r in sorted(records, key=lambda r: r.sent)
    ]
    # the engine's own count of the window's tokens, to hold the clients' against:
    # one a live slot a decode step and one an insertion (live slots are polled,
    # so it is near, not exact); and what the cache held against what it reserves
    per_token = count(ctx.config, "kv_bytes_per_token")(ctx.config)
    live_bytes = counters["live_tokens_mean"] * per_token
    pool_bytes = stats1["kv"].get("hbm_bytes") or 0
    out["facts"].update(
        engine_tokens_per_s=(counters["decode_steps"] * counters["live_slots_mean"]
                             + counters["inserted"]) / seconds,
        kv_live_bytes_mean=live_bytes, kv_pool_bytes=pool_bytes,
        kv_reserved_bytes_mean=counters["reserved_tokens_mean"] * per_token,
        chip_memory_bytes=ctx.memory_bytes,
        kv_live_share_of_pool=live_bytes / pool_bytes if pool_bytes else None,
        kv_live_share_of_chip=live_bytes / ctx.memory_bytes if ctx.memory_bytes else None,
    )
    return out


def account(ctx, records: list, start: float, end: float, counters: dict) -> dict:
    """The window's end-to-end numbers from the clients' records. Tokens are
    those made inside the window: a request's new tokens times the share of its
    life, from sending to result, that lay inside. Latencies are over every
    request that came back inside the window, on the client's clock, over the
    request's new tokens: the time per output token a caller saw, waiting and
    prefill included; a request that failed counts as the worst."""
    seconds = end - start
    tokens = flops = 0.0
    prefill_flops = count(ctx.config, "prefill_flops")
    decode_flops = count(ctx.config, "decode_flops")
    for r in records:
        inside = max(0.0, min(r.done, end) - max(r.sent, start)) / (r.done - r.sent)
        if r.failed or inside <= 0.0:
            continue
        tokens += inside * r.new_tokens
        p, n = len(r.request.prompt), r.new_tokens
        flops += inside * (prefill_flops(ctx.config, p)
                           + (n - 1) * decode_flops(ctx.config, p + n / 2.0))
    back = [r for r in records if start <= r.done <= end]
    failed = [r for r in back if r.failed]
    good = [r for r in back if not r.failed]
    if not good:
        raise BenchError("chipbench: no request came back inside the window")
    worst = float("inf")
    latency = [1e3 * (r.done - r.sent) / r.new_tokens for r in good] + [worst] * len(failed)
    spans = {
        "ttft_ms": [1e3 * r.result.ttft_s for r in good] + [worst] * len(failed),
        "tpot_ms": [1e3 * (r.result.latency_s - r.result.ttft_s) / max(1, r.new_tokens - 1)
                    for r in good] + [worst] * len(failed),
        "queue_wait_ms": [1e3 * r.result.queue_wait_s for r in good],
    }
    if not np.isfinite(percentile(latency, 90.0)):
        raise BenchError(f"chipbench: {len(failed)} of {len(back)} requests failed")
    sent_inside = [r for r in records if start <= r.sent <= end and not r.failed]
    # the issue's count, to hold the first against: the tokens of the requests that
    # came back inside the window (swings with which long ones straddle its ends)
    completed = sum(r.new_tokens for r in good)
    return {
        "window_s": seconds, "tokens": tokens, "flops": flops,
        "attempted": len(back), "failed": len(failed),
        "end_to_end": {"serve_tokens_per_s": tokens / seconds,
                       "norm_latency_p50_ms": percentile(latency, 50.0)},
        "spans": spans,
        "counters": dict(
            counters,
            prompt_tokens_sent=sum(len(r.request.prompt) for r in sent_inside),
            prefill_flops_sent=sum(prefill_flops(ctx.config, len(r.request.prompt))
                                   for r in sent_inside),
            requests_sent=len(sent_inside),
        ),
        "facts": {
            "model_flops": flops,
            "requests_back": len(back), "requests_per_s": len(good) / seconds,
            "tokens_of_requests_back_per_s": completed / seconds,
            "norm_latency_ms": {f"p{q}": percentile(latency, q) for q in (50, 75, 90, 95)},
            "prompt_len_p50": percentile([len(r.request.prompt) for r in good], 50.0),
            "new_tokens_p50": percentile([r.new_tokens for r in good], 50.0),
            **counters,
        },
    }


def release(state: State) -> None:
    state.server.close(drain=True)
    state.server = state.engine = state.loop = None
    free_device_memory()


# ----------------------------------------------------------------- the comparison
def sample_finished(records: list, seed: int, count: int, greedy: bool) -> list:
    """Of the finished requests of one kind (greedy or sampled), the longest
    and ``count - 1`` others drawn from the seed."""
    kind = [r for r in records
            if not r.failed and r.request.greedy == greedy and r.request.index >= 0]
    if not kind:
        raise BenchError(f"chipbench: no {'greedy' if greedy else 'sampled'} request finished")
    kind.sort(key=lambda r: r.request.index)
    longest = max(kind, key=lambda r: len(r.result.tokens))
    others = [r for r in kind if r is not longest]
    rng = np.random.default_rng([int(seed), 41 if greedy else 43])
    picked = rng.choice(len(others), size=min(count - 1, len(others)), replace=False)
    return [longest] + [others[i] for i in sorted(picked)]


def allowed_set(scaled, top_k: int, top_p: float):
    """What a sampler may draw at each position, in the plain: of the ``top_k``
    largest of ``scaled`` (logits over temperature), the fewest whose softmax
    among those k reaches ``top_p``. Returns ``(cutoff, lowest)``: the least
    value still allowed, and the token that holds it."""
    import jax
    import jax.numpy as jnp

    values, tokens = jax.lax.top_k(scaled, top_k)  # descending
    probs = jax.nn.softmax(values, axis=-1)
    above = jnp.cumsum(probs, axis=-1) - probs  # the mass strictly above each
    last = jnp.sum(above < top_p, axis=-1) - 1
    return (jnp.take_along_axis(values, last[:, None], axis=-1)[:, 0],
            jnp.take_along_axis(tokens, last[:, None], axis=-1)[:, 0])


def served_gaps(ctx, spec, dtype, greedy: list, sampled: list, control: str | None = None) -> dict:
    """Over the served tokens of the two samples, against the reference's float32
    logits at the position that made each. ``logit_gap``: how far a greedy token
    lies below the reference's best. ``sample_gap``: how far a sampled token
    lies below the least logit that temperature, top-k and top-p as the cell
    states them still allow (0 inside the allowed set). Each is the widest over
    its tokens. Where ``control`` names a lower precision, the same two of the
    tokens which that precision puts first and allows last. Every request
    goes through one program of the engine's full length, so nothing compiles
    anew from run to run."""
    import jax
    import jax.numpy as jnp

    reference = load_module("reference", ctx.config["reference"])
    weights = weights_lib.make_weights(spec, ctx.seed, dtype)
    length = ctx.workload["serving"]["engine_max_len"]
    knobs = ctx.workload["traffic"]["sampling"]
    temperature, top_p = float(knobs["temperature"]), float(knobs["top_p"] or 1.0)
    top_k = int(knobs["top_k"] or ctx.config["vocab_size"])

    @jax.jit
    def gaps(w, ids):
        """``[kind, t]``: the gaps at the position that made token ``t + 1``."""
        ref = reference.logits(ctx.config, w, ids, "float32")
        best = jnp.max(ref, axis=-1)
        cutoff, _ = allowed_set(ref / temperature, top_k, top_p)

        def of(tokens):
            at = jnp.take_along_axis(ref, tokens[:, None], axis=-1)[:, 0]
            return best - at, jnp.maximum(0.0, cutoff * temperature - at)

        served = of(jnp.roll(ids, -1))
        if control is None:
            return jnp.stack(served), jnp.stack(served)
        low = reference.logits(ctx.config, w, ids, control)
        first = of(jnp.argmax(low, axis=-1))[0]
        last = of(allowed_set(low / temperature, top_k, top_p)[1])[1]
        return jnp.stack(served), jnp.stack((first, last))

    widest = {"served": [0.0, 0.0], "control": [0.0, 0.0]}
    compared = [0, 0]
    for kind, sample in enumerate((greedy, sampled)):
        for r in sample:
            tokens = np.asarray(r.result.tokens, np.int32)
            padded = np.zeros((length,), np.int32)
            padded[: len(tokens)] = tokens
            made = slice(len(r.request.prompt) - 1, len(tokens) - 1)
            for name, values in zip(("served", "control"), gaps(weights, jnp.asarray(padded))):
                widest[name][kind] = max(widest[name][kind],
                                         float(np.asarray(values)[kind][made].max()))
            compared[kind] += made.stop - made.start
    del weights
    free_device_memory()
    return {
        "served": dict(zip(("logit_gap", "sample_gap"), widest["served"])),
        "control": dict(zip(("logit_gap", "sample_gap"), widest["control"])),
        "tokens_compared": {"greedy": compared[0], "sampled": compared[1]},
    }


def wrong_answers(ctx, records: list) -> int:
    """Answers that say the wrong thing whatever the weights: a prompt that came
    back altered, or a token outside the vocabulary."""
    wrong = 0
    for r in records:
        if r.failed or r.result is None:
            continue
        tokens = np.asarray(r.result.tokens)
        n = len(r.request.prompt)
        if (not np.array_equal(tokens[:n], r.request.prompt) or tokens.min() < 0
                or tokens.max() >= ctx.config["vocab_size"]):
            wrong += 1
    return wrong


def check(ctx, state: State) -> dict:
    count = ctx.workload["check"]["requests"]
    state.samples = [sample_finished(state.records, ctx.seed, count, greedy)
                     for greedy in (True, False)]
    gaps = served_gaps(ctx, state.spec, state.dtype, *state.samples)
    return {
        **gaps["served"],
        "wrong_answers": wrong_answers(ctx, state.records),
        "_facts": {"tokens_compared": gaps["tokens_compared"],
                   "requests_compared": [len(s) for s in state.samples],
                   "longest": int(max(len(r.result.tokens) for s in state.samples for r in s))},
    }


def control_readings(ctx, state: State) -> dict:
    """For ``limits.py``: at the same prompts and served tokens, the gaps of the
    tokens that the reference computed in float8 puts first (for a greedy
    request) and still allows (for a sampled one)."""
    gaps = served_gaps(ctx, state.spec, state.dtype, *state.samples, control="float8")
    return {"control_float8": dict(gaps["control"], wrong_answers=0)}
