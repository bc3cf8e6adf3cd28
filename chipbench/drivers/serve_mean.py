"""The ``serve`` driver with one more number in the comparison that decides
``correct``: ``logit_gap_mean``, the mean over the served greedy tokens of what
``logit_gap`` is the widest of.

Set-up, window, accounting and release are ``serve``'s own, name for name; so are
``logit_gap``, ``sample_gap`` and ``wrong_answers``. Why the mean: in a model
that routes each token to a few experts, rounding decides a near-tie between two
experts the other way on some tokens, and such a token's logits move by as much
as they do when an expert's contribution is left out altogether. The widest gap
over some thousand tokens finds one of those tokens in every run and reads the
same for a sound program and for one that drops an expert (PERF.md, PR 30); a
fault touches most tokens where rounding touches few, and the mean tells them
apart. ``serve.py`` is not this PR's to edit: a ``benchmark`` PR may fold this
file into it.
"""

from __future__ import annotations

import numpy as np

from chipbench import weights as weights_lib
from chipbench.drivers.serve import *  # noqa: F401,F403  (the driver's other functions)
from chipbench.drivers.serve import allowed_set, sample_finished, wrong_answers
from chipbench.lib import free_device_memory, load_module

NUMBERS = ("logit_gap", "sample_gap", "logit_gap_mean")


def served_gaps(ctx, spec, dtype, greedy: list, sampled: list, control: str | None = None) -> dict:
    """``serve.served_gaps`` with the greedy gaps' mean beside their widest:
    over the served tokens of the two samples, against the reference's float32
    logits at the position that made each, how far a greedy token lies below the
    reference's best and a sampled one below the least logit the sampler's knobs
    still allow. Where ``control`` names a lower precision, the same of the
    tokens which that precision puts first and allows last."""
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

    made_by = {"served": ([], []), "control": ([], [])}  # per kind, every compared token's gap
    for kind, sample in enumerate((greedy, sampled)):
        for r in sample:
            tokens = np.asarray(r.result.tokens, np.int32)
            padded = np.zeros((length,), np.int32)
            padded[: len(tokens)] = tokens
            made = slice(len(r.request.prompt) - 1, len(tokens) - 1)
            for name, values in zip(("served", "control"), gaps(weights, jnp.asarray(padded))):
                made_by[name][kind].append(np.asarray(values)[kind][made])
    del weights
    free_device_memory()
    out = {}
    for name, kinds in made_by.items():
        of_greedy, of_sampled = (np.concatenate(kind) for kind in kinds)
        out[name] = dict(zip(NUMBERS, (float(of_greedy.max()), float(of_sampled.max()),
                                       float(of_greedy.mean()))))
    out["tokens_compared"] = dict(zip(("greedy", "sampled"),
                                      (sum(len(v) for v in kind) for kind in made_by["served"])))
    return out


def check(ctx, state) -> dict:
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


def control_readings(ctx, state) -> dict:
    gaps = served_gaps(ctx, state.spec, state.dtype, *state.samples, control="float8")
    return {"control_float8": dict(gaps["control"], wrong_answers=0)}
