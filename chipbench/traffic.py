"""The one generator of traffic. A cell's file gives parameters (lengths,
sharing of greedy and sampled requests, clients); this turns them and ``--seed``
into rows to train on or requests to serve.

Every seed gets the same requests in another order: lengths are the stratified
quantiles of the stated distribution, each prompt length is paired with an output
length and a kind (greedy or sampled) by a rule that takes no seed
(``paired_sizes``), and the seed permutes the pairs and draws the token ids. Which
prompt meets which output is part of the work (a long prompt that stays for a long
output holds its keys and values for as long, and the decode kernel's time follows
what is live), so it may not change with the seed. The pairs come round again after
``pool`` requests, so that a window, which takes as many requests as the system is
fast, holds whole rounds of the same work whatever the seed; the token ids are
fresh for every request (a prompt sent twice would be served from the prefix cache).

A closed loop that starts with every client on a fresh request is not in its
steady state: nothing finishes for a while, then much at once. So the requests
that stand in the slots when the window opens are drawn as the steady state has
them, longer requests the likelier, each somewhere along its life
(``standing_requests``); the requests of the mix follow them.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    temperature: float
    top_k: int | None
    top_p: float | None
    seed: int

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def training_rows(params: dict, vocab_size: int, seed: int) -> np.ndarray:
    """``(sequences, seq_len)`` int32 token ids, uniform over the vocabulary:
    rows that all differ."""
    rng = np.random.default_rng([int(seed), 11])
    return rng.integers(
        0, vocab_size, size=(params["sequences"], params["seq_len"]), dtype=np.int32
    )


def stratified_lengths(dist: dict, count: int) -> np.ndarray:
    """``count`` lengths at the mid-quantiles of a log-normal (``median``,
    ``sigma``) cut to ``[min, max]``, ascending."""
    if dist.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {dist!r}")
    normal = statistics.NormalDist()
    values = [
        math.exp(math.log(dist["median"]) + dist["sigma"] * normal.inv_cdf((i + 0.5) / count))
        for i in range(count)
    ]
    return np.clip(np.rint(values), dist["min"], dist["max"]).astype(np.int64)


def spread_evenly(count: int, share: float) -> np.ndarray:
    """``count`` flags of which ``round(share * count)`` are set, as evenly
    spaced as whole numbers allow (every other one for a half)."""
    marks = np.arange(count + 1) * int(round(share * count)) // count
    return marks[1:] > marks[:-1]


def pairing(count: int) -> np.ndarray:
    """A permutation of ``range(count)`` that takes no seed and leaves no order
    behind: ``i -> stride * i mod count`` with the stride at the golden section
    of ``count`` (the next below it that shares no factor with ``count``), so
    that neighbours land far apart and no end of one list meets an end of the
    other throughout."""
    stride = max(1, int(count * (math.sqrt(5.0) - 1.0) / 2.0))
    while math.gcd(stride, count) != 1:
        stride -= 1
    return (stride * np.arange(count)) % count


def paired_sizes(params: dict, count: int) -> tuple:
    """``(prompt_lens, output_lens, greedy)``, ``count`` of each and the same for
    every seed: the two lists of stratified lengths, the prompts ascending,
    the outputs as ``pairing`` deals them, and the greedy share spread evenly
    over the pairs. (For the 32 sizes of ShareGPT's lengths the summed product of
    prompt and output is 0.996 of what independent lengths give.)"""
    prompt_lens = stratified_lengths(params["prompt_len"], count)
    output_lens = stratified_lengths(params["output_len"], count)[pairing(count)]
    return prompt_lens, output_lens, spread_evenly(count, params["greedy_share"])


def request_pool(params: dict, vocab_size: int, seed: int) -> list:
    """The ``requests`` requests of a run, in the order in which clients take
    them; the same ``pool`` pairs of sizes, in an order the seed draws, come
    round with the period ``pool``."""
    period = params["pool"]
    rng = np.random.default_rng([int(seed), 23])
    order = rng.permutation(period)
    prompt_lens, output_lens, greedy = (x[order] for x in paired_sizes(params, period))
    sampling = params["sampling"]
    requests = []
    for index in range(params["requests"]):
        i = index % period
        prompt = rng.integers(0, vocab_size, size=(int(prompt_lens[i]),), dtype=np.int32)
        if greedy[i]:
            knobs = dict(temperature=0.0, top_k=None, top_p=None)
        else:
            knobs = dict(temperature=sampling["temperature"], top_k=sampling["top_k"],
                         top_p=sampling["top_p"])
        requests.append(Request(
            index=index, prompt=prompt, max_new_tokens=int(output_lens[i]),
            seed=int(rng.integers(0, 2**31 - 1)), **knobs,
        ))
    return requests


def warmup_requests(params: dict, vocab_size: int, seed: int) -> list:
    """One request a client for set-up: prompts of the mix's lengths, outputs of
    staggered lengths, so that the clients leave set-up out of step."""
    clients = params["clients"]
    rng = np.random.default_rng([int(seed), 37])
    prompt_lens = rng.permutation(stratified_lengths(params["prompt_len"], clients))
    top = params["warmup_output_max"]
    requests = []
    for i in range(clients):
        prompt = rng.integers(0, vocab_size, size=(int(prompt_lens[i]),), dtype=np.int32)
        sampled = i % 2 == 1
        sampling = params["sampling"]
        requests.append(Request(
            index=-1 - i, prompt=prompt,
            max_new_tokens=max(2, int(round(top * (i + 1) / clients))),
            temperature=sampling["temperature"] if sampled else 0.0,
            top_k=sampling["top_k"] if sampled else None,
            top_p=sampling["top_p"] if sampled else None,
            seed=int(rng.integers(0, 2**31 - 1)),
        ))
    return requests


def standing_requests(params: dict, vocab_size: int, seed: int) -> list:
    """One request a client, to stand in the slots when the window opens, as a
    closed loop in its steady state has them: a request is in flight the likelier
    the longer it is, and is met at a point of its life that is uniform. So the
    outputs are what is left of such requests: the stratified quantiles, over the
    clients, of the mix's output tokens laid end to end; what such a request has
    made already rides in its prompt (as far as the longest prompt allows), so
    that the cache is as full as the steady state has it. The same requests for
    every seed (sizes paired as ``paired_sizes`` pairs them), dealt to the
    clients in an order the seed draws."""
    clients, period = params["clients"], params["pool"]
    rng = np.random.default_rng([int(seed), 31])
    outputs = stratified_lengths(params["output_len"], period)
    ends = np.cumsum(outputs)
    left, made = [], []
    for i in range(clients):
        at = (i + 0.5) / clients * ends[-1]  # a token of the mix, by its place in the row
        which = int(np.searchsorted(ends, at, side="right"))
        left.append(max(1, int(np.ceil(ends[which] - at))))  # what is left of its request
        made.append(int(outputs[which]) - left[-1])
    # which prompt carries which remainder, and of which kind, takes no seed either
    deal = pairing(clients)
    left, made = np.asarray(left)[deal], np.asarray(made)[deal]
    prompt_lens = np.minimum(stratified_lengths(params["prompt_len"], clients) + made,
                             params["prompt_len"]["max"])
    greedy = spread_evenly(clients, params["greedy_share"])
    order = rng.permutation(clients)
    left, prompt_lens, greedy = left[order], prompt_lens[order], greedy[order]
    sampling = params["sampling"]
    requests = []
    for i in range(clients):
        prompt = rng.integers(0, vocab_size, size=(int(prompt_lens[i]),), dtype=np.int32)
        requests.append(Request(
            index=-1 - clients - i, prompt=prompt, max_new_tokens=int(left[i]),
            temperature=0.0 if greedy[i] else sampling["temperature"],
            top_k=None if greedy[i] else sampling["top_k"],
            top_p=None if greedy[i] else sampling["top_p"],
            seed=int(rng.integers(0, 2**31 - 1)),
        ))
    return requests
