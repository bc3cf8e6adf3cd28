"""Faults planted in the program's timed path, by name. A benchmark run never
plants one: the kept tests do, to see ``correct`` come out false, and so does
``limits.py --fault <name>``, to read on the chip what a fault reads."""

from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses

import numpy as np


def _patched_submit(change_arguments=None, change_result=None):
    from accelerate_tpu.serving import InferenceServer

    submit = InferenceServer.submit

    def patched(self, input_ids, **kw):
        if change_arguments is not None:
            kw = change_arguments(kw)
        inner = submit(self, input_ids, **kw)
        if change_result is None:
            return inner
        outer = concurrent.futures.Future()

        def hand_over(done):
            error = done.exception()
            if error is not None:
                outer.set_exception(error)
            else:
                outer.set_result(change_result(done.result()))

        inner.add_done_callback(hand_over)
        return outer

    return InferenceServer, submit, patched


def _last_token_altered(result):
    tokens = np.array(result.tokens)
    tokens[-1] = (tokens[-1] + 1 + len(tokens)) % 256  # the last new token
    return dataclasses.replace(result, tokens=tokens)


FAULTS = {
    # a served token altered where it is produced
    "token_altered": lambda: _patched_submit(change_result=_last_token_altered),
    # the sampler takes no notice of top-k: it draws from the whole nucleus
    "top_k_ignored": lambda: _patched_submit(change_arguments=lambda kw: {**kw, "top_k": None}),
}


@contextlib.contextmanager
def planted(name: str | None):
    """``InferenceServer.submit`` with the fault ``name`` underneath, for the
    length of the block; no name, no fault."""
    if name is None:
        yield
        return
    owner, original, patched = FAULTS[name]()
    owner.submit = patched
    try:
        yield
    finally:
        owner.submit = original
