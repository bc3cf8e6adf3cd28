"""The fault ISSUE 30 asks the lfm2 cell's ``correct`` to catch: an expert's
contribution left out. Whatever is routed to expert ``EXPERT`` of an expert
layer gets weight 0 there; the other weights stay as they were, so a token that
chose it loses that part of its result, in the full forward, the prefill and the
decode step alike (they share ``ops/moe.py::dropless_moe``).

``chipbench/faults.py`` plants its faults in ``InferenceServer.submit`` and is
not this PR's to edit, so this one lives here: the kept test plants it at the
tiny cell, and on the chip

    python chipbench/tests/expert_left_out.py --workload <cell> --seeds 1,2,3 --controls 0

is ``limits.py`` with the fault underneath.
"""

from __future__ import annotations

import contextlib
import os
import sys

EXPERT = 0


@contextlib.contextmanager
def planted(expert: int = EXPERT):
    import jax.numpy as jnp

    from accelerate_tpu.ops import moe

    route = moe.route_sigmoid_topk

    def without_one_expert(*args, **kw):
        experts, weights = route(*args, **kw)
        return experts, jnp.where(experts == expert, 0.0, weights)

    moe.route_sigmoid_topk = without_one_expert
    try:
        yield
    finally:
        moe.route_sigmoid_topk = route


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(here))
    sys.path[:] = [root] + [p for p in sys.path if os.path.abspath(p or ".") != here]
    from chipbench import limits

    with planted():
        sys.exit(limits.main())
