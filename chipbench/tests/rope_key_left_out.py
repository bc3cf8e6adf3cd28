"""The second fault ISSUE 35 asks the A.X-K1 cell's ``correct`` to catch (the first,
one held expert left out, is ``expert_left_out.py``'s: expert 0 is held there): the rotated
shared key ``k_r`` left out of the attention scores. The query's rotary part is
zeroed where it is made, so ``q_r . k_r`` adds nothing to any score, up-projected
(the prefill) and absorbed (a decode step) alike: attention then knows a key's
content and nothing of where it stands.

``chipbench/faults.py`` plants its faults in ``InferenceServer.submit`` and is not
this PR's to edit, so this one lives here, as ``expert_left_out.py`` does: the kept
test plants it at the tiny cell, and on the chip

    python chipbench/tests/rope_key_left_out.py --workload <cell> --seeds 1 --controls 0

is ``limits.py`` with the fault underneath.
"""

from __future__ import annotations

import contextlib
import os
import sys


@contextlib.contextmanager
def planted():
    import jax.numpy as jnp

    from accelerate_tpu.models import axk1

    rope = axk1._rope

    def no_rotary_query(config, x, positions):
        # a query's rotary part has a column for every head; the shared key's has one
        rotated = rope(config, x, positions)
        return jnp.zeros_like(rotated) if x.shape[2] > 1 else rotated

    axk1._rope = no_rotary_query
    try:
        yield
    finally:
        axk1._rope = rope


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(here))
    sys.path[:] = [root] + [p for p in sys.path if os.path.abspath(p or ".") != here]
    from chipbench import limits

    with planted():
        sys.exit(limits.main())
