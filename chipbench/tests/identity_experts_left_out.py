"""The fault ISSUE 38 adds for the LongCat-Flash cell's ``correct`` (beside one held
expert left out, ``expert_left_out.py``): the zero-compute (identity) experts' part
left out. ``dropless_moe`` is told there are none, so a pair that chose one is still
no group's row and adds nothing, where it should add ``w * u``: a token loses about a
third of its top-k weight in every expert layer, in the full forward, the prefill
and the decode step alike.

``chipbench/faults.py`` plants its faults in ``InferenceServer.submit`` and is not
this PR's to edit, so this one lives here, as the two faults beside it do: the kept
test plants it at the tiny cell, and on the chip

    python chipbench/tests/identity_experts_left_out.py --workload <cell> --seeds 1 --controls 0

is ``limits.py`` with the fault underneath.
"""

from __future__ import annotations

import contextlib
import os
import sys


@contextlib.contextmanager
def planted():
    from accelerate_tpu.models import longcat

    layer = longcat.dropless_moe

    def without_identities(*args, **kw):
        return layer(*args, **{**kw, "zero_experts": 0})

    longcat.dropless_moe = without_identities
    try:
        yield
    finally:
        longcat.dropless_moe = layer


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(here))
    sys.path[:] = [root] + [p for p in sys.path if os.path.abspath(p or ".") != here]
    from chipbench import limits

    with planted():
        sys.exit(limits.main())
