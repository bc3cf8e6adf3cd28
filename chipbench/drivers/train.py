"""The training driver: ``create_<family>`` -> ``Accelerator.prepare`` ->
``prepare_data_loader`` -> ``train_step``, as a user's loop.

Set-up builds the one compiled step with its state, drives it through its first
steps on the loader's own batches (they are the warm-up, and what the reference
follows) and hands that same object to the window. The window calls the step,
fetches the loss every ``fetch_every``-th step as a logger would, and stops at
the first such fetch after ``--seconds``.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from chipbench import traffic
from chipbench import weights as weights_lib
from chipbench.lib import BenchError, count, free_device_memory, load_module, median
from chipbench.program import program_config

REFERENCE_STEPS = 3


class State:
    """What set-up hands to the window, and what it read on the way."""


def setup(ctx) -> State:
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.parallelism_config import ParallelismConfig

    workload, config = ctx.workload, ctx.config
    tr, opt = workload["traffic"], workload["optimizer"]
    count(config, "train_flops_per_token")  # a missing count ends the run here, not after its window
    marks = [("start", time.perf_counter(), 0)]

    def mark(name):
        stats = jax.devices()[0].memory_stats() or {}
        marks.append((name, time.perf_counter(), int(stats.get("peak_bytes_in_use", 0))))

    family, built = program_config(config)
    reference = load_module("reference", config["reference"])
    state = State()
    state.spec = reference.weight_spec(config)
    state.dtype = built.param_dtype

    parallelism = workload.get("parallelism")
    accelerator = Accelerator(
        mixed_precision=config["precision"]["mixed_precision"],
        parallelism_config=ParallelismConfig(**parallelism) if parallelism else None,
        gradient_accumulation_steps=opt.get("gradient_accumulation_steps", 1),
    )
    model = getattr(family, f"create_{config['family']}")(built, abstract=True)
    model.params = weights_lib.nest(weights_lib.make_weights(state.spec, ctx.seed, state.dtype))
    n_params = model.num_parameters
    mark("weights")
    model, optimizer = accelerator.prepare(
        model, optax.adamw(opt["learning_rate"], weight_decay=opt["weight_decay"])
    )
    state.rows = traffic.training_rows(tr, config["vocab_size"], ctx.seed)
    batch_size = tr["batch_size"] * ctx.chips
    loader = accelerator.prepare_data_loader(
        {"input_ids": state.rows}, batch_size=batch_size, drop_last=True
    )
    step = accelerator.train_step(
        getattr(family, f"{config['family']}_loss"), max_grad_norm=opt.get("max_grad_norm")
    )
    state.accelerator, state.model, state.optimizer, state.step = (
        accelerator, model, optimizer, step)
    state.batches = itertools.chain.from_iterable(itertools.repeat(loader))
    state.tokens_per_step = batch_size * tr["seq_len"]
    state.facts = {"params": n_params, "batch_size": batch_size, "seq_len": tr["seq_len"]}
    mark("prepared")

    # the first steps: the warm-up, and what the reference follows
    norms_of = jax.jit(lambda tree: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for k, v in weights_lib.flatten(tree).items()
    })
    losses, grad_norms = [], None
    for index in range(REFERENCE_STEPS):
        losses.append(float(step(next(state.batches))))
        if index == 0:
            mark("first_step")
            # AdamW's first moment after one step is (1 - b1) times the gradient
            # that the optimizer got
            mu = _first_moment(optimizer.opt_state)
            b1 = opt.get("b1", 0.9)
            grad_norms = {k: float(v) / (1.0 - b1) for k, v in norms_of(mu).items()}
    change = weights_lib.distance_from_initial(
        state.spec, ctx.seed, state.dtype, weights_lib.flatten(model.params)
    )
    state.readings = {"losses": losses, "grad_norms": grad_norms, "change_norms": change}
    mark("readings")
    # what the window itself calls besides the step: one more step and a fetch
    float(step(next(state.batches)))
    state.facts["setup_marks"] = [
        [name, round(t - marks[0][1], 2), peak] for name, t, peak in marks[1:]
    ]
    return state


def _first_moment(opt_state):
    """The ``mu`` tree of optax's Adam state, wherever the chain keeps it."""
    found = []

    def visit(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for child in node:
                visit(child)

    visit(opt_state)
    if len(found) != 1:
        raise BenchError(f"chipbench: found {len(found)} Adam states in the optimizer's state")
    return found[0]


def window(ctx, state: State, seconds: float, hooks) -> dict:
    fetch_every = ctx.workload["traffic"]["fetch_every"]
    step, batches = state.step, state.batches
    steps, loss, fetched = 0, None, []
    start = time.perf_counter()
    while True:
        loss = step(next(batches))
        steps += 1
        if steps % fetch_every == 0:
            fetched.append(float(loss))  # waits for the device, as a logger's read does
            now = time.perf_counter()
            hooks.tick(now - start)
            if now - start >= seconds:
                break
    elapsed = now - start
    hooks.close()
    if not all(np.isfinite(fetched)):
        raise BenchError(f"chipbench: loss not finite in the window: {fetched[-5:]}")
    tokens = steps * state.tokens_per_step
    return {
        "window_s": elapsed, "steps": steps, "tokens": tokens,
        "attempted": steps, "failed": 0,
        "end_to_end": {"train_tokens_per_s_chip": tokens / elapsed / ctx.chips},
        "facts": {"losses_fetched_first_last": [fetched[0], fetched[-1]],
                  "step_compiles": state.step.jitted._cache_size(),
                  "step_ms_host": 1e3 * elapsed / steps},
    }


def release(state: State) -> None:
    """Drop the program's state so that the reference has the chip's memory."""
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    state.accelerator.free_memory()
    for name in ("model", "optimizer", "step", "batches", "accelerator"):
        setattr(state, name, None)
    for cls in (AcceleratorState, GradientState, PartialState):
        cls._reset_state()
    free_device_memory()


# ----------------------------------------------------------------- the comparison
def worst_leaf_gap(program: dict, reference: dict, skip=()) -> tuple:
    """The worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf, whichever is
    larger (some leaves are all but zero). Returns ``(gap, leaf)``."""
    floor = median(reference.values())
    worst, where = 0.0, None
    for name, ref in reference.items():
        if name in skip:
            continue
        gap = abs(program[name] - ref) / max(ref, floor)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def compare(readings: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``, from the program's readings and the
    reference's (each ``losses``, ``grad_norms``, ``change_norms``)."""
    loss_gap = max(
        abs(a - b) / abs(b) for a, b in zip(readings["losses"], ref["losses"])
    )
    grad_gap, grad_leaf = worst_leaf_gap(readings["grad_norms"], ref["grad_norms"])
    # leaves whose gradient is nought to rounding move under Adam by round-off alone
    floor = 1e-3 * median(ref["grad_norms"].values())
    still = [k for k, v in ref["grad_norms"].items() if v < floor]
    change_gap, change_leaf = worst_leaf_gap(
        readings["change_norms"], ref["change_norms"], skip=still
    )
    return {
        "loss_gap": loss_gap, "grad_gap": grad_gap, "change_gap": change_gap,
        "_where": {"grad_leaf": grad_leaf, "change_leaf": change_leaf, "left_out": still},
    }


def reference_readings(ctx, spec, rows, precision: str = "float32", keep=None) -> dict:
    """The reference's losses, first gradient and change over the first steps,
    from the seed alone."""
    import jax.numpy as jnp

    reference = load_module("reference", ctx.config["reference"])
    opt = ctx.workload["optimizer"]
    batch = ctx.workload["traffic"]["batch_size"] * ctx.chips
    first = [rows[i * batch:(i + 1) * batch] for i in range(REFERENCE_STEPS)]
    out = reference.train(
        ctx.config, weights_lib.make_weights(spec, ctx.seed, jnp.float32), first, opt,
        precision=precision, keep=keep,
    )
    out["change_norms"] = weights_lib.distance_from_initial(
        spec, ctx.seed, jnp.float32, out.pop("weights")
    )
    free_device_memory()
    return out


def check(ctx, state: State) -> dict:
    ref = reference_readings(ctx, state.spec, state.rows)
    numbers = compare(state.readings, ref)
    numbers["_facts"] = {
        "program_losses": state.readings["losses"], "reference_losses": ref["losses"],
        "reference_grad_norm_unclipped": ref["grad_norm_unclipped"],
    }
    return numbers


def flops(ctx, result: dict) -> float:
    """Model FLOPs of the window's tokens, for ``mfu``."""
    per_token = count(ctx.config, "train_flops_per_token")(
        ctx.config, ctx.workload["traffic"]["seq_len"]
    )
    return per_token * result["tokens"]


def control_readings(ctx, state: State) -> dict:
    """For ``limits.py``: the reference put in the program's place and computed
    one precision below what the configuration states (the control), and with
    half of the batch left out of the mean (the planted fault), each compared
    with the float32 reference as a run compares the program."""
    ref = reference_readings(ctx, state.spec, state.rows)
    out = {"control_float8": compare(
        reference_readings(ctx, state.spec, state.rows, "float8"), ref)}
    tr = ctx.workload["traffic"]
    batch = tr["batch_size"] * ctx.chips
    keep = np.zeros((batch, tr["seq_len"] - 1), np.float32)
    if batch > 1:
        keep[: batch // 2] = 1.0  # half of the rows
    else:
        keep[:, : (tr["seq_len"] - 1) // 2] = 1.0  # one row: half of its tokens
    out["fault_half_batch"] = compare(
        reference_readings(ctx, state.spec, state.rows, keep=keep), ref
    )
    return out
