"""What JAX's pipeline took of the set-up: the seconds of the outermost
``jax.trace``, ``jax.lower`` and ``jax.compile`` spans (a persistent-cache fetch is
a ``jax.compile`` too) before the traced window began, from the program tracer's
totals as they stood when the session's first span opened. The window compiles
nothing (``run.py`` refuses that). A program without ``totals`` reports nothing."""

METRIC = {"name": "jax_compile_s.serve", "layer": "set-up", "unit": "s",
          "moves": "setup_s", "source": "program_counter"}

PHASES = ("jax.trace", "jax.lower", "jax.compile")


def read(run):
    try:
        from accelerate_tpu import tracing

        totals = tracing.get_tracer().totals(at_session_start=True)
    except (ImportError, AttributeError):
        return None
    if not totals:
        return None
    return sum(totals[phase]["seconds"] for phase in PHASES)
