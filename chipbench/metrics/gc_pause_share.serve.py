"""Share of the traced window in which Python's cyclic collector held the
interpreter, and so every thread of the server: the summed ``python.gc`` spans of
the session, on every thread, over the session's span of time, from the first
profiled span's start to the last one's end. Collections of generations 1 and 2 are
spans; a program whose tracer keeps no ``totals`` spans none, and reports nothing."""

from chipbench import hostspans

METRIC = {"name": "gc_pause_share.serve", "layer": "host runtime", "unit": "%",
          "moves": "serve_tokens_per_s", "source": "program_span"}


def read(run):
    spans = hostspans.session_spans()
    try:
        from accelerate_tpu import tracing

        spanned = hasattr(tracing.get_tracer(), "totals")
    except ImportError:
        spanned = False
    if not spans or not spanned:
        return None
    pauses = sum(sp.t1 - sp.t0 for sp in spans if sp.name == "python.gc")
    whole = max(sp.t1 for sp in spans) - min(sp.t0 for sp in spans)
    return 100.0 * pauses / whole if whole > 0 else None
