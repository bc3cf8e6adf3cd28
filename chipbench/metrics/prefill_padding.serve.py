"""Positions the prefill programs computed for nothing: 1 less the prompt tokens
(``prompt_len`` of ``engine.prefill``, ``chunk_len`` of ``engine.prefill_chunk``)
over the positions their programs ran (``bucket``), over the traced window."""

from chipbench import hostspans

METRIC = {"name": "prefill_padding.serve", "layer": "model step, prefill", "unit": "%",
          "moves": "serve_tokens_per_s", "source": "program_counter"}

LENGTH = {"engine.prefill": "prompt_len", "engine.prefill_chunk": "chunk_len"}


def read(run):
    real = computed = 0
    for sp in hostspans.session_spans() or []:
        if sp.name in LENGTH and sp.attrs.get("bucket"):
            real += sp.attrs[LENGTH[sp.name]]
            computed += sp.attrs["bucket"]
    return 100.0 * (1.0 - real / computed) if computed else None
