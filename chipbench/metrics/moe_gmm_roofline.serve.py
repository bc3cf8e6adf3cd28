"""The expert layers' grouped matmuls' share of their roofline, where the program
computes them with its own Pallas kernel: the least time to move what they have to
move (``moe_expert_bytes`` of the configuration's counts file: the three matrices of
every expert that got a row, as the program's own counters say, and the rows'
activations; over the HBM peak: bytes bind, an expert sees a handful of rows a step)
over the summed device time of those matmuls in the traced window. The kernel is
``accelerate_tpu/ops/grouped_matmul.py``'s, three calls an expert layer; the chip's
trace shows each under the name its ``pallas_call`` carries, ``moe_gmm``, with the
compiler's numbering behind it. The counters are the ``moe_experts_touched`` and
``moe_assignments`` that the engine puts on ``engine.readback`` spans, decode steps
and prefills alike: the same as ``moe_experts_roofline.serve`` reads, which matches
what the compiler makes of ``jax.lax.ragged_dot`` and reports nothing where this one
reports. A program without the kernel gives nothing here."""

from chipbench import hostspans, lib, trace

METRIC = {"name": "moe_gmm_roofline.serve", "layer": "expert layer", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "device_trace"}

KERNEL = r"^%?moe_gmm[.\d]* = "


def counted(spans) -> tuple:
    """``(experts touched, rows)`` summed over the spans that carry the counters."""
    steps = [sp.attrs for sp in spans or [] if sp.attrs.get("moe_expert_slots")]
    return (sum(a["moe_experts_touched"] for a in steps),
            sum(a["moe_assignments"] for a in steps))


def read(run):
    seconds, _ = trace.time_matching(run.summary, KERNEL)
    touched, rows = counted(hostspans.session_spans("engine.readback"))
    moved = lib.find_count(run.ctx.config, "moe_expert_bytes")
    if not seconds or not touched or moved is None:
        return None
    least = moved(run.ctx.config, touched, rows) / run.ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
