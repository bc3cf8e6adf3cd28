"""The expert layers' grouped matmuls' share of their roofline: the least time to
move what they have to move (``moe_expert_bytes`` of the configuration's counts
file: the three matrices of every expert that got a row, as the program's own
counters say, and the rows' activations; over the HBM peak: bytes bind, an expert
sees a handful of rows a step) over the summed device time of those matmuls in the
traced window. The program computes them with ``jax.lax.ragged_dot``, which the
chip's compiler turns into one Mosaic kernel a matmul: the custom call is named
``ragged-dot-none`` with the compiler's numbering behind it (its small companion
``ragged-dot-metadata``, which lays the groups out, is none of this metric's).
The counters are the ``moe_experts_touched`` and ``moe_assignments`` that the
engine puts on ``engine.readback`` spans, decode steps and prefills alike."""

from chipbench import hostspans, lib, trace

METRIC = {"name": "moe_experts_roofline.serve", "layer": "expert layer", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "device_trace"}

KERNEL = r"^%?ragged-dot(?!-metadata)[\w.\-]* = "


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
