"""Whole-step model FLOP/s utilisation of the ``qwen3next_engine`` cell:
the operations the steps inside the traced stretch need
(``models/qwen3next_costs.py``, from each step's own record: the
DeltaNet chunkwise for the chunk and recurrent for the rows, the gated
attention, the held experts' pairs, the projections and the head) over
the seconds in which a device operation ran there, over the chip's
peak."""

from models.qwen3next_costs import record_cost


def read(ctx, **_):
    steps, trace, peaks = (ctx.get("trace_steps"), ctx.get("trace"),
                           ctx.get("peaks"))
    if not steps or not trace or not peaks or trace["busy_s"] <= 0:
        return None
    flops = sum(record_cost(ctx["model"], r)["flops"] for r in steps)
    return 100.0 * flops / trace["busy_s"] / peaks["flops_per_s"]
