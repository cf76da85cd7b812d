"""Whole-tick model FLOP/s utilisation: the operations one tick's
algorithm needs (``models/whisper_costs.py``, from shapes, for the
windows an average tick of this run served) over the device seconds of
one run of the tick's program in the trace, over the chip's peak."""

from readers._program import run_and_cost


def read(ctx, **_):
    found = run_and_cost(ctx)
    if found is None:
        return None
    per_run, cost = found
    return 100.0 * cost["flops"] / per_run / ctx["peaks"]["flops_per_s"]
