"""The tick's program against its roofline: the least time the chip
could take for one tick (the larger of needed operations over peak and
needed bytes over HBM bandwidth) over the device seconds of one run of
the program in the trace."""

from models.whisper_costs import least_seconds
from readers._program import run_and_cost


def read(ctx, **_):
    found = run_and_cost(ctx)
    if found is None:
        return None
    per_run, cost = found
    least, _bound = least_seconds(cost, ctx["peaks"])
    return 100.0 * least / per_run
