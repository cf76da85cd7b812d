"""A kernel's share of its roofline in the ``qwen3next_engine`` cell:
the least seconds the chip could take for ``part`` (``gdn_chunk``,
``gdn_rows``, ``attn`` or ``experts`` of ``qwen3next_costs.step_cost``:
the larger of needed operations over peak and needed bytes over HBM
bandwidth, per step) summed over the steps inside the traced stretch,
over the device seconds the capture books to the named ``scopes``. A
program without the scopes (or without the family) gives ``None``."""

from models.qwen3next_costs import least_seconds, record_cost


def read(ctx, part, scopes, **_):
    steps, scope_s, peaks = (ctx.get("trace_steps"), ctx.get("scope_s"),
                             ctx.get("peaks"))
    if not steps or not scope_s or not peaks:
        return None
    took = sum(scope_s.get(s, 0.0) for s in scopes)
    if took <= 0:
        return None
    least = sum(least_seconds(record_cost(ctx["model"], r)["parts"][part],
                              peaks)[0] for r in steps)
    return 100.0 * least / took
