"""The share of the window's valid token-choice pairs that the router
sent to the experts this chip holds (``held_choices`` of the step
records, counted on the device: pairs on the held experts, all pairs,
over the expert layers): about the held share of the experts where the
routing is even. A program that records nothing of it gives ``None``."""


def read(ctx, **_):
    got = [r["held_choices"] for r in ctx.get("step_log") or ()
           if r.get("held_choices")]
    total = sum(a for _, a in got)
    if not total:
        return None
    return 100.0 * sum(h for h, _ in got) / total
