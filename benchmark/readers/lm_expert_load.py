"""How uneven the routing is: per step and expert layer the fullest
expert's tokens over the mean tokens an expert (both counted on the
device over valid tokens, read out with the step's tokens), mean over
layers and steps. 1 is a perfectly even split."""


def read(ctx, **_):
    experts = (ctx.get("model") or {}).get("num_experts")
    ratios = [mx * experts / total
              for r in ctx.get("step_log") or ()
              for mx, total, *_ in r.get("expert_load") or () if total]
    if not ratios or not experts:
        return None
    return sum(ratios) / len(ratios)
