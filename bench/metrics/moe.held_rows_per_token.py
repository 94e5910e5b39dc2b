"""Rows routed to the experts held on one chip's share of a layer, per
token: the mean over the MoE layers and the traced steps of the step's
``expert_load`` over the family's held experts, over the step's tokens.
Top-k x held / routed experts under uniform load (0.75 for 6 of 64
experts with 8 held)."""

from bench import weights


def read(ctx):
    n = weights.family(ctx.cfg).dims(ctx.cfg)
    if "held" not in n or not ctx.loads or not ctx.tokens_per_step:
        return None
    held = slice(n["first"], n["first"] + n["held"])
    per = [float(load[:, held].sum(axis=1).mean()) for load in ctx.loads]
    return sum(per) / len(per) / ctx.tokens_per_step
