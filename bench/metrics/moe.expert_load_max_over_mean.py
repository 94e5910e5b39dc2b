"""Largest per-expert routed-row count over the mean, per MoE layer,
averaged over the layers and the traced steps (the ``expert_load`` counts
the step returns to ``Trainer.fit``)."""


def read(ctx):
    if not ctx.loads:
        return None
    vals = [float((l.max(axis=1) / l.mean(axis=1)).mean()) for l in ctx.loads]
    return sum(vals) / len(vals)
