"""Share of the ragged grouped-GEMM kernels' device time that their
roofline accounts for, in %, for the experts held on one chip's share of
a layer (the family's ``held`` experts from ``first``).

Per step, the rows routed to the held experts come from the step's
``expert_load`` (MoE layers x every routed expert), over the MoE layers
only; ``bench/flops.py`` turns them into the needed calls' operations and
bytes, each call's least time max(operations / bf16 peak, bytes / HBM
bandwidth).  The share is the summed least time over the measured kernel
time.  One chip only."""

from bench import flops, weights


def read(ctx):
    n = weights.family(ctx.cfg).dims(ctx.cfg)
    if ("held" not in n or ctx.chips != 1 or not ctx.loads or not ctx.steps
            or not ctx.peak):
        return None
    spent = sum(d for dev in ctx.device_ids()
                for _, _, d in ctx.kernel_ops(dev)) * 1e-9 / ctx.steps
    if not spent:
        return None
    held = slice(n["first"], n["first"] + n["held"])
    least = 0.0
    for load in ctx.loads:
        for layer in load:
            calls = flops.ragged_ffn_calls(float(layer[held].sum()), n["d"],
                                           n["f"], n["held"])
            least += sum(flops.roofline_s(
                calls, ctx.peak["bf16_flops"],
                ctx.peak["hbm_bytes_per_s"]).values()) / len(ctx.loads)
    return 100.0 * least / spent
