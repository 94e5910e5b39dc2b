"""Device time of the ragged grouped-GEMM kernels (``kernels/moe_gemm``)
per traced step, in ms, the mean over the chips the cell uses."""


def read(ctx):
    per = [sum(d for _, _, d in ctx.kernel_ops(dev))
           for dev in ctx.device_ids()]
    if not per or not any(per) or not ctx.steps:
        return None
    return sum(per) / len(per) * 1e-6 / ctx.steps
