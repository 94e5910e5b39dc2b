"""The whole train step's share of the chips' bf16 peak while the devices
run it, in %: model FLOPs (``bench/flops.py``, recomputation not counted)
of the traced steps over the devices' busy time in the traced window
(the union of each device's op intervals, summed over the chips) x peak.

The host clock does not enter: the gaps in which a device waits for the
host count against ``mfu`` and ``device_idle_pct``, not against this.  A
kernel taken off the path leaves its roofline silent; this still bounds
the step it sped up."""

from bench import trace


def read(ctx):
    devs = ctx.device_ids()
    if not ctx.steps or not ctx.peak or not devs or ctx.window is None:
        return None
    busy_s = sum(trace.length(ctx.busy(d)) for d in devs) * 1e-9
    if not busy_s:
        return None
    done = ctx.flops_per_token * ctx.tokens_per_step * ctx.steps
    return 100.0 * done / (busy_s * ctx.peak["bf16_flops"])
