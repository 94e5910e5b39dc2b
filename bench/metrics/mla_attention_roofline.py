"""Share of the flash attention kernels' device time that the causal MLA
core's roofline accounts for, in %.

The kernels are JAX's splash kernels (ops named ``splash_mha_*``: the
forward, run again in the layer's recompute, and the fused backward).  The
least time is, per call kind of the family's ``mla_core_calls`` (the
forward and the fused backward over all layers, q/k heads of 192 and v
heads of 128, no recomputation), max(operations / bf16 peak, bytes / HBM
bandwidth).  One chip only: the share of the core each chip of a mesh
runs is not modelled here."""

import re

from bench import flops, weights

SPLASH = re.compile(r"^splash_mha_")


def read(ctx):
    fam = weights.family(ctx.cfg)
    if (not hasattr(fam, "mla_core_calls") or ctx.chips != 1
            or not ctx.steps or not ctx.peak):
        return None
    spent = sum(d for dev in ctx.device_ids()
                for _, _, d in ctx.kernel_ops(dev, SPLASH))
    if not spent:
        return None
    tr = ctx.cfg["training"]
    calls = fam.mla_core_calls(ctx.cfg, tr["batch"], tr["seq"])
    least = sum(flops.roofline_s(calls, ctx.peak["bf16_flops"],
                                 ctx.peak["hbm_bytes_per_s"]).values())
    print("[roofline] MLA core calls bound by: " + " ".join(
        f"{k}={v}" for k, v in sorted(flops.bound_by(
            calls, ctx.peak["bf16_flops"],
            ctx.peak["hbm_bytes_per_s"]).items())))
    return 100.0 * least / (spent * 1e-9 / ctx.steps)
