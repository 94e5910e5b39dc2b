"""Share of the ragged grouped-GEMM kernels' device time that their
roofline accounts for, in %.

Per device and step, the rows routed to that device's experts come from
the step's ``expert_load`` (tokens x top-k per expert and layer), over the
layers of the device's pipeline stage.  ``bench/flops.py`` turns them into
the needed calls' operations and bytes (gated experts unless the
family's ``dims()`` says ``gated`` False); each call's least time is
max(operations / bf16 peak, bytes / HBM bandwidth).  The share is the sum
of least times over the sum of measured kernel times, over all devices.
"""

from bench import flops, weights


def read(ctx):
    if not ctx.loads or not ctx.steps or not ctx.peak:
        return None
    n = weights.family(ctx.cfg).dims(ctx.cfg)
    e_l = n["E"] // ctx.ep
    lps = n["L"] // ctx.pp
    least = measured = 0.0
    kinds = {}
    for dev in ctx.device_ids():
        spent = sum(d for _, _, d in ctx.kernel_ops(dev)) * 1e-9 / ctx.steps
        if not spent:
            continue
        stage, rank = ctx.coords.get(int(dev), (0, 0))
        for load in ctx.loads:
            for layer in range(stage * lps, (stage + 1) * lps):
                rows = float(load[layer, rank * e_l:(rank + 1) * e_l].sum())
                calls = flops.ragged_ffn_calls(rows, n["d"], n["f"], e_l,
                                               n.get("gated", True))
                t = flops.roofline_s(calls, ctx.peak["bf16_flops"],
                                     ctx.peak["hbm_bytes_per_s"])
                least += sum(t.values()) / len(ctx.loads)
                kinds.update(flops.bound_by(calls, ctx.peak["bf16_flops"],
                                            ctx.peak["hbm_bytes_per_s"]))
        measured += spent
    if not measured:
        return None
    print("[roofline] moe_gemm calls bound by: "
          + " ".join(f"{k}={v}" for k, v in sorted(kinds.items())))
    return 100.0 * least / measured
