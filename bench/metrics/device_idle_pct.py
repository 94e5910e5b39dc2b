"""Device idle share of the traced window: 1 - (union of the device's op
intervals) / window, in %, the mean over the chips the cell uses."""

from bench import trace


def read(ctx):
    w, devs = ctx.window, ctx.device_ids()
    if w is None or not devs:
        return None
    span = w[1] - w[0]
    shares = [100.0 * (1.0 - trace.length(ctx.busy(d)) / span) for d in devs]
    if len(shares) > 1:
        print("[trace] device idle %: " + " ".join(
            f"{d}={s:.4f}" for d, s in zip(devs, shares)))
    return sum(shares) / len(shares)
