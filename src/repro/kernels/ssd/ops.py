"""Public wrapper: (b, nc, ...) <-> (b*nc, ...) layout + interpret switch."""

from __future__ import annotations

from typing import Optional

from repro.kernels import interpret_mode
from repro.kernels.ssd import ssd


def ssd_intra_chunk(xc, dAc, Bc, Cc, *, interpret: Optional[bool] = None):
    """xc: (b, nc, cl, h, p); dAc: (b, nc, cl, h); Bc/Cc: (b, nc, cl, h, n).
    Returns the intra-chunk output (b, nc, cl, h, p)."""
    interpret = interpret_mode() if interpret is None else interpret
    b, nc, cl, h, p = xc.shape
    fold = lambda t: t.reshape((b * nc,) + t.shape[2:])
    y = ssd.ssd_intra_chunk(
        fold(xc), fold(dAc.astype(xc.dtype)), fold(Bc), fold(Cc),
        interpret=interpret,
    )
    return y.reshape(b, nc, cl, h, p)
