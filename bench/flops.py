"""Operation and byte counts, from the configuration's published widths.

``model_flops_per_token`` counts what training needs per token as in PaLM
(Chowdhery et al. 2022, App. B): ``6 N`` for the N active matmul
parameters (the model family's ``active_matmul_params``: for granitemoe
the attention projections, router, the top-k experts and the tied head as
a matmul; the embedding gather is no matmul) plus
``12 L (heads x head_dim) seq`` for attention.  Recomputation is not
counted.

``ragged_ffn_calls`` lists the ragged grouped-GEMM kernel calls
(``kernels/moe_gemm``) that one MoE layer needs per step for ``rows``
routed (token, expert) rows on one device: the forward (fused gate-up-SiLU,
down projection) and the backward (three row GEMMs, three weight-gradient
GEMMs).  Rematerialised forwards are not counted: they are work the
algorithm does not need.  Bytes are the least traffic of each call: its
operands and results once, in the dtypes the kernels use (bf16 token rows
and weights, fp32 activations, cotangents and results).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from bench import weights


def active_matmul_params(cfg: Dict) -> int:
    return weights.family(cfg).active_matmul_params(cfg)


def model_flops_per_token(cfg: Dict, seq: int) -> float:
    n = weights.family(cfg).dims(cfg)
    attn = 12 * n["L"] * n["H"] * n["hd"] * seq
    return 6.0 * active_matmul_params(cfg) + attn


def ragged_ffn_calls(rows: float, d: int, f: int,
                     experts_here: int) -> List[Tuple[str, float, float]]:
    """``[(kind, flops, bytes)]`` for one layer's needed kernel calls."""
    r, w2 = float(rows), 2.0 * experts_here * d * f  # bf16 weight bytes
    mm = 2 * r * d * f
    return [
        ("gate_up", 2 * mm, r * d * 2 + 2 * w2 + 3 * r * f * 4),
        ("down", mm, r * f * 4 + w2 + r * d * 4),
        ("dh", mm, r * d * 4 + w2 + r * f * 4),
        ("dx_gate", mm, r * f * 4 + w2 + r * d * 4),
        ("dx_up", mm, r * f * 4 + w2 + r * d * 4),
        ("dw_down", mm, r * f * 4 + r * d * 4 + 2 * w2),
        ("dw_gate", mm, r * d * 2 + r * f * 4 + 2 * w2),
        ("dw_up", mm, r * d * 2 + r * f * 4 + 2 * w2),
    ]


def roofline_s(calls, peak_flops: float, peak_bw: float) -> Dict[str, float]:
    """Least time per call kind: max(flops / peak, bytes / bandwidth)."""
    out: Dict[str, float] = {}
    for kind, fl, by in calls:
        out[kind] = out.get(kind, 0.0) + max(fl / peak_flops, by / peak_bw)
    return out


def bound_by(calls, peak_flops: float, peak_bw: float) -> Dict[str, str]:
    return {kind: ("flops" if fl / peak_flops >= by / peak_bw else "bytes")
            for kind, fl, by in calls}
