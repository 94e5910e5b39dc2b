"""The readers of one chip's share of an expert layer and of its latent
attention kernels (``mla_attention_roofline``, ``moe_gemm_roofline.held``,
``moe.held_rows_per_token``) on a synthetic trace of the Moonlight cell's
shapes, and their silence where they have nothing to read."""

import numpy as np
import pytest

from bench import flops, manifest, reduce, weights

CFG = manifest.config("moonlight-16b-a3b-5l-ep8")
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000
BATCH, SEQ = CFG["training"]["batch"], CFG["training"]["seq"]
TOKENS = BATCH * SEQ


def _ctx(cfg=CFG, loads=None, steps=2, chips=1):
    events = {
        "host": [["bench.window", 0, 1000 * MS]],
        "devices": {"0": [
            ["while.1", 0, 900 * MS, 0],
            ["splash_mha_fwd_residuals.25", 0, 100 * MS, 1],
            ["splash_mha_dkv_no_residuals.11", 100 * MS, 200 * MS, 1],
            ["ragged_gate_up_silu_f32.3", 300 * MS, 30 * MS, 1],
            ["ragged_dw_f32.4", 330 * MS, 10 * MS, 1],
            ["fusion.5", 340 * MS, 500 * MS, 1],
        ]},
    }
    return reduce.Ctx(events=events, steps=steps, cfg=cfg, chips=chips,
                      peak=PEAK, tokens_per_step=TOKENS,
                      flops_per_token=flops.model_flops_per_token(cfg, SEQ),
                      loads=loads if loads is not None else [])


def _loads(held_share):
    """(MoE layers, 64) counts: ``held_share`` of each token's 6 rows on
    experts 0-7, the rest spread evenly over the other 56."""
    rows = TOKENS * 6
    out = np.full((4, 64), rows * (1 - held_share) / 56)
    out[:, :8] = rows * held_share / 8
    return out


def test_held_rows_per_token_is_the_uniform_share():
    read = reduce.load_reader("moe.held_rows_per_token")
    assert read(_ctx(loads=[_loads(8 / 64)])) == pytest.approx(0.75)
    assert read(_ctx(loads=[_loads(8 / 64), _loads(0.25)])) == \
        pytest.approx((0.75 + 1.5) / 2)


def test_held_roofline_counts_the_held_experts_of_the_moe_layers():
    loads = [_loads(8 / 64), _loads(0.25)]
    got = reduce.load_reader("moe_gemm_roofline.held")(_ctx(loads=loads))
    least = 0.0
    for share in (8 / 64, 0.25):
        calls = flops.ragged_ffn_calls(TOKENS * 6 * share, 2048, 1408, 8)
        least += 4 * sum(flops.roofline_s(calls, 197e12, 819e9).values()) / 2
    spent = 40e-3 / 2  # two kernels, 40 ms over two steps
    assert got == pytest.approx(100 * least / spent)


def test_mla_roofline_is_the_core_least_time_over_the_splash_time():
    got = reduce.load_reader("mla_attention_roofline")(_ctx())
    calls = weights.family(CFG).mla_core_calls(CFG, BATCH, SEQ)
    least = sum(flops.roofline_s(calls, 197e12, 819e9).values())
    assert got == pytest.approx(100 * least / (300e-3 / 2))
    assert flops.bound_by(calls, 197e12, 819e9) == {"fwd": "flops",
                                                    "bwd": "flops"}


@pytest.mark.parametrize("name", ["mla_attention_roofline",
                                  "moe_gemm_roofline.held",
                                  "moe.held_rows_per_token"])
def test_readers_are_silent_without_their_inputs(name):
    read = reduce.load_reader(name)
    granite = manifest.config("granite-moe-3b-a800m-4l")
    assert read(_ctx(cfg=granite, loads=[np.ones((4, 40))])) is None
    assert read(_ctx(cfg=CFG, loads=[], steps=0)) is None
    if name != "moe.held_rows_per_token":
        assert read(_ctx(loads=[_loads(0.125)], chips=4)) is None
