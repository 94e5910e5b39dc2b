"""What the yardstick takes from a model family's own file, on a stand-in
hybrid family built here (not a file under ``bench/configs``, so the
config-file tests do not pick it up): four blocks in the shape of one
period of a Mamba-2/MoE hybrid, (mamba, moe), (mamba, moe), (mamba, none),
(attention, moe), so one block attends and one has no FFN norm, with
ungated (two-matrix) experts."""

import types

import numpy as np
import pytest

from bench import flops, reduce, weights

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
BLOCKS = (("mamba", "moe"), ("mamba", "moe"), ("mamba", None),
          ("attention", "moe"))
CFG = {"model_type": "standin_hybrid", "hidden_size": 256, "heads": 8,
       "head_dim": 32, "window": None, "mamba_inner": 512, "experts": 8,
       "top_k": 2, "expert_width": 96, "held": 4, "vocab_size": 1000}


def _dims(cfg):
    return {"d": cfg["hidden_size"], "L": len(BLOCKS), "H": cfg["heads"],
            "hd": cfg["head_dim"], "E": cfg["experts"], "k": cfg["top_k"],
            "f": cfg["expert_width"], "held": cfg["held"], "first": 0,
            "gated": False}


def _active_matmul_params(cfg):
    n = _dims(cfg)
    d, di = n["d"], cfg["mamba_inner"]
    per = {"mamba": 3 * d * di, "attention": 4 * d * n["H"] * n["hd"],
           "moe": d * n["E"] + n["k"] * 2 * d * n["f"], None: 0}
    return sum(per[m] + per[f] for m, f in BLOCKS) + cfg["vocab_size"] * d


def _attention_flops_per_token(cfg, seq):
    return flops.attention_term(
        [(cfg["heads"], cfg["head_dim"], cfg["window"])
         for mixer, _ in BLOCKS if mixer == "attention"], seq)


def _norm_params(cfg):
    """A pre-norm per block and per FFN, the final norm, and a gated norm
    of the inner width in each Mamba mixer."""
    d = cfg["hidden_size"]
    return (sum(d + (d if f else 0) for _, f in BLOCKS) + d
            + sum(cfg["mamba_inner"] for m, _ in BLOCKS if m == "mamba"))


FAMILY = types.SimpleNamespace(
    dims=_dims, active_matmul_params=_active_matmul_params,
    attention_flops_per_token=_attention_flops_per_token,
    norm_params=_norm_params)
# The same family without its attention term: every block would attend.
BARE = types.SimpleNamespace(dims=_dims,
                             active_matmul_params=_active_matmul_params)
FAMILIES = {"standin_hybrid": FAMILY, "standin_bare": BARE}


@pytest.fixture(autouse=True)
def standin(monkeypatch):
    found = weights.family
    monkeypatch.setattr(weights, "family", lambda cfg: FAMILIES.get(
        cfg["model_type"]) or found(cfg))


def test_attention_term_counts_only_the_attending_block():
    seq = 4096
    one = 12 * 8 * 32 * seq
    assert flops.attention_flops_per_token(CFG, seq) == one
    assert flops.model_flops_per_token(CFG, seq) == (
        6.0 * _active_matmul_params(CFG) + one)
    bare = {**CFG, "model_type": "standin_bare"}
    assert flops.attention_flops_per_token(bare, seq) == 4 * one


@pytest.mark.parametrize("window,seq,keys", [(1024, 4096, 1024),
                                             (1024, 512, 512),
                                             (None, 4096, 4096)])
def test_a_windowed_block_counts_min_window_seq_keys(window, seq, keys):
    cfg = {**CFG, "window": window}
    assert flops.attention_flops_per_token(cfg, seq) == 12 * 8 * 32 * keys
    assert flops.attention_term([(8, 32, window), (4, 64, None)], seq) == (
        12 * 8 * 32 * keys + 12 * 4 * 64 * seq)


def test_norm_scales_come_from_the_family():
    d, L = 256, 4
    want = 4 * d + 3 * d + d + 3 * 512  # block, FFN, final, Mamba norms
    assert flops.norm_params(CFG) == want != (2 * L + 1) * d


def test_ungated_calls_have_six_kinds_and_their_flops():
    rows, d, f, e = 3000.0, 256, 96, 4
    calls = flops.ragged_ffn_calls(rows, d, f, e, gated=False)
    kinds = [k for k, _, _ in calls]
    assert kinds == ["up", "down", "dh", "dx_up", "dw_down", "dw_up"]
    fl = {k: v for k, v, _ in calls}
    fwd = fl["up"] + fl["down"]
    assert fwd == 2 * 2 * rows * d * f
    assert sum(fl.values()) - fwd == 2 * fwd
    by = {k: v for k, _, v in calls}
    w = 2 * e * d * f
    assert by["up"] == rows * d * 2 + w + 2 * rows * f * 4
    gated = {k: v for k, _, v in flops.ragged_ffn_calls(rows, d, f, e)}
    for kind in ("down", "dh", "dx_up", "dw_down", "dw_up"):
        assert by[kind] == gated[kind]


def test_held_roofline_counts_ungated_calls_for_an_ungated_family():
    ms = 1_000_000
    events = {"host": [["bench.window", 0, 100 * ms]],
              "devices": {"0": [["ragged_matmul_f32.1", 0, 10 * ms, 1]]}}
    load = np.full((3, 8), 4096 * 2 / 8)  # the 3 MoE blocks, 8 experts
    ctx = reduce.Ctx(events=events, steps=1, cfg=CFG, chips=1, peak=PEAK,
                     tokens_per_step=4096, flops_per_token=1.0, loads=[load])
    got = reduce.load_reader("moe_gemm_roofline.held")(ctx)
    least = 3 * sum(flops.roofline_s(flops.ragged_ffn_calls(
        4 * 1024.0, 256, 96, 4, gated=False), 197e12, 819e9).values())
    assert got == pytest.approx(100 * least / 10e-3)
    gated = 3 * sum(flops.roofline_s(flops.ragged_ffn_calls(
        4 * 1024.0, 256, 96, 4), 197e12, 819e9).values())
    assert got < 100 * gated / 10e-3
