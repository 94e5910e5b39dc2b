"""CPU checks of the benchmark's yardstick: FLOP and byte counts, traffic,
the manifest's contract, and the trace arithmetic."""

import json
import re

import numpy as np
import pytest

from bench import flops, gen, manifest, reduce, trace, weights
from bench.tests.tiny import tiny

MAN = manifest.load()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CONFIGS = [c["name"] for c in MAN["configs"]]
CONFIG_FILES = sorted(p.stem for p in (manifest.BENCH / "configs").glob(
    "*.json"))


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_flop_counter_matches_active_params(name):
    from repro.configs import get_arch

    cfg = manifest.config(name)
    n = weights.family(cfg).dims(cfg)
    arch = get_arch(cfg["program"]["arch"]).replace(num_layers=n["L"])
    # active_params counts every norm scale; matmul parameters do not.
    norms = (2 * n["L"] + 1) * n["d"]
    assert flops.active_matmul_params(cfg) == arch.active_params() - norms


def test_model_flops_per_token_at_granite_4l():
    cfg = manifest.config("granite-moe-3b-a800m-4l")
    n_active = flops.active_matmul_params(cfg)
    assert n_active == 176_411_136
    attn = 12 * 4 * 24 * 64 * 4096
    assert flops.model_flops_per_token(cfg, 4096) == 6 * n_active + attn
    assert 1.35e9 < flops.model_flops_per_token(cfg, 4096) < 1.37e9


def test_ragged_ffn_calls_at_granite_widths():
    rows, d, f, e = 4 * 4096 * 8, 1536, 512, 40
    calls = {k: (fl, by) for k, fl, by in flops.ragged_ffn_calls(rows, d, f,
                                                                 e)}
    assert set(calls) == {"gate_up", "down", "dh", "dx_gate", "dx_up",
                          "dw_down", "dw_gate", "dw_up"}
    mm = 2 * rows * d * f
    assert calls["gate_up"][0] == 2 * mm
    assert sum(fl for fl, _ in calls.values()) == 9 * mm
    w = 2 * e * d * f
    assert calls["gate_up"][1] == rows * d * 2 + 2 * w + 3 * rows * f * 4
    assert calls["down"][1] == rows * f * 4 + w + rows * d * 4
    bound = flops.bound_by(flops.ragged_ffn_calls(rows, d, f, e), 197e12,
                           819e9)
    assert bound["gate_up"] == "flops" and bound["down"] == "bytes"
    t = flops.roofline_s(flops.ragged_ffn_calls(rows, d, f, e), 197e12,
                         819e9)
    assert t["gate_up"] == pytest.approx(2 * mm / 197e12)
    assert t["down"] == pytest.approx(calls["down"][1] / 819e9)


@pytest.mark.parametrize("mix", ["zipf-topics", "uniform"])
def test_traffic_is_a_function_of_seed_and_step(mix):
    m = manifest.traffic(mix)
    a = gen.TokenStream(m, 49155, 4, 4096, 2**31 + 11)
    b = gen.TokenStream(m, 49155, 4, 4096, 2**31 + 11)
    c = gen.TokenStream(m, 49155, 4, 4096, 2**31 + 12)
    x, y = a.batch_at(3), b.batch_at(3)
    assert x["tokens"].shape == (4, 4096) and x["tokens"].dtype == np.int32
    np.testing.assert_array_equal(x["tokens"], y["tokens"])
    np.testing.assert_array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
    assert not np.array_equal(x["tokens"], c.batch_at(3)["tokens"])
    assert not np.array_equal(x["tokens"], a.batch_at(4)["tokens"])
    assert x["tokens"].min() >= 0 and x["tokens"].max() < 49155


@pytest.mark.parametrize("mix,lo,hi", [("zipf-topics", 0.35, 1.0),
                                        ("uniform", 0.0, 0.1)])
def test_traffic_skew(mix, lo, hi):
    """Share of a batch's tokens taken by its 1% most frequent ids: about
    0.44 for Zipf (s = 1) across 64 topics, 0.077 for uniform tokens."""
    s = gen.TokenStream(manifest.traffic(mix), 49155, 4, 4096, 7)
    toks = s.batch_at(0)["tokens"].ravel()
    top = np.sort(np.bincount(toks, minlength=49155))[::-1][:492]
    assert lo < top.sum() / toks.size < hi


def test_manifest_names_and_units():
    for key in ("end_to_end", "per_layer", "workloads", "configs"):
        names = [e["name"] for e in MAN[key]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert {"setup_s", "tokens_per_s", "mfu"} <= {m["name"] for m in
                                                  MAN["end_to_end"]}


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    for w in MAN["workloads"]:
        reported = {m["name"] for m in
                    manifest.metrics_of(MAN, w["name"], "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        per_layer = manifest.metrics_of(MAN, w["name"], "per_layer")
        assert per_layer
        for m in per_layer:
            assert m["moves"] in e2e and m["moves"] in reported


def test_every_entry_names_files_that_exist():
    bench = manifest.BENCH
    for c in MAN["configs"]:
        path = manifest.ROOT / c["file"]
        assert path.is_file() and path.parent == bench / "configs"
        cfg = json.loads(path.read_text())
        assert set(c["reduced"]) <= set(cfg) and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert (bench / "models" / f"{cfg['model_type']}.py").is_file()
    for w in MAN["workloads"]:
        assert w["config"] in CONFIGS
        assert (bench / "traffic" / f"{w['traffic']}.json").is_file()
        assert (bench / "limits" / f"{w['name']}.json").is_file()
        assert w["chips"] in (1, 4)
    cells = {w["name"] for w in MAN["workloads"]}
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for m in MAN["per_layer"]:
        assert (bench / "metrics" / f"{m['name']}.py").is_file()
    for p in MAN["paths"]:
        assert (manifest.ROOT / p).is_dir()
    assert MAN["command"][1].startswith(MAN["paths"][0] + "/")


def test_run_seconds_fit_a_full_check():
    runs = 2 + 14 * 24
    total = runs * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_leaves_are_ops_that_hold_no_other_op():
    evs = [["while.1", 0, 100], ["fusion.2", 10, 20], ["fusion.3", 30, 5],
           ["copy.4", 100, 3], ["fusion.5", 30, 2]]
    got = {e[0]: e[3] for e in trace.mark_leaves(evs)}
    assert got == {"while.1": 0, "fusion.2": 1, "fusion.3": 0, "copy.4": 1,
                   "fusion.5": 1}
    assert trace.short_name("%fusion.7 = bf16[4]{0} fusion(%p.1)") == \
        "fusion.7"


def test_interval_arithmetic():
    u = trace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert trace.length(u) == 7
    assert trace.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert trace.subtract([(0, 10)], u) == [(3, 5), (9, 10)]
    assert trace.gaps(u, 0, 12) == [(3, 5), (9, 12)]


def _ctx(events, steps=2, loads=None):
    cfg = manifest.config("granite-moe-3b-a800m-4l")
    return reduce.Ctx(events=events, steps=steps, cfg=cfg, chips=1,
                      peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                      tokens_per_step=16384,
                      flops_per_token=flops.model_flops_per_token(cfg, 4096),
                      loads=loads or [], host_fetches=5)


def test_readers_on_a_synthetic_trace():
    ms = 1_000_000
    events = {
        "host": [["bench.window", 0, 100 * ms], ["bench.data", 40 * ms, 5]],
        "devices": {"0": [
            ["while.1", 0, 40 * ms, 0],
            ["fusion.1", 0, 30 * ms, 1],
            ["ragged_matmul_f32.2", 30 * ms, 10 * ms, 1],
            ["all-to-all.2", 45 * ms, 10 * ms, 1],
            ["fusion.3", 50 * ms, 40 * ms, 1],
        ]},
    }
    loads = [np.full((4, 40), 16384 * 8 / 40)]
    ctx = _ctx(events, loads=loads)
    read = reduce.load_reader
    assert read("device_idle_pct")(ctx) == pytest.approx(15.0)
    assert read("moe_gemm.ms_per_step")(ctx) == pytest.approx(5.0)
    assert read("trainer.host_syncs_per_step")(ctx) == pytest.approx(2.5)
    assert read("moe.expert_load_max_over_mean")(ctx) == pytest.approx(1.0)
    least = 4 * sum(flops.roofline_s(
        flops.ragged_ffn_calls(16384 * 8, 1536, 512, 40), 197e12,
        819e9).values())
    assert read("moe_gemm_roofline")(ctx) == pytest.approx(
        100 * least / 5e-3)
    # Busy 85 of the window's 100 ms: the host gaps do not count.
    assert read("step_mfu")(ctx) == pytest.approx(
        100 * ctx.flops_per_token * 16384 * 2 / (0.085 * 197e12))
    b = reduce.breakdown(ctx)
    assert b["device_ops"][0] == ["fusion.3", pytest.approx(0.04)]
    assert b["idle_gaps"] == [["trainer loop", pytest.approx(0.01)],
                              ["bench.data", pytest.approx(0.005)]]


def test_readers_return_nothing_without_a_trace():
    ctx = _ctx({"host": [], "devices": {}}, steps=0)
    for m in MAN["per_layer"]:
        if m["source"] == "device_trace":
            assert reduce.load_reader(m["name"])(ctx) is None


def test_recorded_trace_reduces_to_known_values():
    """Three steps of granite4l-zipf traced on one TPU v5 lite
    (``bench/testdata``), reduced by the readers the benchmark runs."""
    rec = trace.load(str(manifest.BENCH / "testdata"
                         / "granite4l-zipf.trace.json.gz"))
    ctx = _ctx(rec, steps=rec["steps"],
               loads=[np.asarray(x) for x in rec["loads"]])
    read = reduce.load_reader
    assert read("device_idle_pct")(ctx) == pytest.approx(0.4812368254354493)
    assert read("moe_gemm.ms_per_step")(ctx) == pytest.approx(
        335.0977983333333)
    assert read("moe_gemm_roofline")(ctx) == pytest.approx(
        13.371703362630065)
    assert read("step_mfu")(ctx) == pytest.approx(8.448938239863582)
    assert ctx.window_s == pytest.approx(4.036942722, rel=1e-6)


def test_model_family_is_found_by_model_type():
    fam = weights.family(manifest.config("granite-moe-3b-a800m-4l"))
    assert fam.__name__ == "bench_model_granitemoe"
    assert set(weights.shapes(tiny())) == set(fam.NAMES)
    with pytest.raises(KeyError, match="bench/models/nosuchfamily.py"):
        weights.family({"model_type": "nosuchfamily"})


def test_flat_layout_round_trips_through_the_program_tree():
    fam = weights.family(tiny())
    flat = {n: np.full(2, i) for i, n in enumerate(fam.NAMES)}
    like = {"blocks": ({"ffn": {"replicas": "kept"}},)}
    tree = fam.from_flat(flat, like)
    assert tree["blocks"][0]["ffn"]["replicas"] == "kept"
    assert fam.to_flat(tree) == flat


@pytest.mark.parametrize("key,value", [("embedding_multiplier", 2.0),
                                       ("residual_multiplier", 0.5),
                                       ("attention_multiplier", 0.5),
                                       ("logits_scaling", 3.0)])
def test_reference_applies_the_configured_multipliers(key, value):
    import jax

    from bench import reference

    def loss(cfg):
        s = gen.TokenStream(manifest.traffic("zipf-topics"),
                            cfg["vocab_size"], 2, 64, 5)
        b = s.batch_at(0)
        p = weights.make(cfg, jax.random.key(5))
        dot, dot_w = reference._dots("highest")
        return float(weights.family(cfg).loss_fn(
            p, b["tokens"], b["labels"], cfg, dot, dot_w)[1][0])

    cfg = tiny()
    changed = {**cfg, key: value}
    assert abs(loss(changed) - loss(cfg)) > 1e-4
