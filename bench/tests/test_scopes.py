"""The program's own names in a traced run (``bench/scopes.py``): the op_name
map of the compiled step, the scope and pass of each op, the host spans on
the profiler's clock, and the readers built on them."""

from pathlib import Path

import numpy as np
import pytest

from bench import flops, manifest, reduce, scopes, trace
from bench.tests.tiny import tiny

TESTDATA = manifest.BENCH / "testdata"

HLO = """HloModule m, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/jvp(attention)/add" source_file="a.py" source_line=3}
}

%cond.1 (c: (s32[], f32[4])) -> pred[] {
  %c = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(false)
}

%body.2 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %gte.3 = f32[4]{0} get-tuple-element(%p), index=1
  %copy.4 = f32[4]{0} copy(%gte.3)
  %gte.5 = s32[] get-tuple-element(%p), index=0
  ROOT %tuple.6 = (s32[], f32[4]{0}) tuple(%gte.5, %copy.4)
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %copy.2 = f32[4]{0} copy(%fusion.1)
  %zero = s32[] constant(0)
  %t = (s32[], f32[4]{0}) tuple(%zero, %copy.2)
  %while.3 = (s32[], f32[4]{0}) while(%t), condition=%cond.1, body=%body.2, metadata={op_name="jit(f)/jvp(block)/while"}
  ROOT %out = f32[4]{0} get-tuple-element(%while.3), index=1
}
"""


def test_op_names_resolve_instructions_without_metadata():
    names = scopes.op_names(HLO)
    attention = "jit(f)/jvp(attention)/add"
    loop = "jit(f)/jvp(block)/while"
    assert names["add.1"] == names["fusion.1"] == names["copy.2"] == attention
    assert names["copy.4"] == names["gte.3"] == names["p"] == loop
    assert names["x"] == "x" and names["out"] == loop
    assert names["zero"] == ""  # nothing to take it from
    assert {scopes.classify(names[n]) for n in ("fusion.1", "copy.2")} == {
        ("attention", "fwd")}


R = "jit(train_step)/transpose(jvp(block))/while/body/closed_call/checkpoint"


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/jvp(block)/while/body/closed_call/block/attention/"
     "bsd,dk->bsk/dot_general", ("attention", "fwd")),
    (f"{R}/block/attention/bsk,kd->bsd/dot_general", ("attention", "bwd")),
    (f"{R}/rematted_computation/block/attention/bsd,dk->bsk/dot_general",
     ("attention", "remat")),
    (f"{R}/block/moe.experts/jit(ragged_matmul_f32)", ("moe.experts", "bwd")),
    ("checkpoint/rematted_computation/block/moe.dispatch/moe.dispatch/add",
     ("moe.dispatch", "remat")),
    ("jit(train_step)/transpose(jvp(loss_head))/while/body/closed_call/"
     "checkpoint/rematted_computation/add", ("loss_head", "remat")),
    ("jit(train_step)/optimizer/sentinel/jit(_where)/select_n",
     ("optimizer/sentinel", "none")),
    ("jit(train_step)/optimizer/mul", ("optimizer", "none")),
    ("jit(train_step)/jvp(block)/while", ("block", "fwd")),
    ("state['m']['embed']", ("unscoped", "none")),
    ("", ("unscoped", "none")),
    (None, ("unmatched", "unmatched")),
])
def test_classify_names_the_scope_and_pass(op_name, want):
    assert scopes.classify(op_name) == want


def test_gaps_take_the_innermost_mark_that_covers_most():
    marks = [["train.step", 0, 100], ["train.fetch:skipped", 10, 30],
             ["bench.data", 58, 14], ["train.data", 55, 20]]
    got = scopes.label_gaps([(5, 35), (50, 80), (200, 210)], marks)
    assert got[0] == ("train.fetch:skipped", 30, 1.0)
    # 50-80: train.step holds 10, train.data 6 and bench.data 14.
    assert got[1] == ("bench.data", 30, 1.0)
    assert got[2] == ("trainer loop", 10, 0.0)


# -- a tiny traced run on the CPU ------------------------------------------------


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """One scoped run of the tiny granite on the CPU: warm-up, one pair of
    4-step windows, then 3 traced steps (steps 10-12; step 10 logs)."""
    from bench import scoped_run

    tdir = tmp_path_factory.mktemp("scoped")
    out = scoped_run.run("granite4l-zipf", 2**31 + 77, windows=1,
                         window_steps=4, cfg=tiny(), require_chip=False,
                         trace_dir=tdir)
    return out, tdir


def test_spans_sit_on_the_profiler_clock_and_nest(tiny_run):
    out, _ = tiny_run
    spans = out["spans"]
    names = {s[0] for s in spans}
    assert {"train.data", "train.step", "train.fetch:step",
            "train.fetch:skipped", "train.fetch:expert_load", "train.log",
            "train.fetch:loss"} <= names
    steps = {n: [s[3] for s in spans if s[0] == n]
             for n in ("train.data", "train.step", "train.log")}
    assert steps == {"train.data": [10, 11, 12], "train.step": [10, 11, 12],
                     "train.log": [10]}

    def inside(inner, outer):
        return [any(o[1] <= i[1] and i[1] + i[2] <= o[1] + o[2]
                    for o in spans if o[0] == outer)
                for i in spans if i[0] == inner]

    assert inside("train.fetch:skipped", "train.step") == [True] * 3
    assert inside("train.fetch:loss", "train.log") == [True]
    assert out["tokens_per_s"]["off"] and out["tokens_per_s"]["on"]


def _cpu_step_ops(tdir):
    """``[name, start, dur, leaf]`` of the train step's ops on the CPU
    backend's op line of the kept trace."""
    from jax.profiler import ProfileData

    (path,) = Path(tdir).glob("**/*.xplane.pb")
    evs = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs += [[ev.name, ev.start_ns, ev.duration_ns]
                    for ev in line.events
                    if dict(ev.stats).get("hlo_module") == "jit_train_step"]
    return trace.mark_leaves(evs)


def test_scope_map_covers_every_op_of_the_compiled_step(tiny_run):
    out, tdir = tiny_run
    names = out["op_names"]
    ops = [op for op in _cpu_step_ops(tdir) if op[3]]
    assert len(ops) > 100
    assert all(n in names for n, *_ in ops)
    times = {}
    for n, _, d, _ in ops:
        key = scopes.classify(names[n])
        times[key] = times.get(key, 0.0) + d
    cov = scopes.coverage(times)
    assert cov["matched"] == 1.0 and cov["scoped"] >= 0.95, times
    seen = set(times)
    for scope in ("attention", "moe.experts", "moe.router", "moe.dispatch",
                  "block"):
        assert {(scope, p) for p in ("fwd", "bwd", "remat")} <= seen
    for key in (("loss_head", "fwd"), ("loss_head", "bwd"),
                ("optimizer", "none"), ("optimizer/sentinel", "none"),
                ("moe.combine", "bwd"), ("embed", "fwd")):
        assert key in seen, key


# -- recorded traces -------------------------------------------------------------


def _ctx(rec):
    cfg = manifest.config("granite-moe-3b-a800m-4l")
    return reduce.Ctx(events=rec, steps=rec["steps"], cfg=cfg, chips=1,
                      peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                      tokens_per_step=16384,
                      flops_per_token=flops.model_flops_per_token(cfg, 4096),
                      loads=[np.asarray(x) for x in rec["loads"]],
                      host_fetches=rec["host_fetches"])


@pytest.mark.parametrize("name", sorted(scopes.READERS))
def test_readers_return_nothing_without_scopes(name):
    """Without an op_name map (a program without scopes, or no trace) each
    reader finds nothing, as the benchmark's readers do."""
    rec = trace.load(str(TESTDATA / "granite4l-zipf.trace.json.gz"))
    assert scopes.READERS[name](_ctx(rec), {}) is None
    empty = {"host": [], "devices": {}, "steps": 0, "loads": [],
             "host_fetches": 0}
    assert scopes.READERS[name](_ctx(empty), {"fusion.1": "x"}) is None


PR12_READINGS = {
    "device_idle_pct": 0.4812368254354493,
    "step_mfu": 8.448938239863582,
    "moe_gemm_roofline": 13.371703362630065,
    "moe_gemm.ms_per_step": 335.0977983333333,
    "moe.expert_load_max_over_mean": 1.0,  # the file keeps even loads
    "trainer.host_syncs_per_step": 2.3333333333333335,
}


def test_existing_readers_read_the_pr12_trace_as_before():
    """Six readers the benchmark runs, each by name, bit for bit, on the
    trace of three granite4l-zipf steps recorded before the program had
    scopes."""
    ctx = _ctx(trace.load(str(TESTDATA / "granite4l-zipf.trace.json.gz")))
    got = {name: reduce.load_reader(name)(ctx) for name in PR12_READINGS}
    assert got == PR12_READINGS


@pytest.mark.parametrize("entry", manifest.load()["per_layer"],
                         ids=lambda m: m["name"])
def test_every_listed_reader_loads_and_reads_the_pr12_trace(entry):
    """Each ``per_layer`` entry of ``BENCHMARK.json``: its reader loads,
    returns a float or None on the recorded granite trace, and its
    ``workloads``, where given, name only cells the benchmark has."""
    ctx = _ctx(trace.load(str(TESTDATA / "granite4l-zipf.trace.json.gz")))
    value = reduce.load_reader(entry["name"])(ctx)
    assert value is None or isinstance(value, float), value
    cells = {w["name"] for w in manifest.load()["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells


def test_scoped_trace_reads_what_perf_md_records():
    """Three granite4l-zipf steps traced on one TPU v5 lite with the
    program's scopes and spans (``bench/scoped_run.py``, seed 3100000013),
    reduced by the five scope readers; PERF.md section 5 records these."""
    rec = trace.load(str(TESTDATA / "granite4l-zipf.scoped.trace.json.gz"))
    ctx, names = _ctx(rec), rec["scopes"]
    got = {n: f(ctx, names) for n, f in scopes.READERS.items()}
    assert got == pytest.approx({
        "attention.ms_per_step": 690.7267423333333,
        "moe_dispatch.ms_per_step": 149.30855433333326,
        "loss_head.ms_per_step": 64.17994199999973,
        "optimizer.ms_per_step": 23.88591199999999,
        "step.remat_pct": 30.49819712376915,
    }, rel=1e-12)
    times = scopes.scope_times(ctx, names)
    cov = scopes.coverage(times)
    assert cov["matched"] == 1.0 and cov["scoped"] > 0.998
    busy = scopes.busy_s_per_step(ctx)
    assert abs(sum(times.values()) / busy - 1.0) < 0.01
    by_pass = {p: sum(v for (_, q), v in times.items() if q == p)
               for p in scopes.PASSES}
    assert sum(by_pass.values()) == pytest.approx(sum(times.values()))
    assert by_pass["bwd"] > by_pass["remat"] > by_pass["fwd"] > by_pass["none"]


def test_scoped_trace_labels_its_idle_gaps_and_ops():
    rec = trace.load(str(TESTDATA / "granite4l-zipf.scoped.trace.json.gz"))
    ctx = _ctx(rec)
    bd = scopes.breakdown(ctx, rec["scopes"], rec["spans"])
    plain = reduce.breakdown(ctx)
    assert [v for _, v in bd["device_ops"]] == [v for _, v in
                                                plain["device_ops"]]
    assert bd["device_ops"][0][0] == "fusion.792@attention.remat"
    assert [v for _, v in bd["idle_gaps"]] == [v for _, v in
                                               plain["idle_gaps"]]
    assert bd["idle_gaps"][0][0] == "train.fetch:skipped"
    assert bd["labelled_idle_share"] == pytest.approx(0.899372137115918)
    # Every idle gap of the window overlaps a program span or bench.data.
    gaps = trace.gaps(ctx.busy(ctx.device_ids()[0]), *ctx.window)
    data = [m for m in rec["host"] if m[0] == "bench.data"]
    labels = {n for n, _, _ in scopes.label_gaps(gaps, rec["spans"] + data)}
    assert "trainer loop" not in labels
    assert {s[0] for s in rec["spans"]} == {
        "train.fetch:step", "train.data", "train.step", "train.fetch:skipped",
        "train.fetch:expert_load"}
