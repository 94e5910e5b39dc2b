"""Telemetry subsystem tests: span/sink semantics, Chrome-trace export
(schedule lanes pinned against the IR occupancy trace), drift-tracker
arithmetic, the engine's structured-trace migration, and the trainer
hot-loop sync-cadence + no-retrace pins."""

import dataclasses
import json
import threading
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.obs import core as obs_core
from repro.core import schedules as sched_lib


def _tel(**kw):
    ring = obs.RingBufferSink()
    return obs.Telemetry(sinks=[ring], **kw), ring


# -- span semantics ----------------------------------------------------------


def test_span_nesting_depth_parent():
    tel, ring = _tel()
    with tel.span("outer", a=1):
        with tel.span("inner"):
            pass
        with tel.span("inner2"):
            pass
    evs = ring.events()
    # inner spans close (and emit) before outer
    assert [e["name"] for e in evs] == ["inner", "inner2", "outer"]
    by = {e["name"]: e for e in evs}
    assert by["outer"]["depth"] == 0 and by["outer"]["parent"] is None
    assert by["inner"]["depth"] == 1 and by["inner"]["parent"] == "outer"
    assert by["inner2"]["parent"] == "outer"
    assert by["outer"]["attrs"] == {"a": 1}
    assert by["outer"]["dur"] >= by["inner"]["dur"] >= 0.0


def test_span_exception_safety():
    tel, ring = _tel()
    with pytest.raises(ValueError):
        with tel.span("boom", x=3):
            raise ValueError("nope")
    evs = ring.events()
    assert len(evs) == 1
    assert evs[0]["attrs"] == {"x": 3, "error": "ValueError"}
    # the stack unwound: the next span is a root again
    with tel.span("after"):
        pass
    assert ring.events()[-1]["depth"] == 0
    assert ring.events()[-1]["parent"] is None


def test_span_set_merges_attrs():
    tel, ring = _tel()
    with tel.span("s", a=1) as sp:
        sp.set(b=2, a=3)
    assert ring.events()[0]["attrs"] == {"a": 3, "b": 2}


def test_record_span_external_duration():
    tel, ring = _tel()
    tel.record_span("bench", 1.25, cell="x")
    (ev,) = ring.events()
    assert ev["kind"] == "span" and ev["dur"] == 1.25
    assert ev["attrs"] == {"cell": "x"}


def test_counters_gauges_histograms_accumulate():
    tel, ring = _tel()
    tel.counter("c")
    tel.counter("c", 2.0)
    tel.gauge("g", 7.5)
    assert tel.counters["c"] == 3.0
    kinds = [e["kind"] for e in ring.events()]
    assert kinds == ["counter", "counter", "gauge"]
    # counter events carry the running total
    assert ring.events()[1]["total"] == 3.0


# -- disabled mode -----------------------------------------------------------


def test_disabled_mode_is_null_singleton_and_silent():
    tel, ring = _tel(enabled=False)
    s1 = tel.span("a", x=1)
    s2 = tel.span("b")
    assert s1 is s2 is obs_core._NULL_SPAN
    with s1 as sp:
        assert sp.set(y=2) is sp
    tel.instant("i")
    tel.counter("c")
    tel.gauge("g", 1.0)
    assert ring.events() == []
    assert tel.counters == {}


def test_disabled_mode_zero_allocation():
    tel = obs.Telemetry(enabled=False)

    def burst(n=200):
        for _ in range(n):
            with tel.span("x", a=1):
                pass
            tel.instant("y", b=2)
            tel.counter("c")

    burst(10)  # warm any lazy state
    flt = tracemalloc.Filter(True, obs_core.__file__)
    tracemalloc.start()
    snap1 = tracemalloc.take_snapshot().filter_traces([flt])
    burst()
    snap2 = tracemalloc.take_snapshot().filter_traces([flt])
    tracemalloc.stop()
    retained = sum(d.size_diff for d in snap2.compare_to(snap1, "lineno"))
    assert retained == 0, f"disabled telemetry retained {retained}B in obs/core"


def _profiled(fn, tmp_path):
    """Run ``fn`` under a ``jax.profiler`` trace; return the host-plane
    events as ``(name, start_ns, dur_ns, stats)``."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    return [
        (ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host")
        for line in plane.lines
        for ev in line.events
    ]


def test_live_span_is_on_the_profiler_clock(tmp_path):
    """A live span enters a TraceAnnotation of its name with its
    constructor attributes; attrs set later stay in the event only."""
    tel, ring = _tel()

    def work():
        with tel.span("t.outer", step=3) as sp:
            with tel.span("t.inner", what="skipped"):
                pass
            sp.set(late=1)

    evs = {n: (s, d, st) for n, s, d, st in _profiled(work, tmp_path)
           if n.startswith("t.")}
    assert set(evs) == {"t.outer", "t.inner"}
    assert evs["t.outer"][2] == {"step": 3}
    assert evs["t.inner"][2] == {"what": "skipped"}
    (s0, d0, _), (s1, d1, _) = evs["t.outer"], evs["t.inner"]
    assert s0 <= s1 and s1 + d1 <= s0 + d0
    assert ring.events()[-1]["attrs"] == {"step": 3, "late": 1}


def test_disabled_span_stays_off_the_profiler_clock(tmp_path):
    tel = obs.Telemetry(enabled=False)

    def work():
        with tel.span("t.off", step=1):
            pass

    assert not [e for e in _profiled(work, tmp_path) if e[0] == "t.off"]


# -- thread safety -----------------------------------------------------------


def test_thread_safety_spans_and_counters():
    tel, ring = _tel()
    N, M = 8, 50

    def work(tid):
        for i in range(M):
            with tel.span("t.outer", tid=tid):
                with tel.span("t.inner"):
                    pass
            tel.counter("t.count")

    threads = [threading.Thread(target=work, args=(i,)) for i in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = ring.events()
    assert len(evs) == N * M * 3
    assert tel.counters["t.count"] == N * M
    # span stacks are thread-local: every inner has depth 1 under t.outer,
    # regardless of interleaving across threads
    for e in evs:
        if e["name"] == "t.inner":
            assert e["depth"] == 1 and e["parent"] == "t.outer"
        elif e["name"] == "t.outer":
            assert e["depth"] == 0


# -- sinks -------------------------------------------------------------------


def test_ring_buffer_capacity_and_clear():
    ring = obs.RingBufferSink(capacity=3)
    tel = obs.Telemetry(sinks=[ring])
    for i in range(5):
        tel.instant(f"e{i}")
    assert [e["name"] for e in ring.events()] == ["e2", "e3", "e4"]
    assert len(ring) == 3
    ring.clear()
    assert ring.events() == []


def test_jsonl_sink_roundtrip(tmp_path):
    path = tmp_path / "metrics.jsonl"
    sink = obs.JsonlSink(path)
    tel = obs.Telemetry(sinks=[sink])
    with tel.span("s", rids=(1, 2), arr=np.int32(7)):
        pass
    tel.counter("c", 2.0)
    tel.close()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == 2
    assert lines[0]["name"] == "s" and lines[0]["kind"] == "span"
    # tuples and numpy scalars serialize to plain JSON
    assert lines[0]["attrs"] == {"rids": [1, 2], "arr": 7}
    assert lines[1]["total"] == 2.0


def test_global_configure_and_restore():
    prev = obs.get_telemetry()
    try:
        tel = obs.configure(sinks=[obs.RingBufferSink()])
        assert obs.get_telemetry() is tel
        with obs.span("g"):
            obs.instant("gi")
        assert [e["name"] for e in tel.sinks[0].events()] == ["gi", "g"]
    finally:
        obs.set_telemetry(prev)


# -- chrome trace export -----------------------------------------------------


def test_chrome_trace_schema_and_kinds():
    tel, ring = _tel()
    with tel.span("phase", step=1):
        tel.instant("mark")
    tel.counter("count")
    tel.gauge("load", 0.5)
    trace = obs.chrome_trace(ring.events(), process_name="test")
    obs.validate_chrome_trace(trace)
    phs = [e["ph"] for e in trace["traceEvents"]]
    assert phs.count("X") == 1 and phs.count("i") == 1 and phs.count("C") == 2
    x = next(e for e in trace["traceEvents"] if e["ph"] == "X")
    assert x["name"] == "phase" and x["args"] == {"step": 1}
    assert isinstance(x["ts"], float) and isinstance(x["dur"], float)


def test_chrome_trace_validation_rejects_malformed():
    with pytest.raises(ValueError):
        obs.validate_chrome_trace({"nope": []})
    with pytest.raises(ValueError):
        obs.validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "a"}]})
    with pytest.raises(ValueError):
        obs.validate_chrome_trace(
            {"traceEvents": [{"ph": "Z", "name": "a", "ts": 0}]}
        )
    with pytest.raises(ValueError):
        obs.validate_chrome_trace(
            {"traceEvents": [
                {"ph": "X", "name": "a", "ts": "soon", "dur": 1,
                 "pid": 1, "tid": 0}
            ]}
        )


@pytest.mark.parametrize(
    "name,PP,M,V",
    [
        ("1f1b", 4, 8, 1),
        ("1f1b_overlap", 4, 8, 1),
        ("zb_h1", 4, 8, 1),
        ("interleaved_1f1b", 2, 4, 2),
        ("gpipe", 2, 4, 1),
    ],
)
def test_schedule_lanes_match_occupancy_trace(name, PP, M, V):
    """The acceptance pin: the rendered pipeline lanes ARE the schedule IR —
    one complete event per non-idle op, and the per-stage counter series
    equals Schedule.occupancy_trace() value-for-value.  (Comm events live
    on their own lanes, tid >= PP — the compute lanes stay pure.)"""
    sched = sched_lib.build(name, PP, M, V)
    evs = obs.schedule_lane_events(sched, tick_s=1e-3)
    obs.validate_chrome_trace({"traceEvents": evs})
    occ = sched.occupancy_trace()
    ops = [e for e in evs if e["ph"] == "X" and e["tid"] < sched.PP]
    n_ops = sum(
        1
        for st in range(sched.PP)
        for t in range(sched.num_ticks)
        if sched.ops[st][t] is not None
    )
    assert len(ops) == n_ops > 0
    for stage in range(sched.PP):
        counters = [
            e["args"]["value"]
            for e in evs
            if e["ph"] == "C" and e["tid"] == stage
        ]
        assert counters == [int(v) for v in occ[stage]]
        # every op event on this lane reproduces the IR cell it came from
        for e in ops:
            if e["tid"] != stage:
                continue
            kind, mb, vs = sched.ops[stage][e["args"]["tick"]]
            assert (e["args"]["kind"], e["args"]["mb"], e["args"]["vstage"]) \
                == (kind, mb, vs)
            assert e["name"] == f"{kind}{mb}"


def test_schedule_comm_lane_matches_comm_trace():
    """Overlap schedules: the per-stage comm lane renders every comm op of
    the IR exactly once, the dwell spans cover the (send+1, recv) windows,
    and the comm_inflight counter series equals Schedule.comm_trace()
    value-for-value.  Legacy schedules emit no comm lane at all."""
    sched = sched_lib.build("1f1b_overlap", 4, 8)
    evs = obs.schedule_lane_events(sched, tick_s=1e-3)
    obs.validate_chrome_trace({"traceEvents": evs})
    ctrace = sched.comm_trace()
    for stage in range(sched.PP):
        tid = sched.PP + stage
        counters = [
            e["args"]["value"]
            for e in evs
            if e["ph"] == "C" and e["tid"] == tid
        ]
        assert counters == [int(v) for v in ctrace[stage]]
        lane = [
            e for e in evs
            if e["ph"] == "X" and e["tid"] == tid and "direction" not in e["args"]
        ]
        want = [
            (f"{k}{mb}", k, mb, vs, t)
            for t in range(sched.num_ticks)
            for k, mb, vs in sched.comm[stage][t]
        ]
        got = [
            (e["name"], e["args"]["kind"], e["args"]["mb"],
             e["args"]["vstage"], e["args"]["tick"])
            for e in lane
        ]
        assert got == want and len(want) > 0
    # dwell spans: one per comm edge with a nonzero in-flight window
    dwells = [
        e for e in evs if e["ph"] == "X" and "direction" in e.get("args", {})
    ]
    edges = [
        (d, key, ts, tr)
        for d, key, ts, tr in sched.comm_edges()
        if tr > ts + 1
    ]
    assert len(dwells) == len(edges) > 0
    for e in dwells:
        assert e["dur"] > 0
    # legacy: no comm lane
    legacy = obs.schedule_lane_events(sched_lib.build("1f1b", 4, 8), 1e-3)
    assert not any(e.get("tid", 0) >= 4 and e["ph"] != "M" for e in legacy)
    assert not any(
        str(e.get("name", "")).startswith("comm_inflight") for e in legacy
    )


def test_write_chrome_trace_with_schedule(tmp_path):
    tel, ring = _tel()
    with tel.span("train.step", step=0):
        pass
    sched = sched_lib.build("1f1b", 2, 4, 1)
    path = tmp_path / "trace.json"
    obs.write_chrome_trace(path, ring.events(), schedule=sched, tick_s=2e-3)
    loaded = json.loads(path.read_text())
    obs.validate_chrome_trace(loaded)
    names = {e["name"] for e in loaded["traceEvents"]}
    assert "train.step" in names
    assert any(n.startswith("occupancy stage") for n in names)
    # lane ops render at the requested tick width
    lane_ops = [
        e for e in loaded["traceEvents"]
        if e["ph"] == "X" and "vstage" in e.get("args", {})
    ]
    assert lane_ops and all(e["dur"] == pytest.approx(2e3) for e in lane_ops)


# -- drift tracker -----------------------------------------------------------


def test_drift_tracker_arithmetic():
    tr = obs.DriftTracker({"step": 0.1, "ckpt": 2.0}, warmup=1)
    for v in (0.5, 0.2, 0.3):  # first sample (compile) discarded
        tr.record("step", v)
    tr.record("data", 0.01)
    tr.record("data", 0.03)
    rep = tr.report()
    assert rep["step"]["n"] == 2
    assert rep["step"]["mean_s"] == pytest.approx(0.25)
    assert rep["step"]["min_s"] == 0.2 and rep["step"]["max_s"] == 0.3
    assert rep["step"]["ratio"] == pytest.approx(2.5)
    # modeled but never measured: visible with n=0, no ratio
    assert rep["ckpt"] == {"modeled_s": 2.0, "n": 0}
    # measured but unmodeled: no ratio  (first 'data' sample was warmup)
    assert rep["data"]["modeled_s"] is None and rep["data"]["n"] == 1
    assert "ratio" not in rep["data"]
    txt = tr.format_report("t")
    assert "step" in txt and "2.5" in txt


def test_drift_observe_events_scrapes_spans():
    tel, ring = _tel()
    with tel.span("train.step", step=0):
        pass
    with tel.span("train.step", step=1):
        pass
    with tel.span("engine.decode", step=2):
        pass
    with tel.span("unrelated"):
        pass
    tel.instant("train.step")  # instants are not durations
    tr = obs.DriftTracker({"step": 1.0, "decode": 1.0}, warmup=0)
    n = tr.observe_events(ring.events())
    assert n == 3
    assert tr.report()["step"]["n"] == 2
    assert tr.report()["decode"]["n"] == 1


def test_modeled_phase_views_cover_acceptance_phases():
    from repro.configs import get_arch
    from repro.core import resource_model as rm
    from repro.core.platform import TPU_V5E

    m = rm.ModelShape.from_arch(get_arch("granite-moe-3b-a800m"))
    est = rm.estimate(m, rm.TrainSetup(b=64, s=1024, PP=4, EP=4, DP=2), TPU_V5E)
    phases = rm.modeled_phases(est)
    assert {"step", "a2a", "ckpt"} <= set(phases)
    assert phases["step"] > 0 and phases["ckpt"] > 0
    se = rm.serve_estimate(
        m, rm.ServeSetup(batch=8, context=2048, prefill_len=1024), TPU_V5E
    )
    sphases = rm.modeled_serve_phases(se)
    assert {"decode", "prefill"} <= set(sphases)
    assert sphases["decode"] > 0
    # the four acceptance phases all have a modeled source
    assert set(phases) | set(sphases) >= {"step", "a2a", "ckpt", "decode"}
    # DriftTracker classmethods wire these through
    tr = obs.DriftTracker.for_train(
        m, rm.TrainSetup(b=64, s=1024), TPU_V5E
    )
    assert tr.modeled["step"] > 0


# -- engine structured-trace migration ---------------------------------------


def _engine_run(n=5, max_new=3):
    from repro.configs import get_arch
    from repro.models.model import LanguageModel, init_params
    from repro.serving import Engine, Request, ServeConfig
    from repro.sharding import single_device_plan
    import jax

    arch = get_arch("granite-moe-3b-a800m").reduced()
    arch = arch.replace(
        moe=dataclasses.replace(arch.moe, dispatch="ragged")
    )
    plan = single_device_plan(arch)
    lm = LanguageModel(arch, plan)
    rng = np.random.default_rng(3)
    reqs = [
        Request(
            rid=i,
            tokens=rng.integers(0, arch.vocab_size, size=int(l)),
            max_new_tokens=max_new,
        )
        for i, l in enumerate(rng.integers(3, 14, size=n))
    ]
    with plan.mesh:
        params = init_params(arch, jax.random.PRNGKey(0))
        eng = Engine(
            lm, params,
            ServeConfig(max_seqs=2, block_size=4, num_blocks=32,
                        max_blocks_per_seq=8),
        )
        out = eng.run(reqs)
    return eng, out


def test_engine_tuple_view_equals_structured_stream():
    """Satellite pin: the legacy tuple trace is a pure view of the
    structured event stream — rebuilt event-for-event they are equal."""
    eng, out = _engine_run()
    assert len(out) == 5
    tuples = eng.trace
    instants = [
        e for e in eng.trace_ring.events()
        if e["kind"] == "instant"
        and e["name"].split(".", 1)[-1] in eng._TRACE_FIELDS
    ]
    assert len(tuples) == len(instants) > 0
    for tup, ev in zip(tuples, instants):
        kind = ev["name"][len("engine."):]
        a = ev["attrs"]
        assert tup == (kind, a["step"]) + tuple(
            a[f] for f in eng._TRACE_FIELDS[kind]
        )
    # the stream also carries spans the tuple view ignores
    span_names = {
        e["name"] for e in eng.trace_ring.events() if e["kind"] == "span"
    }
    assert {"engine.step", "engine.prefill", "engine.decode"} <= span_names
    # timestamp-free determinism survives the migration
    eng2, out2 = _engine_run()
    assert eng2.trace == tuples and out2 == out


# -- trainer hot-loop cadence + no-retrace pins ------------------------------


def _fit_tiny_trainer(total_steps=8, log_every=4):
    import jax
    from repro.configs import get_arch
    from repro.data import SyntheticTokens
    from repro.models.model import LanguageModel
    from repro.optim import OptimizerConfig
    from repro.runtime import Trainer, TrainerConfig
    from repro.sharding import single_device_plan
    from repro import training as tr_lib

    arch = get_arch("smollm-360m").reduced()  # dense: no expert_load fetch
    plan = single_device_plan(arch)
    lm = LanguageModel(arch, plan)
    opt = OptimizerConfig(lr=1e-3, total_steps=total_steps)
    trainer = Trainer(
        lm, opt,
        TrainerConfig(total_steps=total_steps, log_every=log_every),
        log_fn=lambda *_: None,
    )
    with plan.mesh:
        state = tr_lib.init_state(lm, jax.random.PRNGKey(0), opt)
        data = SyntheticTokens(arch.vocab_size, 2, 32)
        out = trainer.fit(state, data)
    return trainer, out


def test_trainer_host_fetch_cadence():
    """Satellite pin: per step the trainer syncs the host exactly once (the
    in-jit skipped flag); loss is fetched only on log_every steps."""
    trainer, out = _fit_tiny_trainer(total_steps=8, log_every=4)
    assert out["last_step"] == 7 and not out["anomalies"]
    # 1 (start_step) + 8 (skipped flag) + 2 (loss at steps 0 and 4)
    assert trainer.host_fetches == 1 + 8 + 2


def test_trainer_spans_name_the_host_work():
    """Every blocking fetch is a ``train.fetch`` span naming its value,
    inside the step span for the skip flag and inside ``train.log`` for
    the loss; the data and step spans carry their step."""
    ring = obs.RingBufferSink()
    prev = obs.set_telemetry(obs.Telemetry(sinks=[ring]))
    try:
        trainer, _ = _fit_tiny_trainer(total_steps=3, log_every=2)
    finally:
        obs.set_telemetry(prev)
    spans = [e for e in ring.events() if e["kind"] == "span"]
    fetches = [e for e in spans if e["name"] == "train.fetch"]
    assert [e["attrs"]["what"] for e in fetches] == [
        "step", "skipped", "loss", "skipped", "skipped", "loss"]
    assert len(fetches) == trainer.host_fetches
    assert {e["parent"] for e in fetches if e["attrs"]["what"] == "skipped"
            } == {"train.step"}
    assert {e["parent"] for e in fetches if e["attrs"]["what"] == "loss"
            } == {"train.log"}
    for name in ("train.data", "train.step"):
        assert [e["attrs"]["step"] for e in spans if e["name"] == name] \
            == [0, 1, 2]
    assert [e["attrs"]["step"] for e in spans if e["name"] == "train.log"] \
        == [0, 2]


def test_trainer_step_not_retraced():
    """The jitted step compiles at most twice — once for init_state's
    uncommitted arrays, once for its own committed outputs — and NEVER
    again, no matter how many steps run (a per-step retrace would show up
    as cache_size ~ total_steps)."""
    t6, _ = _fit_tiny_trainer(total_steps=6, log_every=3)
    t9, _ = _fit_tiny_trainer(total_steps=9, log_every=3)
    assert t6.train_step._cache_size() == t9.train_step._cache_size() <= 2
