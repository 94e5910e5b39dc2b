"""The program's own names in a traced run, and what the benchmark reads
from them.

Device side: the program wraps each layer's work in ``jax.named_scope``
(``embed``, ``attention``, ``moe.router`` ...).  The names reach the
compiled step's HLO as ``metadata={op_name=...}``; ``op_names`` maps each
instruction of the step module (``compiled.as_text()``) to its op_name,
and the trace names its device ops after those instructions.  The path
also says which pass an op belongs to: ``jvp(...)`` is the forward,
``transpose(...)`` the backward, ``.../rematted_computation/...`` work
recomputed for the backward, and an op outside differentiation (the
optimizer) none of these.

Host side: a live ``obs`` span holds a ``jax.profiler.TraceAnnotation`` of
its name, so the program's spans (``train.*``, ``ckpt.*``,
``pipeline.*``) sit on the host plane of the same trace, with their
``step`` and ``what`` as event stats.  ``extract_spans`` keeps them as
``[name, start_ns, dur_ns, step]``, with ``what`` folded into the name
(``train.fetch:expert_load``).

The readers take a ``reduce.Ctx`` and the op_name map; each returns None
where it finds nothing to read.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from bench import trace

# The program's scopes, innermost wins; ``optimizer`` nests ``sentinel``.
SCOPES = ("embed", "block", "attention", "moe.router", "moe.dispatch",
          "moe.experts", "moe.combine", "moe.exchange", "loss_head",
          "optimizer")
NESTED = {"optimizer": "sentinel"}
PASSES = ("fwd", "bwd", "remat", "none")
UNSCOPED = "unscoped"
UNMATCHED = "unmatched"
SPAN_PREFIXES = ("train.", "ckpt.", "pipeline.")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w.\-]*\((.*)\)$")


def op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of every instruction of an HLO
    module's text ("" where none is found).

    An instruction without metadata of its own takes that of the
    computation it calls (a fusion: its root's, else the first inside that
    has one), else that of its first operand that has one (an inserted
    copy takes the op_name of what it copies), else that of the
    instruction that calls its computation (a loop body's carry copies
    take the loop's)."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    home: Dict[str, str] = {}  # instruction -> its computation
    comps: Dict[str, List[str]] = {}  # computation -> instructions, root first
    refs: Dict[str, List[str]] = {}  # instruction -> every name it names
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            if c:
                comp = c.group(1)
                comps.setdefault(comp, [])
            continue
        name = m.group(2)
        if comp is not None:
            home[name] = comp
            if m.group(1):
                comps[comp].insert(0, name)
            else:
                comps[comp].append(name)
        body = line[m.end():].split(", metadata=", 1)[0]
        refs[name] = _REF.findall(body)
        n = _OP_NAME.search(line)
        if n:
            own[name] = n.group(1)
            continue
        c = _CALLS.search(body)
        if c:
            calls[name] = c.group(1)
        operands[name] = _REF.findall(body[:c.start()] if c else body)
    caller = {r: name for name, rs in refs.items() for r in rs if r in comps}
    out = dict(own)

    def resolve(name: str, depth: int = 0) -> str:
        if name in out or depth > 16:
            return out.get(name, "")
        found = ""
        if name in calls:
            found = next((own[i] for i in comps.get(calls[name], ())
                          if i in own), "")
        for r in operands.get(name, ()):
            if found:
                break
            found = resolve(r, depth + 1)
        if not found and home.get(name) in caller:
            found = resolve(caller[home[name]], depth + 1)
        if found:
            out[name] = found
        return found

    for name in refs:
        out[name] = resolve(name)
    return out


def _names(op_name: str) -> List[str]:
    """The scope and op names of a path, transforms peeled:
    ``jit(f)/transpose(jvp(attention))/dot_general`` ->
    ``[f, attention, dot_general]``."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        out.append(part)
    return out


def classify(op_name: Optional[str]) -> Tuple[str, str]:
    """``(scope, pass)`` of an op_name: the innermost of ``SCOPES`` on the
    path (``optimizer/sentinel`` for the sentinel), else ``unscoped``; the
    pass is ``remat``, ``bwd``, ``fwd`` or ``none``.  None (an op the step
    module does not hold) is ``(unmatched, unmatched)``."""
    if op_name is None:
        return UNMATCHED, UNMATCHED
    names = _names(op_name)
    scope, at = UNSCOPED, -1
    for i, n in enumerate(names):
        if n in SCOPES:
            scope, at = n, i
    if scope in NESTED and NESTED[scope] in names[at + 1:]:
        scope = f"{scope}/{NESTED[scope]}"
    if "rematted_computation" in names:
        pas = "remat"
    elif "transpose(" in op_name:
        pas = "bwd"
    elif "jvp(" in op_name:
        pas = "fwd"
    else:
        pas = "none"
    return scope, pas


# -- device time by scope ------------------------------------------------------


def scope_times(ctx, names: Dict[str, str]) -> Dict[Tuple[str, str], float]:
    """Leaf-op device seconds per step by ``(scope, pass)``, the mean over
    the chips; ops the map does not hold are ``(unmatched, unmatched)``."""
    devs = ctx.device_ids()
    out: Dict[Tuple[str, str], float] = {}
    if not devs or not ctx.steps:
        return out
    per = 1e-9 / (len(devs) * ctx.steps)
    for dev in devs:
        for name, _, d, leaf in ctx.ops(dev):
            if leaf:
                key = classify(names.get(name))
                out[key] = out.get(key, 0.0) + d * per
    return out


def busy_s_per_step(ctx) -> float:
    devs = ctx.device_ids()
    if not devs or not ctx.steps:
        return 0.0
    return (sum(trace.length(ctx.busy(d)) for d in devs) * 1e-9
            / (len(devs) * ctx.steps))


def coverage(times: Dict[Tuple[str, str], float]) -> Dict[str, float]:
    """Shares of leaf-op time that found an HLO instruction, and a scope."""
    total = sum(times.values())
    if not total:
        return {"matched": 0.0, "scoped": 0.0}
    unmatched = sum(v for (s, _), v in times.items() if s == UNMATCHED)
    unscoped = sum(v for (s, _), v in times.items() if s == UNSCOPED)
    return {"matched": 1.0 - unmatched / total,
            "scoped": 1.0 - (unmatched + unscoped) / total}


def table(times: Dict[Tuple[str, str], float]) -> List[str]:
    """One line per scope: ms/step in each pass and in all, largest first."""
    rows: Dict[str, Dict[str, float]] = {}
    for (scope, pas), v in times.items():
        rows.setdefault(scope, {})[pas] = v
    order = sorted(rows, key=lambda s: -sum(rows[s].values()))
    cols = PASSES + (UNMATCHED,)
    lines = [f"{'scope':<20}" + "".join(f"{c:>11}" for c in cols)
             + f"{'total':>11}"]
    for s in order:
        r = rows[s]
        lines.append(f"{s:<20}" + "".join(
            f"{r.get(c, 0.0) * 1e3:>11.3f}" for c in cols)
            + f"{sum(r.values()) * 1e3:>11.3f}")
    return lines


def _ms_under(ctx, names, scopes) -> Optional[float]:
    if not names:
        return None
    times = scope_times(ctx, names)
    if not times:
        return None
    return 1e3 * sum(v for (s, _), v in times.items() if s in scopes)


def attention_ms_per_step(ctx, names):
    return _ms_under(ctx, names, ("attention",))


def moe_dispatch_ms_per_step(ctx, names):
    return _ms_under(ctx, names, ("moe.router", "moe.dispatch",
                                  "moe.combine"))


def loss_head_ms_per_step(ctx, names):
    return _ms_under(ctx, names, ("loss_head",))


def optimizer_ms_per_step(ctx, names):
    return _ms_under(ctx, names, ("optimizer", "optimizer/sentinel"))


def step_remat_pct(ctx, names):
    if not names:
        return None
    times = scope_times(ctx, names)
    busy = busy_s_per_step(ctx)
    if not times or not busy:
        return None
    return 100.0 * sum(v for (_, p), v in times.items() if p == "remat") / busy


READERS = {
    "attention.ms_per_step": attention_ms_per_step,
    "moe_dispatch.ms_per_step": moe_dispatch_ms_per_step,
    "loss_head.ms_per_step": loss_head_ms_per_step,
    "optimizer.ms_per_step": optimizer_ms_per_step,
    "step.remat_pct": step_remat_pct,
}


# -- host spans ----------------------------------------------------------------


def extract_spans(xplane_path: str) -> List[List]:
    """The program's spans on the trace's host plane:
    ``[name, start_ns, dur_ns, step]`` (``step`` None where the span has
    none), ``what`` folded into the name."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIXES):
                    continue
                stats = dict(ev.stats)
                name = ev.name
                if "what" in stats:
                    name = f"{name}:{stats['what']}"
                step = stats.get("step")
                out.append([name, ev.start_ns, ev.duration_ns,
                            None if step is None else int(step)])
    return sorted(out, key=lambda s: (s[1], -s[2]))


def label_gaps(gaps: List[trace.Interval], marks: List[List]
               ) -> List[Tuple[str, float, float]]:
    """Each gap's label and the share of it that some mark covers.  Marks
    are ``[name, start, dur, ...]``; at each instant the shortest covering
    mark (the innermost) holds, and the gap takes the label that holds
    longest, ``trainer loop`` where no mark overlaps."""
    by_len = sorted(marks, key=lambda m: -m[2])  # outer first, inner paints
    out = []
    for a, b in gaps:
        owner: List[Tuple[float, float, str]] = []
        for name, s, d, *_ in by_len:
            lo, hi = max(a, s), min(b, s + d)
            if hi > lo:
                owner = [(x, y, n) for x0, y0, n in owner
                         for x, y in trace.subtract([(x0, y0)], [(lo, hi)])]
                owner.append((lo, hi, name))
        held: Dict[str, float] = {}
        for x, y, n in owner:
            held[n] = held.get(n, 0.0) + (y - x)
        if not held:
            out.append(("trainer loop", b - a, 0.0))
            continue
        label = max(held, key=held.get)
        out.append((label, b - a, sum(held.values()) / (b - a)))
    return out


def breakdown(ctx, names: Dict[str, str], spans: List[List],
              top: int = 10) -> Dict:
    """``reduce.breakdown``'s ranking and values, labelled: each device op
    with its scope and pass (``fusion.792@attention.bwd``), each idle gap
    of the first device with the innermost program span or ``bench.data``
    that covers most of it.  ``labelled_idle_share`` is the share of the
    window's idle time of the first device that some mark covers."""
    from bench import reduce

    out = reduce.breakdown(ctx, top)
    out["device_ops"] = [
        [f"{n}@{'.'.join(classify(names.get(n)))}", s]
        for n, s in out["device_ops"]]
    devs, w = ctx.device_ids(), ctx.window
    if not devs or w is None:
        return out
    gaps = trace.gaps(ctx.busy(devs[0]), *w)
    data = [m for m in ctx.events["host"] if m[0] == "bench.data"]
    labelled = label_gaps(gaps, spans + data)
    idle = sum(g for _, g, _ in labelled)
    out["labelled_idle_share"] = (
        sum(g * c for _, g, c in labelled) / idle if idle else None)
    ranked = sorted(labelled, key=lambda x: -x[1])[:top]
    out["idle_gaps"] = [[n, g * 1e-9] for n, g, _ in ranked]
    return out
