"""Sharding plan: mapping Piper's PP x EP x DP hybrid onto a TPU mesh.

The production mesh is ``(16,16) -> ("data","model")`` per pod (and
``(2,16,16) -> ("pod","data","model")`` multi-pod).  Piper factors the fast
"model" axis into **EP x TP** sub-axes (``ep * tp == |model|``) so that the
expert-parallel all-to-all spans exactly the expert-count-compatible subgroup
(paper constraint Eq 8: ``EP | E``).  We realize the factoring by *refining*
the production mesh: the same device grid, with the model axis reshaped into
("ep","tp").  ``tp`` lanes are innermost, i.e. ICI-adjacent.

Logical parameter axes -> mesh axes ("sharding rules", MaxText-style):

    =============  =======================  =================================
    logical axis   baseline rule            meaning
    =============  =======================  =================================
    "batch"        ("pod","data")           data parallelism
    "seq"          ("ep","tp")              sequence sharding (X-MoE-style)
    "vocab"        ("data",)                embedding vocab (ZeRO)
    "embed"        ("data",)                d_model dim of weights (ZeRO-3)
    "model_out"    ("ep","tp")              output dim of weight matrices
    "expert"       ("ep",)                  expert index dim of MoE weights
    "expert_ffn"   ("data","tp")            d_ff dim of expert weights
    "pipe"         ("pod",) when PP on pod  pipeline stage dim
    =============  =======================  =================================

Everything the planner searches over (EP degree, PP-on-pod, remat, optimizer
dtypes) funnels through :class:`MeshPlan`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, DEFAULT_SCHEDULE, SCHEDULES

# ---------------------------------------------------------------------------
# Mesh refinement
# ---------------------------------------------------------------------------


def choose_ep(num_experts: int, model_axis: int) -> int:
    """Largest EP degree that divides both the expert count (paper Eq 8)
    and the fast-domain axis size (paper Eq 10)."""
    return math.gcd(num_experts, model_axis)


def refine_mesh(mesh: Mesh, ep: int) -> Mesh:
    """Reshape the production mesh's "model" axis into ("ep","tp").

    Same devices, same topology: "tp" lanes are innermost (ICI-adjacent on
    the torus), so TP/FSDP-lane collectives stay single-hop, and "ep"
    subgroups are contiguous strided blocks — the TPU analogue of the
    paper's "EP within a fast-interconnect domain" (Eq 10).
    """
    axis_names = list(mesh.axis_names)
    assert axis_names[-1] == "model", mesh
    model = mesh.devices.shape[-1]
    assert model % ep == 0, (model, ep)
    tp = model // ep
    new_shape = mesh.devices.shape[:-1] + (ep, tp)
    new_names = tuple(axis_names[:-1]) + ("ep", "tp")
    return Mesh(mesh.devices.reshape(new_shape), new_names)


# ---------------------------------------------------------------------------
# Mesh plan
# ---------------------------------------------------------------------------


@dataclass
class MeshPlan:
    """A concrete parallelization strategy bound to a (refined) mesh."""

    mesh: Mesh
    ep: int
    tp: int
    dp_axes: Tuple[str, ...]  # batch-sharding axes
    sp_axes: Tuple[str, ...] = ("ep", "tp")  # sequence-sharding axes
    ep_axis: str = "ep"
    tp_axis: str = "tp"
    pp_axis: Optional[str] = None  # "pod" when Piper pipelines across pods
    pp: int = 1
    # Pipeline schedule (a core.schedules builder name).  1F1B is the
    # paper's schedule (Eq 4 memory profile); "gpipe" keeps the all-F-then-
    # all-B order; "interleaved_1f1b" splits each stage into ``vstages``
    # virtual stages (model chunks); "zb_h1" splits the backward into
    # Bi/Bw and fills the drain bubble with the deferred weight grads at
    # Eq-4-equal residual memory.  Only consulted when pp > 1.
    schedule: str = DEFAULT_SCHEDULE
    # Virtual stages per pipeline stage; > 1 only with interleaved_1f1b
    # (must divide the layer-reps per stage — the executor asserts it).
    vstages: int = 1
    # memory-policy knobs the planner searches over
    remat: str = "full"  # none | dots | full
    optimizer_dtype: str = "float32"  # adam m/v dtype
    master_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # Beyond-paper: schedule expert a2a hierarchically when EP spans pods
    hierarchical_a2a: bool = False
    # Chunked double-buffered EP a2a: split the dispatch/combine payload
    # into this many row chunks and overlap each chunk's transfer with the
    # previous chunk's expert FFN (models.moe via halo.overlapped_a2a).
    # 1 = monolithic transfer (bit-identical to the pre-chunking path).
    a2a_chunks: int = 1
    # Beyond-paper: int8 pipeline hand-offs across the slow pod axis
    compress_p2p: bool = False
    # Dry-run-only workaround: the embedding-table gradient path under
    # pod-axis pipelining trips an XLA SPMD crash at 512 fake CPU devices
    # (XLA bug b/433785288: 'Invalid binary instruction opcode copy' in the
    # involuntary-remat fallback).  False => stop_gradient on the table.
    # Embedding gradients under pipelining are verified on host meshes in
    # tests/test_pipeline.py, where the buggy path is not taken.
    embed_grad: bool = True
    # Pipeline microbatch count (None -> 2*PP)
    microbatches: Optional[int] = None
    # Sharding rules: logical axis -> mesh axes tuple (None = replicate)
    rules: Dict[str, Optional[Tuple[str, ...]]] = field(default_factory=dict)

    def __post_init__(self):
        assert self.schedule in SCHEDULES, (
            f"unknown schedule {self.schedule!r}; choose from {SCHEDULES}"
        )
        assert self.vstages >= 1, self.vstages
        assert self.vstages == 1 or self.schedule == "interleaved_1f1b", (
            f"vstages={self.vstages} needs schedule='interleaved_1f1b', "
            f"got {self.schedule!r}"
        )
        assert self.a2a_chunks >= 1, self.a2a_chunks
        if not self.rules:
            self.rules = default_rules(self)

    # -- helpers ------------------------------------------------------------

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    @property
    def dp(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.dp_axes])) or 1

    def axis_size(self, name: str) -> int:
        return self.mesh.shape[name]

    def spec(self, *logical: Optional[str]) -> P:
        """PartitionSpec from logical dim names (None = replicated dim)."""
        out = []
        for ax in logical:
            if ax is None:
                out.append(None)
                continue
            rule = self.rules.get(ax)
            if rule is None:
                out.append(None)
            elif len(rule) == 1:
                out.append(rule[0])
            else:
                out.append(tuple(rule))
        return P(*out)

    def sharding(self, *logical: Optional[str]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical))

    def divisor(self, logical: str) -> int:
        rule = self.rules.get(logical)
        if not rule:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in rule]))


def default_rules(plan: MeshPlan) -> Dict[str, Optional[Tuple[str, ...]]]:
    dp: Tuple[str, ...] = plan.dp_axes
    # Under pod-axis pipelining, a vocab-sharded embedding gather triggers an
    # XLA SPMD partitioner crash (invalid `copy` opcode during involuntary
    # remat) — keep the vocab dim replicated there; the d_model dim stays
    # model-sharded so the table is still 16-way distributed.
    vocab_rule: Optional[Tuple[str, ...]] = (
        None if plan.pp_axis is not None else ("data",)
    )
    return {
        "batch": dp,
        "seq": tuple(plan.sp_axes),
        "vocab": vocab_rule,
        "embed": ("data",),
        "model_out": ("ep", "tp"),
        "expert": ("ep",),
        "expert_ffn": ("data", "tp"),
        "ssm_inner": ("ep", "tp"),
        "pipe": (plan.pp_axis,) if plan.pp_axis else None,
        "kv_seq": tuple(plan.sp_axes),  # KV-cache seq dim (decode)
        "kv_heads": None,
        "_replicated": None,
    }


# ---------------------------------------------------------------------------
# Plan construction
# ---------------------------------------------------------------------------


def make_plan(
    mesh: Mesh,
    arch: ArchConfig,
    *,
    pipeline_on_pod: bool = False,
    schedule: str = DEFAULT_SCHEDULE,
    vstages: int = 1,
    remat: str = "full",
    optimizer_dtype: str = "float32",
    hierarchical_a2a: bool = False,
    a2a_chunks: int = 1,
) -> MeshPlan:
    """Bind an architecture to a production mesh.

    ``mesh`` must carry a trailing "model" axis (the production meshes do);
    it is refined into ("ep","tp") per the architecture's expert count.
    Dense architectures get ep = |model| (the "ep" axis then only carries
    sequence/tensor sharding and the a2a machinery is inert — see DESIGN.md
    §Arch-applicability).
    """
    model_axis = mesh.shape["model"]
    n_exp = arch.moe.num_experts if arch.moe is not None else model_axis
    ep = choose_ep(n_exp, model_axis)
    refined = refine_mesh(mesh, ep)
    tp = model_axis // ep

    axis_names = refined.axis_names
    pp_axis = None
    pp = 1
    if pipeline_on_pod:
        assert "pod" in axis_names, "pipeline_on_pod requires a pod axis"
        pp_axis = "pod"
        pp = refined.shape["pod"]
        dp_axes: Tuple[str, ...] = ("data",)
    else:
        dp_axes = tuple(a for a in ("pod", "data") if a in axis_names)

    return MeshPlan(
        mesh=refined,
        ep=ep,
        tp=tp,
        dp_axes=dp_axes,
        sp_axes=("ep", "tp"),
        pp_axis=pp_axis,
        pp=pp,
        schedule=schedule,
        vstages=vstages,
        remat=remat,
        optimizer_dtype=optimizer_dtype,
        hierarchical_a2a=hierarchical_a2a,
        a2a_chunks=a2a_chunks,
    )


def single_device_plan(arch: ArchConfig) -> MeshPlan:
    """A trivial 1-device plan for CPU smoke tests."""
    dev = np.asarray(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(dev, ("data", "model"))
    return make_plan(mesh, arch)


def host_mesh(shape: Sequence[int], names: Sequence[str]) -> Mesh:
    """A mesh over the first ``prod(shape)`` local devices, laid out by
    ``jax.make_mesh`` (physical-topology order on a TPU), with GSPMD-auto
    axes like every other mesh here."""
    return jax.make_mesh(
        tuple(shape), tuple(names),
        axis_types=(AxisType.Auto,) * len(shape),
    )
