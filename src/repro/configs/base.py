"""Architecture & shape configuration system.

Every assigned architecture is expressed as an :class:`ArchConfig` — a frozen,
hashable description of the model family, the per-layer block pattern, and the
MoE / SSM / attention hyper-parameters.  The model substrate
(``repro.models``) consumes these configs; the Piper planner
(``repro.core.planner``) consumes the same configs for resource modeling, so
there is a single source of truth for "what the model is".
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

# Pipeline schedules the system understands end-to-end: the planner
# enumerates over them (schedule-aware Eq 3/4 memory, and the vstage count
# V for the interleaved family), ``MeshPlan.schedule``/``MeshPlan.vstages``
# bind the winner, and the executor (``repro.core.pipeline``) interprets
# the matching ``repro.core.schedules`` IR.  Kept here — next to the other
# single-source-of-truth config vocabulary — so configs, planner and
# executor can never disagree on the legal names.  ``zb_h1`` is the
# zero-bubble ZB-H1 schedule: backward split into activation-grad (Bi) and
# deferred weight-grad (Bw) ops at 1F1B-equal residual memory, the drain
# bubble filled by the deferred Bw's (plus a small W-stash priced
# separately by the resource model).  ``1f1b_overlap`` is 1F1B with the
# stage P2P hand-offs promoted to first-class comm ops on the IR's comm
# lane (send at the producer tick, recv at the consumer tick,
# double-buffered in-flight comm slots) so the transfer overlaps the
# intervening compute — same compute table, residual slots and bubble as
# 1f1b, with the modeled exposed p2p collapsing to the fill staircase.
SCHEDULES: Tuple[str, ...] = (
    "gpipe", "1f1b", "1f1b_overlap", "interleaved_1f1b", "zb_h1"
)
DEFAULT_SCHEDULE = "1f1b"

# Expert dispatch modes the system understands end-to-end: the MoE layer
# executes them (``repro.models.moe``), the resource model prices them
# (capacity pays the cf padding-FLOPs tax and drops overflow tokens; ragged
# pays the sort + tile-metadata overhead but is dropless), and the planner
# enumerates them per Strategy.  Single source of truth, like SCHEDULES.
DISPATCH_MODES: Tuple[str, ...] = ("capacity", "ragged")
DEFAULT_DISPATCH = "capacity"

# EP all-to-all algorithms and chunk depths the system understands
# end-to-end: the MoE layer executes them (``repro.models.moe`` routes the
# dispatch/combine through ``repro.core.halo`` — flat collective vs the
# HALO hierarchical decomposition, monolithic vs chunked double-buffered),
# ``repro.core.comm_model`` prices them (per-phase latency + the
# chunked-overlap closed form), and the planner enumerates
# ``a2a_algo x a2a_chunks`` per Strategy.  Single source of truth, like
# SCHEDULES and DISPATCH_MODES.
A2A_ALGOS: Tuple[str, ...] = ("flat", "halo")
DEFAULT_A2A = "flat"
A2A_CHUNK_CANDIDATES: Tuple[int, ...] = (1, 2, 4, 8)

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoECfg:
    """Mixture-of-Experts FFN sub-layer configuration."""

    num_experts: int
    top_k: int
    d_ff: int  # intermediate dim of EACH expert (paper: d_ffn^MoE)
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01  # Switch-style load balancing loss
    z_loss_coef: float = 1e-3  # router z-loss
    router_dtype: str = "float32"
    # Expert dispatch: "capacity" = GShard/Tutel (E, C, d) zero-padded
    # buffers, overflow dropped; "ragged" = MegaBlocks-style sort-based
    # dropless dispatch (sorted rows + per-expert offsets, ragged grouped
    # GEMM).  Under EP, ragged still bounds the a2a payload at the
    # capacity-mode wire size, but budgets rows per *rank* instead of per
    # expert, which strictly dominates per-expert capacity on kept tokens.
    dispatch: str = DEFAULT_DISPATCH
    # Hot-expert replication channels: >0 adds a (max_replicas,) int32
    # "replicas" routing leaf (sentinel num_experts = free channel).  A
    # replicated expert's rows compute source-locally on every EP rank —
    # off the a2a wire — splitting its load across groups by token origin.
    max_replicas: int = 0
    # Router scoring: "softmax" (top-k of the softmax, renormalised) or
    # "sigmoid" (DeepSeek-V3: top-k of sigmoid scores plus a selection
    # bias, the weights being the unbiased scores of the chosen experts,
    # renormalised and times ``routed_scale``).
    scoring: str = "softmax"
    routed_scale: float = 1.0
    # Auxiliary-loss-free balancing (DeepSeek-V3 §2.1.2): after each step
    # every expert's selection bias moves by ``bias_update_speed`` towards
    # the mean load; > 0 adds the layer's int32 "router_bias" leaf, which
    # counts those moves.
    bias_update_speed: float = 0.0
    # Balance loss: per token group (Switch) or per sequence (DeepSeek-V3's
    # sequence-wise loss on normalised sigmoid scores).
    seq_aux: bool = False
    # One chip's share of a layer spread over ``ep_share`` chips: the
    # router keeps all ``num_experts`` outputs, this chip holds experts
    # [ep_rank * E/ep_share, (ep_rank + 1) * E/ep_share) and the layer
    # returns their part of the output (plus the shared experts).
    ep_share: int = 1
    ep_rank: int = 0

    def __post_init__(self):
        assert self.dispatch in DISPATCH_MODES, self.dispatch
        assert self.max_replicas >= 0, self.max_replicas
        assert self.scoring in ("softmax", "sigmoid"), self.scoring
        assert self.bias_update_speed == 0 or self.scoring == "sigmoid"
        assert self.num_experts % self.ep_share == 0, (
            self.num_experts, self.ep_share)
        assert 0 <= self.ep_rank < self.ep_share, self.ep_rank
        if self.ep_share > 1:
            # A share holds a fixed slice of the experts: no replica may
            # land here and only the dropless path computes a slice.
            assert self.max_replicas == 0, "a share holds no replicas"
            assert self.dispatch == "ragged", "a share needs ragged dispatch"

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.ep_share

    @property
    def first_held(self) -> int:
        return self.ep_rank * self.experts_held


@dataclass(frozen=True)
class SSMCfg:
    """Mamba2 (SSD — state-space duality) sub-layer configuration."""

    state_size: int = 128  # N (dstate)
    head_dim: int = 64  # P
    expand: int = 2  # d_inner = expand * d_model
    conv_width: int = 4
    chunk_size: int = 256
    n_groups: int = 1  # B/C groups (GVA)

    def num_heads(self, d_model: int) -> int:
        return (self.expand * d_model) // self.head_dim


@dataclass(frozen=True)
class MLACfg:
    """DeepSeek-V2/V3 multi-head latent attention (``q_lora_rank`` null:
    queries are projected directly).  Keys and values come from one
    latent of ``kv_lora_rank`` per token (RMS-normed, then projected up to
    every head's ``qk_nope_head_dim`` key and ``v_head_dim`` value) plus a
    ``qk_rope_head_dim`` rotary key that all heads share."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


# Per-layer block description: (mixer, ffn)
#   mixer: "attn" | "attn_local" | "mla" | "mamba"
#   ffn:   "dense" | "moe" | "none"
Block = Tuple[str, str]


@dataclass(frozen=True)
class ArchConfig:
    """A complete architecture description.

    ``block_pattern`` is tiled to cover ``num_layers`` — e.g. gemma2's
    alternating local/global attention is ``(("attn_local","dense"),
    ("attn","dense"))`` and jamba's 1:7 attention:mamba interleave with MoE
    every other layer is an 8-entry pattern.
    """

    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int  # dense FFN intermediate dim (0 if no dense FFN layers)
    vocab_size: int
    block_pattern: Tuple[Block, ...]
    moe: Optional[MoECfg] = None
    ssm: Optional[SSMCfg] = None
    mla: Optional[MLACfg] = None
    # Leading layers with a dense FFN before the tiled ``block_pattern``
    # (DeepSeek's ``first_k_dense_replace``); their mixer is the pattern's
    # first.
    first_k_dense: int = 0
    # attention details
    rope_type: str = "rope"  # rope | mrope | none
    rope_theta: float = 10_000.0
    sliding_window: Optional[int] = None  # window for "attn_local" mixers
    attn_logit_softcap: Optional[float] = None  # gemma2: 50.0
    final_logit_softcap: Optional[float] = None  # gemma2: 30.0
    tie_embeddings: bool = False
    scale_embeddings: bool = False  # gemma2: x *= sqrt(d_model)
    norm_eps: float = 1e-6
    # FFN form: "swiglu" (3 weight matrices — paper Table II n_mat=3) or
    # "gelu" (2 matrices — the paper's M10B base implies n_mat=2).
    ffn_activation: str = "swiglu"
    # modality frontend stub: None | "audio_frames" | "vision_patches".
    # Non-None => input_specs() provides precomputed (b, s, d_model)
    # embeddings instead of token ids (backbone-only scope per assignment).
    frontend: Optional[str] = None
    # True if attention cost is sub-quadratic in context (SSM / hybrid with
    # bounded-window attn) — gates the long_500k shape.
    subquadratic: bool = False
    source: str = ""  # provenance note

    # -- derived ------------------------------------------------------------

    def __post_init__(self):
        assert self.num_heads % self.num_kv_heads == 0, self.name
        body = self.num_layers - self.first_k_dense
        assert body > 0 and body % len(self.block_pattern) == 0, (
            f"{self.name}: num_layers={self.num_layers} less "
            f"first_k_dense={self.first_k_dense} is not a multiple of "
            f"pattern period {len(self.block_pattern)}"
        )
        if any(m == "mla" for m, _ in self.block_pattern):
            assert self.mla is not None and self.num_kv_heads == self.num_heads
            assert self.head_dim == self.mla.qk_head_dim, self.name

    @property
    def reps(self) -> int:
        """Repetitions of ``block_pattern`` after the dense prefix."""
        return (self.num_layers - self.first_k_dense) // len(self.block_pattern)

    @property
    def prefix_block(self) -> Block:
        return (self.block_pattern[0][0], "dense")

    @property
    def layers(self) -> Tuple[Block, ...]:
        return ((self.prefix_block,) * self.first_k_dense
                + self.block_pattern * self.reps)

    @property
    def pattern_period(self) -> int:
        return len(self.block_pattern)

    @property
    def num_moe_layers(self) -> int:
        return sum(1 for _, f in self.layers if f == "moe")

    @property
    def num_attn_layers(self) -> int:
        return sum(1 for m, _ in self.layers
                   if m.startswith("attn") or m == "mla")

    @property
    def num_mamba_layers(self) -> int:
        return sum(1 for m, _ in self.layers if m == "mamba")

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    # -- parameter accounting (exact, matches models/model.py init) --------

    def attn_params(self) -> int:
        d, hq, hkv = self.d_model, self.q_dim, self.kv_dim
        return d * hq + 2 * d * hkv + hq * d  # Wq, Wk, Wv, Wo

    def mla_params(self) -> int:
        """Wq, the latent down-projection (with the shared rope key), the
        latent's norm scale, the up-projection to keys and values, Wo."""
        m, d, H = self.mla, self.d_model, self.num_heads
        return (d * H * m.qk_head_dim
                + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                + m.kv_lora_rank
                + m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
                + H * m.v_head_dim * d)

    @property
    def n_mat(self) -> int:
        """Weight matrices per FFN (paper Table II: 3 for SwiGLU)."""
        return 3 if self.ffn_activation == "swiglu" else 2

    def dense_ffn_params(self) -> int:
        return self.n_mat * self.d_model * self.d_ff if self.d_ff else 0

    def moe_ffn_params(self) -> int:
        assert self.moe is not None
        m = self.moe
        expert = self.n_mat * self.d_model * m.d_ff
        router = self.d_model * m.num_experts
        shared = m.num_shared_experts * expert
        return m.experts_held * expert + shared + router

    def mamba_params(self) -> int:
        assert self.ssm is not None
        s = self.ssm
        d_in = s.expand * self.d_model
        nh = s.num_heads(self.d_model)
        conv_dim = d_in + 2 * s.n_groups * s.state_size
        in_proj = self.d_model * (2 * d_in + 2 * s.n_groups * s.state_size + nh)
        conv = conv_dim * s.conv_width + conv_dim
        extras = nh * 3  # A_log, D, dt_bias
        norm = d_in
        out_proj = d_in * self.d_model
        return in_proj + conv + extras + norm + out_proj

    def layer_params(self, block: Block) -> int:
        mixer, ffn = block
        p = 2 * self.d_model  # two RMSNorm scales
        if mixer.startswith("attn"):
            p += self.attn_params()
        elif mixer == "mla":
            p += self.mla_params()
        elif mixer == "mamba":
            p += self.mamba_params()
        if ffn == "dense":
            p += self.dense_ffn_params()
        elif ffn == "moe":
            p += self.moe_ffn_params()
        elif ffn == "none":
            p -= self.d_model  # only one norm when there is no FFN sub-layer
        return p

    def total_params(self) -> int:
        body = sum(self.layer_params(b) for b in self.layers)
        embed = self.vocab_size * self.d_model
        head = 0 if self.tie_embeddings else self.vocab_size * self.d_model
        return body + embed + head + self.d_model  # final norm

    def active_params(self) -> int:
        """Parameters a token's matmuls touch, with the norm scales (MoE:
        top-k + shared experts only; on a share, the held experts at their
        expected top_k * held / E routed rows a token).  An untied input
        embedding is a gather of one row, so its table does not count; a
        tied one counts once, as the head."""
        total = self.total_params()
        if not self.tie_embeddings:
            total -= self.vocab_size * self.d_model
        if self.moe is None:
            return total
        m = self.moe
        expert = self.n_mat * self.d_model * m.d_ff
        held = m.experts_held * expert
        active = m.top_k * held // m.num_experts
        return total - (held - active) * self.num_moe_layers

    # -- utilities ----------------------------------------------------------

    def padded_vocab(self, multiple: int = 256) -> int:
        v = self.vocab_size
        return ((v + multiple - 1) // multiple) * multiple

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    def share(self, chips: int, rank: int = 0) -> "ArchConfig":
        """What one of ``chips`` chips that share each layer holds: its
        ``1/chips`` of every MoE layer's experts (the router keeps its
        width) and of the vocabulary."""
        assert self.moe is not None, self.name
        return self.replace(
            name=f"{self.name}-ep{chips}" if rank == 0
            else f"{self.name}-ep{chips}r{rank}",
            vocab_size=self.vocab_size // chips,
            moe=dataclasses.replace(self.moe, ep_share=chips, ep_rank=rank,
                                    dispatch="ragged"),
        )

    def reduced(self) -> "ArchConfig":
        """A tiny same-family config for CPU smoke tests."""
        period = len(self.block_pattern)
        n_layers = period * min(2, self.reps) + self.first_k_dense
        kw = dict(
            num_layers=max(n_layers, period + self.first_k_dense),
            d_model=64,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            d_ff=128 if self.d_ff else 0,
            vocab_size=512,
            sliding_window=32 if self.sliding_window else None,
        )
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe,
                num_experts=min(self.moe.num_experts, 8),
                top_k=min(self.moe.top_k, 2),
                d_ff=64,
                num_shared_experts=min(self.moe.num_shared_experts, 1),
                ep_share=min(self.moe.ep_share, 4),
                ep_rank=0,
            )
        if self.mla is not None:
            kw.update(num_heads=2, num_kv_heads=2, head_dim=24,
                      mla=MLACfg(kv_lora_rank=16, qk_nope_head_dim=16,
                                 qk_rope_head_dim=8, v_head_dim=16))
        if self.ssm is not None:
            kw["ssm"] = dataclasses.replace(
                self.ssm, state_size=16, head_dim=16, chunk_size=32
            )
        return self.replace(name=self.name + "-reduced", **kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned shape pool for the LM family)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


TRAIN_4K = ShapeSpec("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeSpec("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeSpec("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeSpec("long_500k", 524_288, 1, "decode")

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


def shape_applicable(arch: ArchConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """long_500k requires sub-quadratic attention (SSM/hybrid)."""
    if shape.name == "long_500k" and not arch.subquadratic:
        return False, "full-attention arch: 500k dense-KV decode excluded"
    return True, ""
