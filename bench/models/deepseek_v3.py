"""The ``deepseek_v3`` model family: its weight layout, its plain float32
loss, its active matmul parameters, the operation counts of its attention
core, and the map onto the program's parameter tree.

``bench/weights.py`` loads this file for a configuration whose
``model_type`` is ``deepseek_v3``.  Nothing of the program is imported.

Equations (DeepSeek-V3, arXiv:2412.19437, as Moonlight's config sets them,
at one chip's share of an expert-parallel deployment):

- token embedding; RMSNorm with scale ``1 + w``;
- multi-head latent attention with ``q_lora_rank`` null, expanded (not
  absorbed): q = h Wq per head [nope | rope]; the latent h W_kv_a splits
  into c (``kv_lora_rank``, RMS-normed) and one rope key all heads share;
  c W_kv_b gives each head's nope key and value; causal softmax of
  q.k / sqrt(nope + rope); RoPE on the rope dims by rotating their two
  halves (the published code interleaves them: a fixed permutation of
  those weight columns, ``assumed.rope``);
- the first ``first_k_dense_replace`` layers: a dense SwiGLU FFN of
  ``intermediate_size``;
- the other layers: a float32 sigmoid router over all
  ``share.n_routed_experts`` experts; the top ``num_experts_per_tok`` of
  score + selection bias choose, the unbiased scores of the chosen weigh,
  divided by their sum (+1e-20) and times ``routed_scaling_factor``; the
  ``n_routed_experts`` held here (ids ``share.rank * held`` on) computed
  densely per expert, zero where not chosen, so what absent experts would
  add is left out; plus ``n_shared_experts`` shared experts as one SwiGLU
  of their summed width;
- the sequence-wise balance loss per row, alpha sum_e f_e P_e with f_e =
  E/(k s) x the row's rows sent to e and P_e the mean score normalised over
  the experts (``assumed.seq_aux_alpha``); no z-loss;
- an untied, vocabulary-padded head (padded logits masked), mean
  cross-entropy.

The selection bias is no weight: ``loss_fn`` takes it (zero by default)
and returns each MoE layer's expert loads, and ``bias_update`` is the
step's move of it (``assumed.bias_update_speed``).  ``bench/reference.py``
calls ``loss_fn`` without a bias, so its steps keep the bias at zero.

Memory is bounded by taking each row of the batch in turn (the loss is a
sum over rows; the row is rematerialised), each layer, each head's
attention and each expert rematerialised, and the head chunked over the
sequence.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

# Leaf order fixes each leaf's key (``bench/weights.py``).
NAMES = ("embed", "final_norm", "lm_head",
         "attn_norm", "wq", "w_kv_a", "kv_norm", "w_kv_b", "wo", "ffn_norm",
         "dense_gate", "dense_up", "dense_down",
         "router", "w_gate", "w_up", "w_down",
         "shared_gate", "shared_up", "shared_down")
LAYER_LEAVES = NAMES[3:]
ATTN_LEAVES = NAMES[3:10]  # every layer
DENSE_LEAVES = NAMES[10:13]  # the leading dense layers
MOE_LEAVES = NAMES[13:]  # the MoE layers
HEAD_CHUNK = 512


def dims(cfg: Dict) -> Dict[str, int]:
    pad = cfg["assumed"]["vocab_pad_multiple"]
    v = cfg["vocab_size"]
    L, K = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    return {
        "d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "dn": dn, "dr": dr, "dv": dv, "r": cfg["kv_lora_rank"],
        # hd: the mean of the q/k and v head sizes, which makes
        # 12 L H hd s (``bench/flops.py``) exact for attention.
        "hd": (dn + dr + dv) // 2,
        "L": L, "K": K, "Lm": L - K,
        "E": cfg["share"]["n_routed_experts"], "held": cfg["n_routed_experts"],
        "first": cfg["share"]["rank"] * cfg["n_routed_experts"],
        "k": cfg["num_experts_per_tok"], "f": cfg["moe_intermediate_size"],
        "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "fd": cfg["intermediate_size"], "V": v, "VP": -(-v // pad) * pad,
    }


def shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    n = dims(cfg)
    d, L, K, Lm, H = n["d"], n["L"], n["K"], n["Lm"], n["H"]
    hq = H * (n["dn"] + n["dr"])
    return {
        "embed": (n["VP"], d), "final_norm": (d,), "lm_head": (d, n["VP"]),
        "attn_norm": (L, d), "wq": (L, d, hq),
        "w_kv_a": (L, d, n["r"] + n["dr"]), "kv_norm": (L, n["r"]),
        "w_kv_b": (L, n["r"], H * (n["dn"] + n["dv"])),
        "wo": (L, H * n["dv"], d), "ffn_norm": (L, d),
        "dense_gate": (K, d, n["fd"]), "dense_up": (K, d, n["fd"]),
        "dense_down": (K, n["fd"], d),
        "router": (Lm, d, n["E"]),
        "w_gate": (Lm, n["held"], d, n["f"]),
        "w_up": (Lm, n["held"], d, n["f"]),
        "w_down": (Lm, n["held"], n["f"], d),
        "shared_gate": (Lm, d, n["fs"]), "shared_up": (Lm, d, n["fs"]),
        "shared_down": (Lm, n["fs"], d),
    }


def active_matmul_params(cfg: Dict) -> int:
    """Attention projections, the dense FFN, router, shared experts, the
    held experts at the rows a token is expected to send them (top-k x
    held / E, the routed experts' k x 8/64 = 0.75 at the cell's share),
    and the head (the embedding gather is no matmul).  The latent's norm
    scale (``kv_lora_rank`` a layer, 6 x 2,560 operations a token of 2.9
    G at the cell) is counted with them, as the program's
    ``ArchConfig.active_params`` counts it."""
    n = dims(cfg)
    d, H = n["d"], n["H"]
    attn = (d * H * (n["dn"] + n["dr"]) + d * (n["r"] + n["dr"]) + n["r"]
            + n["r"] * H * (n["dn"] + n["dv"]) + H * n["dv"] * d)
    dense = 3 * d * n["fd"]
    held = n["k"] * n["held"] * 3 * d * n["f"] // n["E"]
    moe = d * n["E"] + 3 * d * n["fs"] + held
    return n["L"] * attn + n["K"] * dense + n["Lm"] * moe + n["V"] * d


def mla_core_calls(cfg: Dict, batch: int, seq: int
                   ) -> List[Tuple[str, float, float]]:
    """``[(kind, operations, bytes)]`` that one step's causal attention
    cores need over all layers, as the flash kernels run them: the forward
    (q.k over the causal half at the q/k head, p.v at the v head) and the
    fused backward (dv, dp, dq, dk: twice the forward).  Recomputation is
    not counted.  Bytes are the least traffic in bf16 (q, k, v, o once,
    their gradients once) and the fp32 log-sum-exp per row and head."""
    n = dims(cfg)
    H, dqk, dv = n["H"], n["dn"] + n["dr"], n["dv"]
    rows = float(batch * seq * H)  # (token, head) rows
    causal = float(seq) / 2  # keys a query row sees, on average
    fwd = 2 * rows * causal * (dqk + dv)
    io = rows * (2 * dqk + 2 * dv) * 2  # q, k, v, o in bf16
    lse = rows * 4
    return [(kind, fl * n["L"], by * n["L"]) for kind, fl, by in (
        ("fwd", fwd, io + lse),
        # reads q, k, v, o, do, lse; writes dq, dk, dv
        ("bwd", 2 * fwd, io + rows * dv * 2 + lse + rows * (2 * dqk + dv) * 2),
    )]


def _rms(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (s, ..., dr); rotate the two halves of the last axis."""
    import jax.numpy as jnp

    s, dr = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    shape = (s,) + (1,) * (x.ndim - 2) + (dr // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, wg, wu, wd, dot_w):
    import jax

    return dot_w("sf,fd->sd", jax.nn.silu(dot_w("sd,df->sf", h, wg))
                 * dot_w("sd,df->sf", h, wu), wd)


def mla(h, lp, cfg: Dict, dot, dot_w):
    """One row's latent attention (no residual): ``h`` (s, d) normed,
    ``lp`` the layer's wq, w_kv_a, kv_norm, w_kv_b, wo."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = dims(cfg)
    s = h.shape[0]
    H, dn, dr, dv, r = n["H"], n["dn"], n["dr"], n["dv"], n["r"]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def head_attn(_, xs):
        qh, kh, vh = xs  # (s, dn + dr), (s, dn + dr), (s, dv)
        sc = dot("qd,kd->qk", qh, kh) / math.sqrt(dn + dr)
        sc = jnp.where(causal, sc, -1e30)
        return None, dot("qk,kd->qd", jax.nn.softmax(sc, axis=-1), vh)

    q = dot_w("sd,dk->sk", h, lp["wq"]).reshape(s, H, dn + dr)
    kv_a = dot_w("sd,dk->sk", h, lp["w_kv_a"])
    c = _rms(kv_a[:, :r], lp["kv_norm"], cfg["rms_norm_eps"])
    kv = dot_w("sc,ck->sk", c, lp["w_kv_b"]).reshape(s, H, dn + dv)
    k_rope = _rope(kv_a[:, r:], cfg["rope_theta"])  # (s, dr), all heads
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg["rope_theta"])],
                        -1)
    kk = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, None], (s, H, dr))], -1)
    _, o = lax.scan(jax.checkpoint(head_attn), None,
                    (q.transpose(1, 0, 2), kk.transpose(1, 0, 2),
                     kv[..., dn:].transpose(1, 0, 2)))
    return dot_w("sk,kd->sd", o.transpose(1, 0, 2).reshape(s, H * dv),
                 lp["wo"])


def moe(h, lp, bias, cfg: Dict, dot, dot_w):
    """One row's MoE FFN at the share (no residual): ``h`` (s, d) normed,
    ``bias`` (E,).  Returns (the held and shared experts' output, the
    row's balance loss, the (E,) counts of chosen rows)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = dims(cfg)
    s, d = h.shape
    E, k = n["E"], n["k"]
    scores = jax.nn.sigmoid(dot("sd,de->se", h, lp["router"]))
    _, top_i = lax.top_k(lax.stop_gradient(scores + bias), k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    top_w = top_w * cfg["routed_scaling_factor"]
    onehot = jax.nn.one_hot(top_i, E, dtype=jnp.float32)  # (s, k, E)
    comb = jnp.einsum("sk,ske->se", top_w, onehot)
    cnt = lax.stop_gradient(onehot.sum((0, 1)))  # (E,)
    norm = scores / jnp.sum(scores, -1, keepdims=True)
    aux = cfg["assumed"]["seq_aux_alpha"] * jnp.sum(
        cnt * E / (k * s) * jnp.mean(norm, 0))

    def expert(acc, xs):
        wg, wu, wd, ce = xs
        return acc + ce[:, None] * _swiglu(h, wg, wu, wd, dot_w), None

    held = lax.dynamic_slice_in_dim(comb, n["first"], n["held"], axis=1)
    y, _ = lax.scan(jax.checkpoint(expert), jnp.zeros((s, d), jnp.float32),
                    (lp["w_gate"], lp["w_up"], lp["w_down"], held.T))
    y = y + _swiglu(h, lp["shared_gate"], lp["shared_up"], lp["shared_down"],
                    dot_w)
    return y, aux, cnt


def loss_fn(p, tokens, labels, cfg: Dict, dot, dot_w, bias=None):
    """Total training loss and ``(ce, aux, z, loads)`` for one batch:
    ``loads`` (MoE layers, E) counts each expert's chosen rows.  ``bias``
    (MoE layers, E): the routers' selection bias, zero by default.  ``dot``
    is the einsum of activations with activations and of the router,
    ``dot_w`` that of a weight matmul (``bench/reference.py``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = dims(cfg)
    eps = cfg["rms_norm_eps"]
    b, s = tokens.shape
    d, E = n["d"], n["E"]
    if bias is None:
        bias = jnp.zeros((n["Lm"], E), jnp.float32)

    def dense_layer(x, lp):
        x = x + mla(_rms(x, lp["attn_norm"], eps), lp, cfg, dot, dot_w)
        h = _rms(x, lp["ffn_norm"], eps)
        return x + _swiglu(h, lp["dense_gate"], lp["dense_up"],
                           lp["dense_down"], dot_w), None

    def moe_layer(x, xs):
        lp, bl = xs
        x = x + mla(_rms(x, lp["attn_norm"], eps), lp, cfg, dot, dot_w)
        y, aux, cnt = moe(_rms(x, lp["ffn_norm"], eps), lp, bl, cfg, dot,
                          dot_w)
        return x + y, (aux, cnt)

    attn = {nm: p[nm] for nm in ATTN_LEAVES}
    K = n["K"]
    dense_p = {**{nm: v[:K] for nm, v in attn.items()},
               **{nm: p[nm] for nm in DENSE_LEAVES}}
    moe_p = {**{nm: v[K:] for nm, v in attn.items()},
             **{nm: p[nm] for nm in MOE_LEAVES}}
    c = min(HEAD_CHUNK, s)
    valid = jnp.arange(n["VP"]) < n["V"]

    def head(tot, xs):
        hc, lc = xs
        lg = dot_w("cd,dv->cv", hc, p["lm_head"])
        lg = jnp.where(valid, lg, -1e30)
        ll = jnp.take_along_axis(lg, lc[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(jax.nn.logsumexp(lg, axis=-1) - ll), None

    def row(carry, xs):
        tok, lab = xs
        x = p["embed"][tok]
        x, _ = lax.scan(jax.checkpoint(dense_layer), x, dense_p)
        x, (aux, cnt) = lax.scan(jax.checkpoint(moe_layer), x, (moe_p, bias))
        h = _rms(x, p["final_norm"], eps)
        ce, _ = lax.scan(jax.checkpoint(head), jnp.float32(0.0),
                         (h.reshape(s // c, c, d), lab.reshape(s // c, c)))
        ce_t, aux_t, cnt_t = carry
        return (ce_t + ce, aux_t + aux.sum(), cnt_t + cnt), None

    zero = (jnp.float32(0.0), jnp.float32(0.0),
            jnp.zeros((n["Lm"], E), jnp.float32))
    (ce, aux, loads), _ = lax.scan(jax.checkpoint(row), zero, (tokens, labels))
    ce, aux = ce / (b * s), aux / b
    return ce + aux, (ce, aux, jnp.float32(0.0), loads)


def bias_update(bias, loads, cfg: Dict):
    """The selection bias after a step with expert ``loads`` (MoE layers,
    E): each moves by ``bias_update_speed`` towards the mean load."""
    import jax.numpy as jnp

    gamma = cfg["assumed"]["bias_update_speed"]
    return bias + gamma * jnp.sign(
        jnp.mean(loads, axis=-1, keepdims=True) - loads)


def to_flat(params) -> Dict:
    """The program's parameter tree -> this family's flat names."""
    import jax.numpy as jnp

    (pre,), (blk,) = params["prefix"], params["blocks"]

    def both(get):
        return jnp.concatenate([get(pre), get(blk)], axis=0)

    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "lm_head": params["lm_head"],
           "attn_norm": both(lambda t: t["norm_mixer"]),
           "ffn_norm": both(lambda t: t["norm_ffn"])}
    for nm in ("wq", "w_kv_a", "kv_norm", "w_kv_b", "wo"):
        out[nm] = both(lambda t, nm=nm: t["mixer"][nm])
    dense, ffn = pre["ffn"], blk["ffn"]
    out.update(dense_gate=dense["w_gate"], dense_up=dense["w_up"],
               dense_down=dense["w_down"], router=ffn["w_router"],
               w_gate=ffn["w_gate"], w_up=ffn["w_up"], w_down=ffn["w_down"],
               shared_gate=ffn["w_shared_gate"],
               shared_up=ffn["w_shared_up"],
               shared_down=ffn["w_shared_down"])
    return out


def from_flat(flat: Dict, like) -> Dict:
    """The flat weights in the program's tree; leaves the flat layout does
    not hold (the routing table, the selection bias) come from ``like``."""
    (pre,), (blk,) = like["prefix"], like["blocks"]
    K = flat["dense_gate"].shape[0]

    def part(sl):
        return {"norm_mixer": flat["attn_norm"][sl],
                "mixer": {nm: flat[nm][sl] for nm in
                          ("wq", "w_kv_a", "kv_norm", "w_kv_b", "wo")},
                "norm_ffn": flat["ffn_norm"][sl]}

    return {
        "embed": flat["embed"], "final_norm": flat["final_norm"],
        "lm_head": flat["lm_head"],
        "prefix": ({**part(slice(None, K)),
                    "ffn": {**pre["ffn"], "w_gate": flat["dense_gate"],
                            "w_up": flat["dense_up"],
                            "w_down": flat["dense_down"]}},),
        "blocks": ({**part(slice(K, None)),
                    "ffn": {**blk["ffn"], "w_router": flat["router"],
                            "w_gate": flat["w_gate"], "w_up": flat["w_up"],
                            "w_down": flat["w_down"],
                            "w_shared_gate": flat["shared_gate"],
                            "w_shared_up": flat["shared_up"],
                            "w_shared_down": flat["shared_down"]}},),
    }
