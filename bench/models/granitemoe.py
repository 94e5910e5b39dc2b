"""The ``granitemoe`` model family: its weight layout, its plain float32
loss, its active matmul parameters, and the map onto the program's
parameter tree.

``bench/weights.py`` loads this file for a configuration whose
``model_type`` is ``granitemoe``; another family is another file here.
Nothing of the program is imported.

Equations (IBM Granite 3.0 MoE, with the configuration's multipliers):
token embedding times ``embedding_multiplier``; RMSNorm with scale
``1 + w``; GQA attention with rotary embeddings on the two halves of each
head, causal, scores scaled by ``attention_multiplier``; residual branches
times ``residual_multiplier``; a float32 softmax router with top-k weights
renormalised to sum to 1; SwiGLU experts, every token's top-k computed
densely (each expert over all tokens, weighted by its routing weight, zero
where it was not chosen), which is exact and dropless; a tied,
vocabulary-padded head divided by ``logits_scaling`` (padded logits
masked); mean cross-entropy; per layer the Switch load-balancing loss
``E * sum_e f_e P_e * coef`` (per token group, averaged over the groups,
as ``aux_loss_groups`` says) and the router z-loss ``mean(lse^2) * coef``.
Memory is bounded by rematerialising each layer, each key/value head's
attention and each expert, and by chunking the head over the sequence.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

# Leaf order fixes each leaf's key (``bench/weights.py``).
NAMES = ("embed", "final_norm", "attn_norm", "wq", "wk", "wv", "wo",
         "ffn_norm", "router", "w_gate", "w_up", "w_down")
LAYER_LEAVES = NAMES[2:]
HEAD_CHUNK = 512


def dims(cfg: Dict) -> Dict[str, int]:
    d = cfg["hidden_size"]
    hd = cfg["assumed"]["head_dim"]
    pad = cfg["assumed"]["vocab_pad_multiple"]
    v = cfg["vocab_size"]
    return {
        "d": d, "hd": hd, "H": cfg["num_attention_heads"],
        "KV": cfg["num_key_value_heads"], "E": cfg["num_local_experts"],
        "k": cfg["num_experts_per_tok"], "f": cfg["intermediate_size"],
        "L": cfg["num_hidden_layers"], "V": v, "VP": -(-v // pad) * pad,
    }


def shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    n = dims(cfg)
    d, L, E, f = n["d"], n["L"], n["E"], n["f"]
    hq, hkv = n["H"] * n["hd"], n["KV"] * n["hd"]
    return {
        "embed": (n["VP"], d), "final_norm": (d,),
        "attn_norm": (L, d), "wq": (L, d, hq), "wk": (L, d, hkv),
        "wv": (L, d, hkv), "wo": (L, hq, d), "ffn_norm": (L, d),
        "router": (L, d, E), "w_gate": (L, E, d, f), "w_up": (L, E, d, f),
        "w_down": (L, E, f, d),
    }


def active_matmul_params(cfg: Dict) -> int:
    """Attention projections, router, the top-k experts and the tied head
    as a matmul (the embedding gather is no matmul)."""
    n = dims(cfg)
    d, hq, hkv = n["d"], n["H"] * n["hd"], n["KV"] * n["hd"]
    attn = d * hq + 2 * d * hkv + hq * d
    router = d * n["E"]
    experts = n["k"] * 3 * d * n["f"]
    return n["L"] * (attn + router + experts) + n["V"] * d


def _rms(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (b, s, ..., hd); rotate the two halves of the last axis."""
    import jax.numpy as jnp

    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    shape = (1, s) + (1,) * (x.ndim - 3) + (hd // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(p, tokens, labels, cfg: Dict, dot, dot_w):
    """Total training loss and ``(ce, aux, z)`` for one batch.  ``dot`` is
    the einsum of activations with activations and of the router,
    ``dot_w`` that of a weight matmul (``bench/reference.py``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = dims(cfg)
    tr = cfg["training"]
    eps = cfg["rms_norm_eps"]
    res = cfg["residual_multiplier"]
    b, s = tokens.shape
    d, hd, H, KV, E, k = n["d"], n["hd"], n["H"], n["KV"], n["E"], n["k"]
    G = H // KV
    T = b * s
    groups = max(min(tr["aux_loss_groups"], b), 1)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def head_attn(_, xs):
        qh, kh, vh = xs  # (b, s, G, hd), (b, s, hd), (b, s, hd)
        sc = dot("bqgd,bkd->bgqk", qh, kh) * cfg["attention_multiplier"]
        sc = jnp.where(causal, sc, -1e30)
        return None, dot("bgqk,bkd->bqgd", jax.nn.softmax(sc, axis=-1), vh)

    def layer(x, lp):
        h = _rms(x, lp["attn_norm"], eps)
        q = _rope(dot_w("bsd,dk->bsk", h, lp["wq"]).reshape(b, s, KV, G, hd),
                  cfg["rope_theta"])
        kk = _rope(dot_w("bsd,dk->bsk", h, lp["wk"]).reshape(b, s, KV, hd),
                   cfg["rope_theta"])
        vv = dot_w("bsd,dk->bsk", h, lp["wv"]).reshape(b, s, KV, hd)
        _, o = lax.scan(jax.checkpoint(head_attn), None,
                        (q.transpose(2, 0, 1, 3, 4), kk.transpose(2, 0, 1, 3),
                         vv.transpose(2, 0, 1, 3)))
        o = o.transpose(1, 2, 0, 3, 4).reshape(b, s, H * hd)
        x = x + res * dot_w("bsk,kd->bsd", o, lp["wo"])

        h = _rms(x, lp["ffn_norm"], eps).reshape(T, d)
        logits = dot("td,de->te", h, lp["router"])
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = lax.top_k(probs, k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        onehot = jax.nn.one_hot(top_i, E, dtype=jnp.float32)  # (T, k, E)
        comb = jnp.einsum("tk,tke->te", top_w, onehot)
        cnt = lax.stop_gradient(onehot.sum(1)).reshape(groups, -1, E).sum(1)
        pm = probs.reshape(groups, -1, E).mean(1)
        aux = jnp.mean(E * jnp.sum(cnt / (T // groups * k) * pm, axis=-1))
        aux = aux * tr["router_aux_loss_coef"]
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        z = z * tr["router_z_loss_coef"]

        def expert(acc, xs):
            wg, wu, wd, ce = xs
            a = jax.nn.silu(dot_w("td,df->tf", h, wg)) * dot_w(
                "td,df->tf", h, wu)
            return acc + ce[:, None] * dot_w("tf,fd->td", a, wd), None

        y, _ = lax.scan(jax.checkpoint(expert), jnp.zeros((T, d), jnp.float32),
                        (lp["w_gate"], lp["w_up"], lp["w_down"], comb.T))
        return x + res * y.reshape(b, s, d), (aux, z)

    x = p["embed"][tokens] * cfg["embedding_multiplier"]
    lps = {name: p[name] for name in LAYER_LEAVES}
    x, (aux, z) = lax.scan(jax.checkpoint(layer), x, lps)

    h = _rms(x, p["final_norm"], eps)
    c = min(HEAD_CHUNK, s)
    nc = s // c
    valid = jnp.arange(n["VP"]) < n["V"]

    def head(tot, xs):
        hc, lc = xs
        lg = dot_w("bcd,vd->bcv", hc, p["embed"]) / cfg["logits_scaling"]
        lg = jnp.where(valid, lg, -1e30)
        ll = jnp.take_along_axis(lg, lc[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(jax.nn.logsumexp(lg, axis=-1) - ll), None

    tot, _ = lax.scan(
        jax.checkpoint(head), jnp.float32(0.0),
        (h.reshape(b, nc, c, d).transpose(1, 0, 2, 3),
         labels.reshape(b, nc, c).transpose(1, 0, 2)),
    )
    ce = tot / T
    return ce + aux.sum() + z.sum(), (ce, aux.sum(), z.sum())


def to_flat(params) -> Dict:
    """The program's parameter tree -> this family's flat names."""
    (blk,) = params["blocks"]  # one (attention, MoE) block, stacked
    mix, ffn = blk["mixer"], blk["ffn"]
    return {
        "embed": params["embed"], "final_norm": params["final_norm"],
        "attn_norm": blk["norm_mixer"], "wq": mix["wq"], "wk": mix["wk"],
        "wv": mix["wv"], "wo": mix["wo"], "ffn_norm": blk["norm_ffn"],
        "router": ffn["w_router"], "w_gate": ffn["w_gate"],
        "w_up": ffn["w_up"], "w_down": ffn["w_down"],
    }


def from_flat(flat: Dict, like) -> Dict:
    """The flat weights in the program's tree; leaves the flat layout does
    not hold (the expert routing table) are taken from ``like``."""
    (blk,) = like["blocks"]
    return {
        "embed": flat["embed"], "final_norm": flat["final_norm"],
        "blocks": ({
            "norm_mixer": flat["attn_norm"],
            "mixer": {k: flat[k] for k in ("wq", "wk", "wv", "wo")},
            "norm_ffn": flat["ffn_norm"],
            "ffn": {**blk["ffn"], "w_router": flat["router"],
                    "w_gate": flat["w_gate"], "w_up": flat["w_up"],
                    "w_down": flat["w_down"]},
        },),
    }
