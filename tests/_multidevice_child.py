"""Child process for test_multidevice.py (8 host devices)."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.core import halo
from repro.core import migration as mig
from repro.models import moe as moe_lib
from repro.models.model import LanguageModel, init_params
from repro.sharding import MeshPlan, host_mesh, make_plan, single_device_plan

RESULTS = {}


def close(a, b, atol=3e-3):
    return bool(
        np.allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=atol
        )
    )


def check_halo():
    mesh = host_mesh((1, 8, 1), ("data", "ep", "tp"))
    plan = MeshPlan(mesh=mesh, ep=8, tp=1, dp_axes=("data",))
    R, d = 3, 5
    xg = jax.random.normal(jax.random.PRNGKey(0), (64, R, d))

    def run(fn):
        return jax.shard_map(
            fn, mesh=mesh, in_specs=P("ep", None, None),
            out_specs=P("ep", None, None), check_vma=False,
        )(xg)

    flat = run(halo.flat_all_to_all)
    for g1 in (2, 4):
        h = run(lambda xl, g=g1: halo.hierarchical_all_to_all(xl, plan, g1=g))
        RESULTS[f"halo_g1_{g1}"] = close(flat, h, atol=1e-6)


def check_pipeline_and_train():
    arch = get_arch("granite-moe-3b-a800m").reduced()
    arch = arch.replace(
        moe=dataclasses.replace(arch.moe, capacity_factor=8.0,
                                aux_loss_coef=0.0)
    )
    mesh = host_mesh((2, 2, 2), ("pod", "data", "model"))
    plan_pp = make_plan(mesh, arch, pipeline_on_pod=True)
    plan_dp = make_plan(mesh, arch)
    params = init_params(arch, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(3), (8, 32), 0,
                              arch.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    with plan_pp.mesh:
        lm_dp = LanguageModel(arch, plan_dp)
        lm_pp = LanguageModel(arch, plan_pp)
        l_dp, _ = jax.jit(lm_dp.loss)(params, batch)
        l_pp, _ = jax.jit(lm_pp.loss)(params, batch)
        RESULTS["pipeline_loss_match"] = close(l_dp, l_pp, atol=1e-4)
        g_dp = jax.jit(
            jax.grad(lambda p: lm_dp.loss(p, batch)[0], allow_int=True)
        )(params)
        g_pp = jax.jit(
            jax.grad(lambda p: lm_pp.loss(p, batch)[0], allow_int=True)
        )(params)
        g_dph = jax.tree.map(lambda t: np.asarray(jax.device_get(t)), g_dp)
        g_pph = jax.tree.map(lambda t: np.asarray(jax.device_get(t)), g_pp)
        # Embedding rows absorb near-tie top-k routing flips across token
        # layouts (see check_moe_ep below) — compare them in Frobenius norm,
        # everything else element-wise.
        emb_rel = np.linalg.norm(g_dph["embed"] - g_pph["embed"]) / (
            np.linalg.norm(g_dph["embed"]) + 1e-9
        )
        errs = jax.tree.map(
            lambda a, b: float(
                np.max(np.abs(a.astype(np.float32) - b.astype(np.float32)))
            )
            if np.issubdtype(a.dtype, np.floating)
            else 0.0,
            {k: v for k, v in g_dph.items() if k != "embed"},
            {k: v for k, v in g_pph.items() if k != "embed"},
        )
        RESULTS["pipeline_grad_match"] = max(jax.tree.leaves(errs)) < 1e-3
        RESULTS["pipeline_embed_grad_match"] = emb_rel < 0.05

        # compressed p2p: lossy but close
        plan_c = make_plan(mesh, arch, pipeline_on_pod=True)
        plan_c.compress_p2p = True
        lm_c = LanguageModel(arch, plan_c)
        l_c, _ = jax.jit(lm_c.loss)(params, batch)
        RESULTS["compressed_p2p_close"] = abs(float(l_c) - float(l_dp)) < 0.1


def check_moe_ep():
    arch = get_arch("granite-moe-3b-a800m").reduced()
    arch = arch.replace(
        moe=dataclasses.replace(arch.moe, capacity_factor=16.0)
    )
    params = init_params(arch, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(3), (8, 32), 0,
                              arch.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    plan1 = single_device_plan(arch)
    with plan1.mesh:
        lm1 = LanguageModel(arch, plan1)
        l1, _ = jax.jit(lm1.loss)(params, batch)
        g1 = jax.jit(jax.grad(lambda p: lm1.loss(p, batch)[0],
                              allow_int=True))(params)

    mesh = host_mesh((2, 4), ("data", "model"))
    plan8 = make_plan(mesh, arch)  # ep=4, tp=1 over the model axis
    with plan8.mesh:
        lm8 = LanguageModel(arch, plan8)
        l8, _ = jax.jit(lm8.loss)(params, batch)
        g8 = jax.jit(jax.grad(lambda p: lm8.loss(p, batch)[0],
                              allow_int=True))(params)
    # fp32 reduction-order noise across shardings is ~3e-4 on a 6.3 loss
    RESULTS["moe_ep_fwd_match"] = close(l1, l8, atol=2e-3)
    g1h = jax.tree.map(lambda t: np.asarray(jax.device_get(t)), g1)
    g8h = jax.tree.map(lambda t: np.asarray(jax.device_get(t)), g8)
    # Near-tie top-k routing can flip for a handful of tokens across
    # sharding layouts (fp32 reduction order in the router logits) — those
    # tokens' embedding rows then receive different (both-valid) expert
    # gradients.  Compare embeddings in Frobenius norm, everything else
    # element-wise.
    emb_rel = np.linalg.norm(g1h["embed"] - g8h["embed"]) / (
        np.linalg.norm(g1h["embed"]) + 1e-9
    )
    errs = jax.tree.map(
        lambda a, b: float(
            np.max(np.abs(a.astype(np.float32) - b.astype(np.float32)))
        )
        if np.issubdtype(a.dtype, np.floating)
        else 0.0,
        {k: v for k, v in g1h.items() if k != "embed"},
        {k: v for k, v in g8h.items() if k != "embed"},
    )
    RESULTS["moe_ep_grad_match"] = (
        max(jax.tree.leaves(errs)) < 2e-3 and emb_rel < 0.05
    )

    # end-to-end sharded train step matches the single-device loss
    from repro import training
    from repro.optim import OptimizerConfig

    opt = OptimizerConfig(lr=1e-3)
    with plan8.mesh:
        lm8 = LanguageModel(arch, plan8)
        state = training.init_state(lm8, jax.random.PRNGKey(0), opt)
        step = jax.jit(training.make_train_step(lm8, opt))
        state, metrics = step(state, batch)
    with plan1.mesh:
        lm1 = LanguageModel(arch, plan1)
        state1 = training.init_state(lm1, jax.random.PRNGKey(0), opt)
        step1 = jax.jit(training.make_train_step(lm1, opt))
        state1, metrics1 = step1(state1, batch)
    RESULTS["sharded_train_matches"] = (
        abs(float(metrics["loss"]) - float(metrics1["loss"])) < 1e-3
    )


def check_a2a_chunked():
    """Chunked double-buffered EP a2a == monolithic path, bit-for-bit on
    the loss and <= 1e-5 on every gradient, for both dispatch modes,
    K that does not divide the payload (tail chunk), and halo + chunks."""
    base = get_arch("granite-moe-3b-a800m").reduced()
    mesh = host_mesh((2, 4), ("data", "model"))
    toks = jax.random.randint(jax.random.PRNGKey(3), (8, 32), 0,
                              base.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    for mode in ("capacity", "ragged"):
        arch = base.replace(
            moe=dataclasses.replace(base.moe, dispatch=mode,
                                    capacity_factor=2.0)
        )
        params = init_params(arch, jax.random.PRNGKey(0))

        def loss_grad(plan):
            with plan.mesh:
                lm = LanguageModel(arch, plan)
                l, _ = jax.jit(lm.loss)(params, batch)
                g = jax.jit(jax.grad(lambda p: lm.loss(p, batch)[0],
                                     allow_int=True))(params)
            return float(l), jax.tree.map(
                lambda t: np.asarray(jax.device_get(t)), g
            )

        l0, g0 = loss_grad(make_plan(mesh, arch))  # monolithic K=1, flat
        # K=2 (even), K=3 (tail chunk: neither capacity nor the ragged
        # wire size divides by 3), and halo composed with chunking.
        for tag, halo_on, K in (("K2", False, 2), ("K3_tail", False, 3),
                                ("halo_K2", True, 2)):
            plan = make_plan(mesh, arch, hierarchical_a2a=halo_on,
                             a2a_chunks=K)
            l1, g1 = loss_grad(plan)
            dmax = max(jax.tree.leaves(jax.tree.map(
                lambda a, b: float(np.max(np.abs(
                    a.astype(np.float32) - b.astype(np.float32)
                ))) if np.issubdtype(a.dtype, np.floating) else 0.0,
                g0, g1,
            )))
            RESULTS[f"a2a_chunked_{mode}_{tag}"] = (
                abs(l1 - l0) < 1e-5 and dmax < 1e-5
            )


def check_replication():
    """Hot-expert replication is function-preserving: the SAME arch and
    params with live replica channels (replicated experts compute
    source-locally off the a2a wire; their weights psum-broadcast over the
    EP groups, grads summed back by the psum transpose) match the
    sentinel-table oracle to <= 1e-5 on loss and every gradient, per
    dispatch mode, on the real EP mesh.  The oracle must be the same arch
    with an INACTIVE table — dropping the replicas leaf instead would
    shift every init PRNG key and change all weights."""
    base = get_arch("granite-moe-3b-a800m").reduced()
    mesh = host_mesh((2, 4), ("data", "model"))
    toks = jax.random.randint(jax.random.PRNGKey(3), (8, 32), 0,
                              base.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    for mode in ("ragged", "capacity"):
        arch = base.replace(
            moe=dataclasses.replace(base.moe, dispatch=mode,
                                    capacity_factor=8.0, max_replicas=2)
        )
        params = init_params(arch, jax.random.PRNGKey(0))  # sentinel table

        def with_live_table(p):
            blocks = []
            for blk in p["blocks"]:
                if "ffn" in blk and "replicas" in blk["ffn"]:
                    f = dict(blk["ffn"])
                    reps = f["replicas"].shape[0]
                    f["replicas"] = jnp.tile(
                        jnp.asarray([0, 3], jnp.int32), (reps, 1)
                    )
                    blk = {**blk, "ffn": f}
                blocks.append(blk)
            return {**p, "blocks": tuple(blocks)}

        plan8 = make_plan(mesh, arch)
        lm8 = LanguageModel(arch, plan8)

        def loss_grad(p):
            with plan8.mesh:
                l, _ = jax.jit(lm8.loss)(p, batch)
                g = jax.jit(jax.grad(lambda q: lm8.loss(q, batch)[0],
                                     allow_int=True))(p)
            return float(l), jax.tree.map(
                lambda t: np.asarray(jax.device_get(t)), g
            )

        l0, g0 = loss_grad(params)
        l1, g1 = loss_grad(with_live_table(params))
        dmax = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(np.max(np.abs(
                a.astype(np.float32) - b.astype(np.float32)
            ))) if np.issubdtype(a.dtype, np.floating) else 0.0,
            g0, g1,
        )))
        RESULTS[f"replication_{mode}_train_parity"] = (
            abs(l1 - l0) < 1e-5 and dmax < 1e-5
        )

        # Decode path (replicated tokens, round-robin replica ownership +
        # psum): no wire cast, so exact parity.
        ffn = jax.tree.map(lambda t: t[0], params["blocks"][0]["ffn"])
        ffn_rep = dict(ffn, replicas=jnp.asarray([0, 3], jnp.int32))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, arch.d_model))
        with plan8.mesh:
            y0, _ = jax.jit(lambda f, xx: moe_lib.moe_ffn(
                f, xx, arch, plan8, token_sharded=False))(ffn, x)
            y1, _ = jax.jit(lambda f, xx: moe_lib.moe_ffn(
                f, xx, arch, plan8, token_sharded=False))(ffn_rep, x)
        RESULTS[f"replication_{mode}_decode_parity"] = bool(
            np.max(np.abs(np.asarray(y0) - np.asarray(y1))) < 1e-5
        )


def check_migration_exactness():
    """The trainer's migration at step k is exactly ONE permutation pass:
    params and both Adam moment trees move with identical perms (bit-equal
    to a manual application — the dead-counter/recomputed-perms bug class),
    the jitted step does not recompile on the migrated state, and the loss
    trajectory is bit-identical to a run whose INIT carried the same
    permutation from step 0 (slot relabeling is bit-invariant).  Swap-only
    arch (max_replicas=0): activating replica channels changes the
    reduction route and is only 1e-5-close, never bit-equal — that path is
    pinned by check_replication instead."""
    from repro import training
    from repro.optim import OptimizerConfig
    from repro.runtime.trainer import Trainer, TrainerConfig

    base = get_arch("granite-moe-3b-a800m").reduced()
    arch = base.replace(
        moe=dataclasses.replace(base.moe, capacity_factor=8.0,
                                aux_loss_coef=0.0)
    )
    mesh = host_mesh((2, 4), ("data", "model"))
    plan8 = make_plan(mesh, arch)
    lm8 = LanguageModel(arch, plan8)
    opt = OptimizerConfig(lr=1e-3)
    moe_positions = [
        i for i, (_, f) in enumerate(arch.block_pattern) if f == "moe"
    ]

    def batch_at(s):
        rng = np.random.default_rng(s)
        toks = rng.integers(0, 4, size=(8, 32), dtype=np.int32)  # skewed
        return {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}

    def feed_loads(tr, met):
        loads = np.asarray(jax.device_get(met["expert_load"]))
        tr.load_stats.update(
            np.concatenate([loads[:, i, :] for i in range(loads.shape[1])])
        )

    k, n = 3, 6
    cfg = TrainerConfig(migrate_every=1, migrate_threshold=1.05)
    tr = Trainer(lm8, opt, cfg, log_fn=lambda s: None)
    with plan8.mesh:
        state = training.init_state(lm8, jax.random.PRNGKey(0), opt)
    losses_a = []
    perms_by_pos = {}
    tables_by_pos = {}
    for s in range(n):
        with plan8.mesh:
            state, met = tr.train_step(state, batch_at(s))
        losses_a.append(float(jax.device_get(met["loss"])))
        feed_loads(tr, met)
        if s == k - 1:
            cache_pre = tr.train_step._cache_size()
            old_state_host = jax.tree.map(
                lambda t: np.asarray(jax.device_get(t)), state
            )
            state = tr._maybe_migrate(state, 1)
            RESULTS["migration_applied"] = bool(
                tr.migrations and tr.migrations[-1]["applied"]
            )
            # Capture what the controller did and replay it by hand on the
            # pre-migration host copy: params AND m AND v must match the
            # controller's output bit-for-bit.
            exact = True
            for pos in moe_positions:
                old_a = old_state_host["params"]["blocks"][pos]["ffn"]["assignment"]
                new_a = np.asarray(
                    state["params"]["blocks"][pos]["ffn"]["assignment"]
                )
                perms = np.stack([
                    mig.permutation_for(old_a[r], new_a[r])
                    for r in range(old_a.shape[0])
                ])
                perms_by_pos[pos] = perms
                tables_by_pos[pos] = {"assignment": new_a}
                for tree in ("params", "m", "v"):
                    want = mig.apply_migration_to_tree(
                        dict(old_state_host[tree]["blocks"][pos]["ffn"]),
                        perms,
                    )
                    got = state[tree]["blocks"][pos]["ffn"]
                    for key in mig.EXPERT_PARAM_KEYS:
                        if key not in want:
                            continue
                        exact &= bool(np.array_equal(
                            np.asarray(want[key]),
                            np.asarray(jax.device_get(got[key])),
                        ))
            RESULTS["migration_moments_exact"] = exact
    RESULTS["migration_no_recompile"] = (
        tr.train_step._cache_size() == cache_pre
    )

    # Run B: the captured permutation baked in at init, no migration.
    with plan8.mesh:
        state_b = training.init_state(lm8, jax.random.PRNGKey(0), opt)
    blocks = {t: list(state_b[t]["blocks"]) for t in ("params", "m", "v")}
    for pos, perms in perms_by_pos.items():
        for t in ("params", "m", "v"):
            blk = dict(blocks[t][pos])
            blk["ffn"] = mig.apply_migration_to_tree(dict(blk["ffn"]), perms)
            if t == "params":
                blk["ffn"]["assignment"] = jnp.asarray(
                    tables_by_pos[pos]["assignment"]
                )
            blocks[t][pos] = blk
    state_b = {
        **state_b,
        **{t: {**state_b[t], "blocks": tuple(blocks[t])}
           for t in ("params", "m", "v")},
    }
    tr_b = Trainer(lm8, opt, TrainerConfig(migrate_every=10**9),
                   log_fn=lambda s: None)
    losses_b = []
    for s in range(n):
        with plan8.mesh:
            state_b, met = tr_b.train_step(state_b, batch_at(s))
        losses_b.append(float(jax.device_get(met["loss"])))
    RESULTS["migration_trajectory_bitexact"] = losses_a == losses_b


if __name__ == "__main__":
    assert len(jax.devices()) == 8, jax.devices()
    check_halo()
    check_pipeline_and_train()
    check_moe_ep()
    check_a2a_chunked()
    check_replication()
    check_migration_exactness()
    print("RESULTS " + json.dumps({k: bool(v) for k, v in RESULTS.items()}))
