"""End-to-end serving driver: planner-picked strategy -> continuous batching.

Examples (CPU container — reduced configs; on TPU drop --reduced):

    PYTHONPATH=src python -m repro.launch.serve \
        --arch granite-moe-3b-a800m --reduced --requests 8 --max-new 8

    PYTHONPATH=src python -m repro.launch.serve \
        --arch granite-moe-3b-a800m --reduced --dispatch capacity --slo-ms 30

The driver: consults the serving planner for the production-scale strategy
report (EP x TP x batch x dispatch under the latency SLO), binds the
planner's dispatch mode and batch width into the local engine, serves a
batch of synthetic mixed-length requests with continuous batching, and
runs a decode parity probe against the uncached forward (ragged decode
must match to 1e-5 of the largest logit — the dropless path recomputes
nothing and drops nothing, so the paged incremental forward is exact up
to fp32 summation order).
"""

from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-3b-a800m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep this many layers (whole periods of the "
                         "block pattern), e.g. to fit one chip; default: "
                         "the config's depth")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--chips", type=int, default=16,
                    help="fleet size for the production planner report")
    ap.add_argument("--slo-ms", type=float, default=20.0,
                    help="per-token decode latency SLO for the planner")
    ap.add_argument("--context", type=int, default=2048,
                    help="planner mean live context")
    ap.add_argument("--prefill-len", type=int, default=1024,
                    help="planner mean prompt length")
    ap.add_argument("--dispatch", default=None,
                    help="MoE expert dispatch (capacity|ragged); default: "
                         "the serving planner's ranked choice")
    ap.add_argument("--max-seqs", type=int, default=4,
                    help="local engine decode width cap")
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None,
                    help="write the engine's structured event stream as "
                         "JSONL here; a Chrome trace_event view lands next "
                         "to it as <path>.trace.json and a decode drift "
                         "report prints at end of run")
    args = ap.parse_args()

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import compile_cache
    from repro.configs import get_arch
    from repro.core import planner
    from repro.core.platform import TPU_V5E
    from repro.models.model import LanguageModel, init_params
    from repro.serving import Engine, Request, ServeConfig
    from repro.sharding import single_device_plan

    compile_cache.enable()
    dev = jax.devices()[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")
    arch = get_arch(args.arch)

    # Production serving-strategy report (what this arch needs at scale).
    best = planner.best_serving_strategy(
        arch, TPU_V5E, args.chips,
        context=args.context, prefill_len=args.prefill_len,
        slo_ms=args.slo_ms,
    )
    if best is not None:
        print(f"[planner] serving strategy for {args.arch} "
              f"@{args.chips}xv5e under {args.slo_ms:.0f}ms/token SLO:")
        print("          " + best.describe())
    else:
        print(f"[planner] no feasible serving strategy for {args.arch} "
              f"@{args.chips}xv5e under {args.slo_ms:.0f}ms/token")

    if args.reduced:
        arch = arch.reduced()
    if args.layers:
        arch = arch.replace(num_layers=args.layers)

    # Bind the planner's choices into the local run: dispatch mode into
    # MoECfg (the MoE layer executes whatever the config says), batch
    # width into the engine (capped for the CPU mesh).
    max_seqs = args.max_seqs
    if best is not None:
        max_seqs = max(1, min(best.batch, args.max_seqs))
    if arch.moe is not None:
        dispatch = args.dispatch or (
            best.dispatch if best is not None else arch.moe.dispatch
        )
        if dispatch != arch.moe.dispatch:
            arch = arch.replace(
                moe=dataclasses.replace(arch.moe, dispatch=dispatch)
            )
        print(f"[serve] moe dispatch: {arch.moe.dispatch}")

    plan = single_device_plan(arch)
    lm = LanguageModel(arch, plan)
    # Size the block table for the longest sequence this run can produce
    # (prompts are drawn from [3, 32] below) — submit() rejects requests
    # that outgrow the table or the pool.
    max_total = 32 + args.max_new
    cfg = ServeConfig(
        max_seqs=max_seqs,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        max_blocks_per_seq=max(-(-max_total // args.block_size), 4),
    )
    print(f"[engine] max_seqs={cfg.max_seqs} block_size={cfg.block_size} "
          f"num_blocks={cfg.num_blocks}")

    rng = np.random.default_rng(args.seed)
    lengths = rng.integers(3, 33, size=args.requests)
    with plan.mesh:
        params = init_params(arch, jax.random.PRNGKey(args.seed))
        engine = Engine(lm, params, cfg)
        if args.metrics_out:
            # Tee the engine's always-on event stream (the same one its
            # deterministic tuple trace is a view of) to a JSONL log.
            from repro import obs

            engine.telemetry.sinks.append(obs.JsonlSink(args.metrics_out))
        reqs = [
            Request(
                rid=i,
                tokens=rng.integers(0, arch.vocab_size, size=int(n)),
                max_new_tokens=args.max_new,
            )
            for i, n in enumerate(lengths)
        ]
        t0 = time.perf_counter()
        out = engine.run(reqs)
        dt = time.perf_counter() - t0
        n_preempt = sum(1 for e in engine.trace if e[0] == "preempt")
        print(f"[serve] {len(out)}/{len(reqs)} requests finished in "
              f"{engine.step_no} steps ({dt:.1f}s wall, jit incl.); "
              f"{engine.decoded_tokens} decode tokens over "
              f"{engine.decode_steps} decode steps, {n_preempt} preemptions")
        for rid in sorted(out)[:4]:
            print(f"  req {rid} (prompt {lengths[rid]:2d}): {out[rid]}")

        if args.metrics_out:
            _telemetry_reports(args, arch, engine, max_seqs)

        # -- decode parity probe vs the uncached forward -------------------
        # Replay request 0's sequence through the paged prefill + decode
        # steps with exact shapes and compare every decode step's logits to
        # the full no-cache forward.  Ragged decode recomputes nothing and
        # drops nothing, so it must agree up to fp32 summation order
        # (asserted); capacity decode re-derives its slot budget from T=1
        # (vs the forward's full-T), so under routing skew its drops may
        # differ — reported for the bound mode, asserted for ragged.  Both
        # sides run at "highest" matmul precision: a TPU's default runs
        # fp32 matmuls in bf16 passes, which the two sides would round
        # differently.  Returns (max |dlogits|, max |logit|, steps).
        def parity_probe(lm_p, seq, plen):
            from repro.serving.kv_cache import BlockPool

            with jax.default_matmul_precision("highest"):
                layout = cfg.layout()
                pool = BlockPool(layout)
                slot = pool.admit(plen)
                cache = lm_p.init_paged_cache(layout, dtype=jnp.float32)
                logits, cache = jax.jit(lm_p.prefill_paged)(
                    params, {"tokens": jnp.asarray(seq[None, :plen])}, cache,
                    jnp.asarray(pool.block_table[slot][None]),
                    jnp.asarray([plen], jnp.int32),
                )
                ref, _, _ = jax.jit(lm_p.forward)(
                    params, {"tokens": jnp.asarray(seq[None])}
                )
                errs = [float(jnp.abs(logits[0] - ref[0, plen - 1]).max())]
                # Largest real logit (the vocab padding holds -1e30).
                scale = float(jnp.abs(ref[0, :, : arch.vocab_size]).max())
                decode = jax.jit(lm_p.decode_step_paged)
                for i, tok in enumerate(seq[plen:]):
                    pool.extend(slot, 1)
                    logits, cache = decode(
                        params, cache,
                        jnp.asarray(pool.block_table[slot][None]),
                        jnp.asarray([plen + i], jnp.int32),
                        {"tokens": jnp.asarray([[int(tok)]])},
                    )
                    errs.append(
                        float(jnp.abs(logits[0] - ref[0, plen + i]).max())
                    )
                return max(errs), scale, len(errs)

        req = reqs[0]
        seq = np.concatenate([req.tokens, out[req.rid][:-1]]).astype(np.int32)
        plen = int(req.tokens.size)
        err, scale, n = parity_probe(lm, seq, plen)
        print(f"[parity] paged decode vs uncached forward: "
              f"max |dlogits| = {err:.2e} (max |logit| {scale:.2e}) over "
              f"{n} steps "
              f"({arch.moe.dispatch if arch.moe else 'dense'} dispatch)")
        if arch.moe is not None and arch.moe.dispatch != "ragged":
            rag_arch = arch.replace(
                moe=dataclasses.replace(arch.moe, dispatch="ragged")
            )
            err, scale, n = parity_probe(
                LanguageModel(rag_arch, plan), seq, plen
            )
            print(f"[parity] ragged decode: max |dlogits| = {err:.2e} "
                  f"(max |logit| {scale:.2e}) over {n} steps")
        if arch.moe is not None:
            # 1e-5 of the largest logit is ~80 fp32 ulps: room for a
            # different summation order, while a bf16 pass (~4e-3
            # relative) fails it.
            tol = 1e-5 * max(1.0, scale)
            if err > tol:
                raise SystemExit(
                    f"ragged decode parity violated: {err:.3e} > {tol:.3e}"
                )
            print(f"[parity] ragged OK (<= {tol:.2e})")


def _telemetry_reports(args, arch, engine, max_seqs):
    """End-of-run observability artifacts for a serving run: decode/prefill
    drift vs the serving resource model at this run's shape, plus a Chrome
    trace_event view of the engine's event stream."""
    from repro import obs
    from repro.core import resource_model as rm
    from repro.core.platform import TPU_V5E

    events = engine.trace_ring.events()
    setup = rm.ServeSetup(
        batch=max_seqs,
        context=args.context,
        prefill_len=args.prefill_len,
        **({"dispatch": arch.moe.dispatch} if arch.moe else {}),
    )
    se = rm.serve_estimate(rm.ModelShape.from_arch(arch), setup, TPU_V5E)
    tracker = obs.DriftTracker(rm.modeled_serve_phases(se))
    n = tracker.observe_events(events)
    print(tracker.format_report(
        f"drift {args.arch} serving: host-measured vs TPU-v5e model "
        f"(structural when run on CPU)"
    ))
    trace_path = args.metrics_out + ".trace.json"
    obs.write_chrome_trace(
        trace_path, events, process_name=f"serve {args.arch}"
    )
    print(f"[obs] {len(events)} events ({n} drift spans) -> "
          f"{args.metrics_out}; chrome trace: {trace_path}")
    engine.telemetry.close()


if __name__ == "__main__":
    main()
