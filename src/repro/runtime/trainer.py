"""Fault-tolerant training loop.

Production concerns handled here (DESIGN.md §3):

* **checkpoint/restart** — periodic async checkpoints; on (re)start the loop
  resumes from the latest one; the data stream is a pure function of step so
  resume is exact.  SIGTERM/SIGINT trigger a final checkpoint before exit
  (preemption handling).
* **straggler mitigation** — per-step wall-time EMA; steps slower than
  ``straggler_factor``x the EMA are logged with their ordinal so the
  orchestrator can cordon slow hosts.  (On real multi-host TPU deployments
  this feeds the controller that re-slices the job; here it is also what the
  elastic-restart test hooks into.)
* **expert migration** — the paper §VI controller, closed-loop: router load
  EMAs are folded in every step from the training metrics; when group
  imbalance exceeds ``migrate_threshold`` the controller plans hot-expert
  replication (``migration.plan_layer``) plus Alg-2 swaps on the residual,
  prices the transfer against the modeled step-time recovery
  (``resource_model.estimate`` with ``imbalance_post``; opt-in via
  ``TrainerConfig.platform``), and only then permutes the expert tensors —
  params and both Adam moments in one pass — re-placing the migrated state
  on the plan's shardings so the jitted step neither recompiles nor
  gathers off-plan leaves.  The load EMA itself is checkpointed (manifest
  ``extras``) so restarts and rollbacks resume the controller bit-exact.
* **elastic scaling** — checkpoints are mesh-independent (see
  ``repro.checkpoint``): restarting on a larger/smaller mesh re-shards
  automatically; the trainer only needs the new plan.
* **anomaly sentinel + rollback** — the jitted step refuses non-finite
  (or, with ``gnorm_skip_cap``, spiking) updates and reports
  ``metrics["skipped"]``; after ``anomaly_rollback_after`` consecutive
  skips the trainer restores the last *intact* checkpoint and re-enters
  the loop at the restored step.  The data stream being a pure function
  of step makes the re-trained trajectory bit-for-bit the fault-free one.
* **transient data errors** — ``batch_at``/``next`` failures retry with
  exponential backoff before surfacing.
* **fault injection** — every recovery path above is driveable through a
  ``runtime.faults.FaultInjector`` (chaos suite + robustness bench).
"""

from __future__ import annotations

import os
import signal
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import numpy as np

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.core import migration as mig
from repro.models.model import LanguageModel
from repro.optim import OptimizerConfig
from repro.runtime.faults import FaultInjector, TransientDataError
from repro import training


@dataclass
class TrainerConfig:
    total_steps: int = 100
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 50
    checkpoint_keep: int = 3
    log_every: int = 10
    # straggler monitor
    straggler_factor: float = 2.0
    # expert migration
    migrate_every: int = 20
    migrate_threshold: float = 1.3  # max/mean group load
    migrate_max_swaps: int = 100
    # Model-priced hysteresis (opt-in): name a core.platform entry and the
    # controller migrates only when the modeled per-step recovery amortized
    # over ``migrate_every`` steps clears the Table-IV transfer cost.
    # None keeps the pure threshold trigger (back-compat).
    platform: Optional[str] = None
    # anomaly sentinel -> skip-step -> rollback
    gnorm_skip_cap: float = 0.0  # >0: also skip when grad_norm exceeds this
    anomaly_rollback_after: int = 3  # K consecutive skips trigger rollback
    max_rollbacks: int = 3  # bounded retry budget for rollbacks
    # transient data-source errors
    data_retries: int = 3
    data_backoff_s: float = 0.05  # doubles per retry


class Trainer:
    def __init__(
        self,
        lm: LanguageModel,
        opt_cfg: OptimizerConfig,
        cfg: TrainerConfig,
        log_fn: Callable[[str], None] = print,
        injector: Optional[FaultInjector] = None,
    ):
        self.lm = lm
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.log = log_fn
        self.injector = (
            injector if injector is not None else FaultInjector(log_fn=log_fn)
        )
        # The new state leaves on the plan's shardings, so the next step's
        # inputs match the first step's and nothing recompiles.
        self.train_step = jax.jit(
            training.make_train_step(
                lm, opt_cfg,
                gnorm_skip_cap=cfg.gnorm_skip_cap
                if cfg.gnorm_skip_cap > 0 else None,
            ),
            donate_argnums=(0,),
            out_shardings=(training.state_shardings(lm), None),
        )
        self.ckpt = (
            CheckpointManager(
                cfg.checkpoint_dir, keep=cfg.checkpoint_keep,
                every=cfg.checkpoint_every, injector=self.injector,
                log_fn=log_fn,
            )
            if cfg.checkpoint_dir
            else None
        )
        arch = lm.arch
        self.load_stats = (
            mig.LoadStats(arch.num_moe_layers, arch.moe.num_experts)
            if arch.moe
            else None
        )
        # (b, s) of the running batch — captured in fit() for the pricing
        # gate's TrainSetup; None until the first batch arrives.
        self._batch_shape: Optional[tuple] = None
        self.step_times: List[float] = []
        # (step, loss) at every log step — the values the log line prints.
        self.losses: List[tuple] = []
        self.stragglers: List[int] = []
        self.migrations: List[Dict[str, Any]] = []
        self.anomalies: List[Dict[str, Any]] = []
        self.rollbacks: List[Dict[str, Any]] = []
        # Every blocking device->host metric fetch goes through _fetch and
        # is counted here, so tests can pin the hot-loop sync cadence.
        self.host_fetches = 0
        self._stop = False

    def _fetch(self, x, what: str):
        """Blocking device->host fetch of a metric value (counted), marked
        ``train.fetch`` with the value's name."""
        self.host_fetches += 1
        obs.counter("train.host_fetches")
        with obs.span("train.fetch", what=what):
            return jax.device_get(x)

    # -- fault handling ------------------------------------------------------

    def _install_signals(self):
        def handler(signum, frame):
            self.log(f"[trainer] signal {signum}: checkpoint + stop")
            self._stop = True

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, handler)
            except ValueError:  # non-main thread (tests)
                pass

    # -- expert migration ------------------------------------------------------

    def _price_migration(self, imb: float, imb_post: float, n_replicas: int):
        """Model-priced hysteresis: estimate the current and post-rebalance
        step times on ``cfg.platform`` and return the pricing record.  The
        gate applies the plan iff the per-step recovery amortized over
        ``migrate_every`` steps clears the Table-IV transfer cost."""
        from repro.core import resource_model as rm
        from repro.core.platform import get_platform

        plan = self.lm.plan
        b, s = self._batch_shape
        setup = rm.TrainSetup(
            b=b,
            s=s,
            PP=max(plan.pp, 1),
            EP=max(plan.ep, 1),
            DP=max(
                plan.mesh.devices.size // (max(plan.pp, 1) * max(plan.ep, 1)),
                1,
            ),
            dispatch=self.lm.arch.moe.dispatch,
            imbalance=imb,
            replicas=n_replicas,
        )
        est = rm.estimate(
            rm.ModelShape.from_arch(self.lm.arch),
            setup,
            get_platform(self.cfg.platform),
            imbalance_post=imb_post,
        )
        gain = est.migrate_gain_per_step * self.cfg.migrate_every
        return {
            "t_migrate": est.t_migrate,
            "gain_per_step": est.migrate_gain_per_step,
            "amortized_gain": gain,
            "worth_it": gain > est.t_migrate,
        }

    def _maybe_migrate(self, state, step: int):
        if self.load_stats is None or step % self.cfg.migrate_every:
            return state
        arch, plan = self.lm.arch, self.lm.plan
        if plan.ep <= 1:
            return state
        params = state["params"]
        moe_positions = [
            i for i, (_, f) in enumerate(arch.block_pattern) if f == "moe"
        ]
        # Assignments (and replica tables, when the arch carries channels)
        # live per pattern-position, stacked over reps into the LoadStats
        # row order: (position-major, rep).
        assign_all = np.concatenate(
            [np.asarray(params["blocks"][i]["ffn"]["assignment"]) for i in moe_positions]
        )  # (num_moe_layers, E)
        have_reps = bool(
            arch.moe.max_replicas > 0
            and "replicas" in params["blocks"][moe_positions[0]]["ffn"]
        )
        reps_all = (
            np.concatenate(
                [np.asarray(params["blocks"][i]["ffn"]["replicas"]) for i in moe_positions]
            )
            if have_reps
            else None
        )
        imb = self.load_stats.imbalance(assign_all, plan.ep, replicas=reps_all)
        if imb < self.cfg.migrate_threshold:
            return state
        # -- plan (cheap, host-side numpy) first: replication for experts no
        # swap can balance, Alg-2 swaps on the residual.  The plan gives the
        # post-rebalance imbalance the pricing gate needs BEFORE any tensor
        # is touched.
        t0 = time.perf_counter()
        ema = self.load_stats.ema  # (num_moe_layers, E) in stack order
        E = arch.moe.num_experts
        plans: Dict[int, Dict[str, np.ndarray]] = {}
        total_swaps = 0
        row = 0
        for pos in moe_positions:
            old_assign = np.asarray(params["blocks"][pos]["ffn"]["assignment"])
            old_reps = (
                np.asarray(params["blocks"][pos]["ffn"]["replicas"])
                if have_reps
                else None
            )
            reps = old_assign.shape[0]
            new_assign = np.empty_like(old_assign)
            new_reps = np.empty_like(old_reps) if have_reps else None
            perms = np.empty_like(old_assign)
            for r in range(reps):
                na, nr, perm, swaps = mig.plan_layer(
                    ema[row], old_assign[r],
                    old_reps[r] if have_reps else None,
                    plan.ep, max_iters=self.cfg.migrate_max_swaps,
                )
                total_swaps += swaps
                new_assign[r] = na
                perms[r] = perm
                if have_reps:
                    new_reps[r] = nr
                row += 1
            plans[pos] = {
                "assignment": new_assign, "perms": perms, "replicas": new_reps
            }
        new_assign_all = np.concatenate(
            [plans[i]["assignment"] for i in moe_positions]
        )
        new_reps_all = (
            np.concatenate([plans[i]["replicas"] for i in moe_positions])
            if have_reps
            else None
        )
        imb_post = self.load_stats.imbalance(
            new_assign_all, plan.ep, replicas=new_reps_all
        )
        n_replicas = (
            int((new_reps_all < E).sum(axis=1).max()) if have_reps else 0
        )
        obs.instant(
            "train.migrate_planned", step=step, imbalance=imb,
            imbalance_post=imb_post, swaps=total_swaps, replicas=n_replicas,
        )
        record: Dict[str, Any] = {
            "step": step,
            "imbalance": imb,
            "imbalance_post": imb_post,
            "swaps": total_swaps,
            "replicas": n_replicas,
        }
        # -- priced hysteresis gate (opt-in via cfg.platform) ---------------
        if self.cfg.platform is not None and self._batch_shape is not None:
            record.update(self._price_migration(imb, imb_post, n_replicas))
            if not record["worth_it"]:
                record["applied"] = False
                self.migrations.append(record)
                self.log(
                    f"[migrate] step={step} imbalance={imb:.2f}->"
                    f"{imb_post:.2f} deferred: amortized gain "
                    f"{record['amortized_gain']*1e3:.1f}ms < transfer "
                    f"{record['t_migrate']*1e3:.1f}ms"
                )
                return state
        # -- apply: ONE permutation pass over params and both Adam moment
        # trees (they must move with their weights or the optimizer
        # mismatches history), then the routing tables.
        with obs.span("train.migrate", step=step):
            new_state = self._apply_migration(state, plans, moe_positions,
                                              have_reps)
        dt = time.perf_counter() - t0
        record.update({"seconds": dt, "applied": True})
        self.migrations.append(record)
        self.log(
            f"[migrate] step={step} imbalance={imb:.2f}->{imb_post:.2f} "
            f"swaps={total_swaps} replicas={n_replicas} ({dt*1e3:.0f} ms)"
        )
        return new_state

    def _apply_migration(self, state, plans, moe_positions, have_reps):
        """Permute the expert tensors of params and both Adam moments by
        ``plans`` and re-place the result on the incoming shardings."""
        import jax.numpy as jnp

        params = state["params"]
        new_blocks = list(params["blocks"])
        new_m_blocks = list(state["m"]["blocks"])
        new_v_blocks = list(state["v"]["blocks"])
        for pos in moe_positions:
            perms = plans[pos]["perms"]
            new_ffn = mig.apply_migration_to_tree(
                dict(new_blocks[pos]["ffn"]), perms
            )
            new_ffn["assignment"] = jnp.asarray(plans[pos]["assignment"])
            if have_reps:
                new_ffn["replicas"] = jnp.asarray(
                    plans[pos]["replicas"], dtype=jnp.int32
                )
            new_blocks[pos] = {**new_blocks[pos], "ffn": new_ffn}
            for tree_blocks in (new_m_blocks, new_v_blocks):
                blk = dict(tree_blocks[pos])
                blk["ffn"] = mig.apply_migration_to_tree(
                    dict(blk["ffn"]), perms
                )
                tree_blocks[pos] = blk
        new_state = {
            "params": {**params, "blocks": tuple(new_blocks)},
            "m": {**state["m"], "blocks": tuple(new_m_blocks)},
            "v": {**state["v"], "blocks": tuple(new_v_blocks)},
            "step": state["step"],
        }
        # Re-place the migrated leaves on the shardings the incoming state
        # actually carries (the jitted step's compiled output layouts —
        # the plan's specs after compiler canonicalization): the eager
        # permute above commits results wherever jax.numpy left them, and
        # feeding off-plan leaves back into the step would either
        # recompile or silently gather.
        live_shardings = jax.tree.map(lambda x: x.sharding, state)
        return jax.device_put(new_state, live_shardings)

    # -- recovery helpers ------------------------------------------------------

    def _ckpt_extras(self) -> Optional[Dict[str, Any]]:
        """Controller state riding along with every checkpoint: the router
        load EMA (manifest ``extras``, digest-verified like every leaf).
        Without it a restart forgets the measured skew and the next
        migration window re-triggers — or misses — on a cold EMA."""
        if self.load_stats is None:
            return None
        return {"load_stats": self.load_stats.to_state()}

    def _restore_load_stats(self, ck_step: int) -> None:
        """Reset the controller to the restored checkpoint's snapshot —
        bit-exact when the checkpoint carried one, cold otherwise (older
        checkpoints predate the extras field)."""
        if self.load_stats is None or self.ckpt is None:
            return
        try:
            extras = self.ckpt.extras_for(ck_step)
        except (FileNotFoundError, OSError):
            extras = {}
        if extras and "load_stats" in extras:
            self.load_stats.load_state(extras["load_stats"])
        else:
            arch = self.lm.arch
            self.load_stats = mig.LoadStats(
                arch.num_moe_layers, arch.moe.num_experts,
                decay=self.load_stats.decay,
            )

    def _abstract_and_shardings(self, state):
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
        )
        # The PLAN's state shardings: restored leaves must land on-device
        # with the mesh layout the step expects — not replicated, and not
        # committed to whatever single device a fresh eager init sat on.
        return abstract, training.state_shardings(self.lm)

    def _next_batch(self, data, data_it, indexed: bool, step: int):
        """Fetch the step's batch, retrying transient data-source errors
        with exponential backoff before surfacing them."""
        delay = self.cfg.data_backoff_s
        for attempt in range(self.cfg.data_retries + 1):
            try:
                self.injector.raise_if("data.transient", step)
                return data.batch_at(step) if indexed else next(data_it)
            except (TransientDataError, OSError) as e:
                if attempt >= self.cfg.data_retries:
                    raise
                self.log(
                    f"[data] transient error at step {step}: {e} "
                    f"(retry {attempt + 1}/{self.cfg.data_retries} "
                    f"in {delay * 1e3:.0f} ms)"
                )
                time.sleep(delay)
                delay *= 2

    def _rollback(self, state, step: int):
        """Restore the newest intact checkpoint and return (state, step) to
        re-enter the loop at.  Exact resume: the data stream is a pure
        function of step, so the re-trained steps match the fault-free
        trajectory bit-for-bit."""
        if self.ckpt is None:
            raise RuntimeError(
                f"step {step}: {self.cfg.anomaly_rollback_after} consecutive "
                f"anomalous steps and no checkpoint_dir to roll back to"
            )
        if len(self.rollbacks) >= self.cfg.max_rollbacks:
            raise RuntimeError(
                f"step {step}: rollback budget exhausted "
                f"({self.cfg.max_rollbacks}) — anomalies persist"
            )
        abstract, shardings = self._abstract_and_shardings(state)
        try:
            new_state, ck_step = self.ckpt.restore_latest(abstract, shardings)
        except FileNotFoundError as e:
            raise RuntimeError(
                f"step {step}: anomaly rollback requested but no intact "
                f"checkpoint exists"
            ) from e
        self.rollbacks.append({"at_step": step, "to_step": ck_step})
        # The load EMA rolls back WITH the weights: keeping the post-fault
        # EMA against pre-fault expert tensors would mis-trigger the next
        # migration window on loads those weights never produced.
        self._restore_load_stats(ck_step)
        self.log(
            f"[rollback] step={step}: {self.cfg.anomaly_rollback_after} "
            f"consecutive anomalies -> restored step {ck_step}"
        )
        return new_state, ck_step

    # -- main loop -------------------------------------------------------------

    def fit(self, state, data: Iterator) -> Dict[str, Any]:
        self._install_signals()
        plan = self.lm.plan
        if plan.pp_axis is not None and plan.pp > 1:
            # The schedule-executing pipeline path (core.pipeline
            # .pipelined_step): backward runs in the bound schedule's op
            # order, not jax.grad's.
            self.log(
                f"[trainer] pipelined: PP={plan.pp} schedule={plan.schedule} "
                + (f"V={plan.vstages} " if plan.vstages > 1 else "")
                + f"(M={plan.microbatches or 2 * plan.pp})"
            )
        start_step = int(self._fetch(state["step"], "step"))
        if self.ckpt is not None:
            try:
                abstract, shardings = self._abstract_and_shardings(state)
                state, ck_step = self.ckpt.restore_latest(abstract, shardings)
                start_step = ck_step
                self._restore_load_stats(ck_step)
                self.log(f"[trainer] resumed from step {ck_step}")
            except FileNotFoundError:
                pass

        metrics = {}
        # Datasets exposing batch_at(step) are pure functions of the step —
        # required for EXACT resume after restart; plain iterators are
        # consumed best-effort.
        indexed = hasattr(data, "batch_at")
        data_it = None if indexed else iter(data)
        step = start_step
        anomaly_streak = 0
        while step < self.cfg.total_steps:
            # Simulated preemption: deliver a REAL signal so the installed
            # handler (final checkpoint + stop) is what gets exercised.
            if self.injector.fire("train.sigterm", step) is not None:
                os.kill(os.getpid(), signal.SIGTERM)
            if self._stop:
                break
            with obs.span("train.data", step=step):
                batch = self._next_batch(data, data_it, indexed, step)
            if self._batch_shape is None:
                tok = batch["tokens"]
                self._batch_shape = (int(tok.shape[0]), int(tok.shape[1]))
            scale = self.injector.payload_if("train.nonfinite", step)
            if scale is not None:
                batch = {**batch, "fault_scale": np.float32(scale)}
            t0 = time.perf_counter()
            # Slow-step injection sleeps inside the timed window so the
            # straggler monitor sees it like a real slow host.
            self.injector.sleep_if("train.slow_step", step)
            with obs.span("train.step", step=step) as sp:
                state, metrics = self.train_step(state, batch)
                # The ONE per-step host sync: the in-jit anomaly sentinel's
                # verdict (the branch below must run on the host).  Fetching
                # it blocks until the step finishes, which also makes dt a
                # true wall time.  loss/grad_norm stay on device except on
                # log steps and skips — fetching them every step serializes
                # the device against the host (the old hot-loop bug).
                skipped = bool(
                    self._fetch(metrics.get("skipped", 0), "skipped")
                )
                sp.set(skipped=skipped)
            dt = time.perf_counter() - t0
            self.step_times.append(dt)
            # Straggler detection on the step-time EMA.
            if len(self.step_times) > 5:
                ema = float(np.mean(self.step_times[-20:-1]))
                if dt > self.cfg.straggler_factor * ema:
                    self.stragglers.append(step)
                    self.log(
                        f"[straggler] step={step} took {dt*1e3:.0f}ms "
                        f"(ema {ema*1e3:.0f}ms)"
                    )
            if skipped:
                # The sentinel refused the update (state unchanged): count
                # the streak, roll back to the last good checkpoint once it
                # crosses the budget, and re-enter AT the restored step.
                loss = float(self._fetch(metrics["loss"], "loss"))
                gnorm = float(self._fetch(metrics["grad_norm"], "grad_norm"))
                obs.instant(
                    "train.anomaly", step=step, loss=loss, grad_norm=gnorm
                )
                anomaly_streak += 1
                self.anomalies.append(
                    {"step": step, "loss": loss, "grad_norm": gnorm}
                )
                self.log(
                    f"[sentinel] step={step} anomalous update skipped "
                    f"(loss={loss:.4g} gnorm={gnorm:.4g}) "
                    f"[{anomaly_streak}/{self.cfg.anomaly_rollback_after}]"
                )
                if anomaly_streak >= self.cfg.anomaly_rollback_after:
                    state, step = self._rollback(state, step)
                    anomaly_streak = 0
                    continue
                step += 1
                continue
            anomaly_streak = 0
            if self.load_stats is not None and "expert_load" in metrics:
                # Migration controller EMA: stays per-step on purpose — the
                # SIGTERM-restart tests pin the controller bit-exact, and
                # thinning the EMA feed would change its trajectory.
                loads = np.asarray(
                    self._fetch(metrics["expert_load"], "expert_load")
                )
                # (reps, n_moe_pos, E) -> stack order (pos-major, rep)
                loads = np.concatenate(
                    [loads[:, i, :] for i in range(loads.shape[1])]
                )
                self.load_stats.update(loads)
            state = self._maybe_migrate(state, step + 1)
            if step % self.cfg.log_every == 0:
                with obs.span("train.log", step=step):
                    loss = float(self._fetch(metrics["loss"], "loss"))
                    self.losses.append((step, loss))
                    obs.gauge("train.loss", loss, step=step)
                    self.log(
                        f"[train] step={step} loss={loss:.4f} "
                        f"({dt*1e3:.0f} ms/step)"
                    )
            if self.ckpt is not None and self.ckpt.should_save(step + 1):
                self.ckpt.save(
                    step + 1, state, blocking=False,
                    extras=self._ckpt_extras(),
                )
            step += 1
        last_step = max(step - 1, start_step)
        if self.ckpt is not None:
            self.ckpt.save(step, state, blocking=True, extras=self._ckpt_extras())
        return {
            "state": state,
            "metrics": metrics,
            "stragglers": self.stragglers,
            "migrations": self.migrations,
            "anomalies": self.anomalies,
            "rollbacks": self.rollbacks,
            "last_step": last_step,
        }
