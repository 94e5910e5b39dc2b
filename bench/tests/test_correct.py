"""The check that decides ``correct``, driven on the CPU at a tiny size.

The harness runs end to end (everything but its look for a chip) with the
program's train step as it is, and with that step broken underneath: the
state returned unchanged, or half of each batch left out and the mean
taken over the rest (limits for this size in ``tiny.py``).  The control,
the reference computed with float8 weight matmuls in the program's place,
must fail the cell's own limits.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import compare, gen, harness, manifest, reference
from bench.tests.tiny import TINY_LIMITS, tiny

CELL = "granite4l-zipf"
SEED = 2**31 + 101


def _unchanged(run):
    step = run["trainer"].train_step

    def same_state(state, batch):
        # The step donates its input: give it a copy and keep the original.
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics

    run["trainer"].train_step = same_state


def _half_batch(run):
    step = run["trainer"].train_step

    def half(state, batch):
        h = batch["tokens"].shape[0] // 2
        kept = {k: jnp.concatenate([v[:h], v[:h]]) for k, v in batch.items()}
        return step(state, kept)

    run["trainer"].train_step = half


@pytest.mark.parametrize("fault,want", [(None, True), (_unchanged, False),
                                        (_half_batch, False)])
def test_harness_judges_the_timed_path(fault, want):
    r = harness.run_cell(CELL, SEED, 1.0, False, t0=time.perf_counter(),
                         require_chip=False, cfg=tiny(), limits=TINY_LIMITS,
                         patch=fault)
    assert r["correct"] is want, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"


def test_control_fails():
    cfg = tiny()
    s = gen.TokenStream(manifest.traffic("zipf-topics"), cfg["vocab_size"],
                        2, 64, SEED)
    batches = [s.batch_at(i) for i in range(harness.check_steps(cfg))]
    ref = reference.Reference(cfg).run(SEED, batches)
    ctl = reference.Reference(cfg, "fp8").run(SEED, batches)
    numbers = compare.readings(ctl, ref)
    assert not compare.judge(numbers, compare.load_limits(CELL)), numbers
