"""``launch/train.py``'s ``setup``: the path ``chip_smoke.py`` shares."""

import itertools

import jax

from repro import training
from repro.launch import train


def test_setup_cuts_depth_places_state_and_records_losses():
    args = train.parse_args(
        ["--arch", "granite-moe-3b-a800m", "--reduced", "--layers", "1",
         "--steps", "3", "--batch", "2", "--seq", "32",
         "--dispatch", "ragged"]
    )
    run = train.setup(args)
    assert run["arch"].num_layers == 1
    assert run["arch"].moe.dispatch == "ragged"
    want = training.state_shardings(run["trainer"].lm)
    placed = jax.tree.map(lambda x: x.sharding, run["state"])
    assert jax.tree.leaves(placed) == jax.tree.leaves(want)

    trainer = run["trainer"]
    trainer.cfg.log_every = 1
    batch = next(run["data"])
    run["data"].close()
    with run["plan"].mesh:
        out = trainer.fit(run["state"], itertools.repeat(batch))
    assert [s for s, _ in trainer.losses] == [0, 1, 2]
    # Output state stays on the plan's shardings: no recompile at step 1.
    assert jax.tree.leaves(jax.tree.map(lambda x: x.sharding, out["state"])) \
        == jax.tree.leaves(want)
    assert trainer.losses[-1][1] < trainer.losses[0][1]
