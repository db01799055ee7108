"""A training cell's run, at a CPU size, with the timed step broken
underneath: ``correct`` has to come out false for each fault a one-chip
step can have, and true for the sound step."""
import cb_fixtures
import pytest

from chipbench import harness as H

CELL = "yi-9b-3l-v16k.train"


def _plant(trainer, fault):
    import jax
    import jax.numpy as jnp

    compile_train = trainer._compile_train

    def wrapped(batch):
        compile_train(batch)
        step = trainer._train_step
        if fault == "state_unchanged":
            def same(state, b):
                keep = jax.tree_util.tree_map(jnp.copy, state)
                return keep, step(state, b)[1]
            trainer._train_step = same
        elif fault == "half_batch":  # the mean taken over half the rows
            trainer._train_step = lambda state, b: step(
                state, {"tokens": b["tokens"][: b["tokens"].shape[0] // 2]})

    trainer._compile_train = wrapped


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
def test_fault_makes_the_run_incorrect(fault):
    ctx = cb_fixtures.context(CELL, seconds=1.0)
    drv = H.load_driver(ctx["traffic"]["kind"])

    class Faulty(drv.Train):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            _plant(self.trainer, fault)

    drv.Train = Faulty
    out = drv.run(ctx)
    assert out["correct"] is (fault is None), out["checks"]
