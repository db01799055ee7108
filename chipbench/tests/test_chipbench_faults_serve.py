"""A serving cell's run, at a CPU size, with the timed path broken
underneath: ``correct`` has to come out false for each fault, and true
for the sound path. The device check is skipped; everything after it
runs as in a run on the chip."""
import cb_fixtures
import numpy as np
import pytest

from chipbench import harness as H

CELL = "yi-9b-8l.chat"


def _plant(serve, fault):
    eng = serve.engine
    run_chunk, sample = eng._chunk_jit, eng._sample
    if fault == "state_unchanged":     # the step returns its KV unchanged
        eng._chunk_jit = lambda p, t, cache, *a: (
            run_chunk(p, t, cache, *a)[0], cache)
    elif fault == "half_batch":        # half the rows left out of a step
        steps = [0]

        def half(*a):
            # the left-out half alternates, so a request in any slot,
            # even one serving alone, loses every other token
            logits, cache = run_chunk(*a)
            steps[0] += 1
            return logits.at[steps[0] % 2::2].set(0.0), cache
        eng._chunk_jit = half
    elif fault == "token_altered":     # each token changed where sampled
        vocab = serve.config["vocab_size"]
        eng._sample = lambda logits, rid, pos: (
            sample(logits, rid, pos) + 1) % vocab


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_makes_the_run_incorrect(fault):
    ctx = cb_fixtures.context(CELL)
    ctx["traffic"]["rate_per_s"] = 12.0  # several slots in use at once
    drv = H.load_driver(ctx["traffic"]["kind"])

    class Faulty(drv.Serve):
        def _warm(self):  # planted before warm-up: nothing new compiles
            _plant(self, fault)
            super()._warm()

    drv.Serve = Faulty
    out = drv.run(ctx)
    gap = out["checks"]["max_logit_gap"]
    assert out["failed"] == 0
    assert out["correct"] is (fault is None), gap
    assert np.isfinite(gap["value"])
