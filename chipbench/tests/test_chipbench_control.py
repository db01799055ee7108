"""The control, at a CPU size: the plain reference put in the program's
place and computed one precision below the configuration's bfloat16
(float8 e4m3 operands). It has to read past a limit, as it does on the
chip at the cell's own size; the sound program's readings at this size
are the fault tests' ``None`` cases."""
import cb_fixtures
import numpy as np
import pytest

from chipbench import harness as H
from chipbench import reference as R
from chipbench import weights as W


@pytest.mark.parametrize("seed", [5, 2**33 + 7, 2**35 + 11])
def test_serving_control_fails_the_chat_limit(seed):
    # about as many served tokens as a chat run scores (check_tokens 300)
    cfg = cb_fixtures.TINY
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg["vocab_size"], 100).tolist()
    served = rng.integers(0, cfg["vocab_size"], 400).tolist()
    gaps = R.served_gaps(W.make(cfg, seed), cfg, prompt, served, "fp8")
    assert len(gaps) == len(served)
    assert gaps.max() > H.load_limits("yi-9b-8l.chat")["max_logit_gap"]


def test_training_control_fails_a_limit():
    cfg, seed = cb_fixtures.TINY, 2**33 + 7
    mix = H.load_traffic("train")
    rng = np.random.default_rng(seed)
    batches = [rng.integers(0, cfg["vocab_size"], (2, 512), dtype=np.int32)
               for _ in range(mix["check_steps"])]
    ref = R.train_steps(cfg, seed, batches, mix["optimizer"], keep_grad=True)
    low = R.train_steps(cfg, seed, batches, mix["optimizer"], "fp8",
                        against=ref["g1"])
    got = H.load_driver(mix["kind"]).compare(low, ref, low["diff"])
    limits = cb_fixtures.TINY_TRAIN_LIMITS
    assert any(v > limits[k] for k, v in got.items()), got
