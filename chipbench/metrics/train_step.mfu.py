"""Model FLOPs of every training step in the window (forward and
backward, recomputation not counted; chipbench.counts.train_step), per
cent of the window's host-clock seconds times the chips times the bf16
peak. Moves train_tok_s."""
from chipbench import counts


def read(run):
    if run.get("kind") != "train" or not run.get("window_s"):
        return None
    mix = run["mix"]
    flops = run["steps"] * counts.train_step(run["config"], mix["batch"],
                                             mix["seq"])
    return 100.0 * flops / (run["window_s"] * run["chips"]
                            * run["peak"]["bf16_flops"])
