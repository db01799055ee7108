"""Per cent of the busy device seconds of the traced training window
spent in leaf ops under the name scopes attention (the attention core:
the flash forward kernel and its recomputation) or attention_bwd (the
flash backward), over all busy seconds (chipbench.scopes). Moves
train_tok_s. A program without the scopes reads nothing."""
from chipbench import scopes as S


def read(run):
    return S.run_share(run, "train", ("attention", "attention_bwd"))
