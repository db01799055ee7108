"""Per cent of the busy device seconds of the traced training window
spent in leaf ops under the name scope optimizer (the optimizer's
update of parameters and moments), over all busy seconds
(chipbench.scopes). Moves train_tok_s. A program without the scope
reads nothing."""
from chipbench import scopes as S


def read(run):
    return S.run_share(run, "train", ("optimizer",))
