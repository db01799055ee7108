"""Per cent of the traced training window in which no operation ran on
a device, averaged over the cell's devices. Moves train_tok_s."""
from chipbench.readers import idle_share


def read(run):
    return idle_share(run, "train")
