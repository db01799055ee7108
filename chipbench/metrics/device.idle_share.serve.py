"""Per cent of the traced serving window in which the device ran no
operation (1 - busy union / window). Moves itl_p95_ms: each token waits
for the host's work between two chunk steps."""
from chipbench.readers import idle_share


def read(run):
    return idle_share(run, "serve")
