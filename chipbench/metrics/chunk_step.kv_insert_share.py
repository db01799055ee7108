"""Per cent of the busy device seconds of the traced serving window
spent in leaf ops under the name scope kv_insert (the scatter of the
chunk's new keys and values into the page pool, and the pool copies
XLA puts around it), over all busy seconds (chipbench.scopes).
Moves itl_p95_ms. A program without the scope reads nothing."""
from chipbench import scopes as S


def read(run):
    return S.run_share(run, "serve", ("kv_insert",))
