"""The paged-attention kernel's share of its roofline, per cent: the
least time its calls in the traced window could take (per call the
larger of its FLOPs over the bf16 peak and its bytes over the HBM
bandwidth, chipbench.counts.paged_attention: each KV head's keys and
values read once, for the tokens each row holds) over the time the
trace gives the kernel: the custom call that reads a (pages, KV heads,
page, head_dim) pool. One call per layer per step. Moves itl_p95_ms."""
from chipbench import counts
from chipbench.readers import (kernel_roofline, layers, least, pallas_op,
                               traced_rows)
from chipbench.weights import dims

KV_BYTES = {"bfloat16": 2, "float16": 2, "int8": 1, "float8_e4m3fn": 1,
            "int4": 0.5}


def read(run):
    rows = traced_rows(run)
    if not rows:
        return None
    n, eng = dims(run["config"]), run["engine"]
    kv = KV_BYTES[eng["kv_dtype"]]
    total = 0.0
    for r in rows:
        f, b = counts.paged_attention(run["config"],
                                      [(p, nv) for p, nv, _ in r], kv)
        total += layers(run) * least(f, b, run)
    pattern = pallas_op((r"\d+", n["K"], eng["page_size"], r"\d+"))
    return kernel_roofline(run, pattern, total)
