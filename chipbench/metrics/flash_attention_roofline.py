"""The flash-attention forward kernel's share of its roofline, per
cent: each call in the traced window (one per layer in the forward
pass, again where the backward recomputes it) could take at least the
larger of its FLOPs over the bf16 peak and its bytes over the HBM
bandwidth (chipbench.counts.flash_forward, for one device's share of
the batch and heads), over the time the trace gives the kernel: the
custom call with a (batch, heads, seq, head_dim) operand. Moves
train_tok_s."""
from chipbench import counts
from chipbench.readers import kernel_calls, kernel_roofline, least, pallas_op
from chipbench.weights import dims


def read(run):
    if run.get("kind") != "train":
        return None
    mix = run["mix"]
    share = mix.get("per_device", {})
    cfg = dict(run["config"])
    batch = mix["batch"] // share.get("batch", 1)
    for key in ("num_attention_heads", "num_key_value_heads"):
        cfg[key] = cfg[key] // share.get("heads", 1)
    f, b = counts.flash_forward(cfg, batch, mix["seq"])
    n = dims(cfg)
    kernel = pallas_op((batch, n["H"], mix["seq"], n["hd"]))
    calls = kernel_calls(run, kernel)
    return kernel_roofline(run, kernel, calls * least(f, b, run))
