"""FLOP and byte counts against hand counts, and the peaks table."""
import cb_fixtures  # noqa: F401  (puts the repo on sys.path)
import pytest

from chipbench import counts
from chipbench import harness as H

# d=8, f=16, 4 query heads and 2 KV heads of 2, one layer, 10 ids
CFG = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 2, "num_hidden_layers": 1,
       "vocab_size": 10}


def test_paged_attention_counts_each_kv_head_once():
    # 2 queries at positions 3, 4 see 4 and 5 keys: 9 pairs
    flops, byts = counts.paged_attention(CFG, [(3, 2)])
    assert flops == 4 * 4 * 2 * 9
    # K and V of 5 tokens x 2 KV heads x 2 dims in bf16; q in, out out
    assert byts == 2 * 5 * 2 * 2 * 2 + 2 * 2 * 4 * 2 * 2
    f8, b8 = counts.paged_attention(CFG, [(3, 2)], kv_bytes=1)
    assert f8 == flops and b8 == byts - 2 * 5 * 2 * 2


def test_flash_forward_counts_the_causal_triangle():
    flops, byts = counts.flash_forward(CFG, 1, 4)
    assert flops == 4 * 4 * 2 * 10          # 10 causal pairs of 4 tokens
    assert byts == 4 * (4 + 2 * 2) * 2 * 2 + 4 * 4 * 2 * 2 + 4 * 4 * 4


def test_step_flops():
    per_tok = 2 * (8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16)
    assert counts.forward_flops(CFG, [(0, 1)], 1) == per_tok + 32 + 160
    # the head only where a row emits
    assert counts.chunk_step(CFG, [(0, 1, True), (5, 1, False)]) == (
        2 * per_tok + 32 * (1 + 6) + 160)
    assert counts.train_step(CFG, 1, 2) == 3 * (2 * per_tok + 32 * 3 + 160)


def test_least_time_names_its_bound():
    peak = H.peaks("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    t, bound = counts.least_time(197e12, 1.0, peak)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = counts.least_time(1.0, 819e9, peak)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(H.Failure, match="not in the peaks table"):
        H.peaks("TPU v9 imaginary")


def _hlo(name, out, *operands):
    args = ", ".join(f"bf16[{','.join(map(str, s))}]{{3,2,1,0}} %a{i}"
                     for i, s in enumerate(operands))
    return (f"%{name} = bf16[{','.join(map(str, out))}]{{3,2,1,0}} "
            f'custom-call({args}), custom_call_target="tpu_custom_call"')


def test_kernel_readers_find_unnamed_pallas_calls_by_shape():
    """The roofline readers on a constructed trace: each kernel's calls
    are the custom calls with its operand shapes, and the share is the
    least time of those calls over their traced time."""
    from chipbench import trace as T

    cfg = dict(CFG, num_hidden_layers=2)
    peak = H.peaks("TPU v5 lite")
    paged = H.load_metric("paged_attention_roofline")
    flash = H.load_metric("flash_attention_roofline")
    rows = [(3, 2, True), (0, 1, True)]
    f, b = counts.paged_attention(cfg, [(p, n) for p, n, _ in rows])
    least = counts.least_time(f, b, peak)[0]
    ops = [T.Op("closed_call.1", 0.0, 4 * least,
                _hlo("closed_call.1", (2, 4, 2, 2), (2, 4, 2, 2),
                     (9, 2, 16, 2))),
           T.Op("closed_call.1", 1.0, 1.0 + 4 * least,
                _hlo("closed_call.1", (2, 4, 2, 2), (2, 4, 2, 2),
                     (9, 2, 16, 2))),
           T.Op("pallas_call.2", 2.0, 3.0,
                _hlo("pallas_call.2", (1, 4, 8, 2), (1, 4, 8, 2)))]
    run = {"kind": "serve", "config": cfg, "peak": peak,
           "trace": T.Trace({"/device:TPU:0": ops}, []), "window": (0, 5),
           "rows": [rows], "steps": [(0, 1, True)],
           "engine": {"page_size": 16, "kv_dtype": "bfloat16"}}
    # one step, two layers: two calls of the least time each, 4x slower
    assert paged.read(run) == pytest.approx(25.0)
    assert flash.read(run) is None
    mix = {"batch": 1, "seq": 8}
    f, b = counts.flash_forward(cfg, 1, 8)
    run.update(kind="train", mix=mix)
    assert flash.read(run) == pytest.approx(
        100 * counts.least_time(f, b, peak)[0] / 1.0)
    run["trace"] = T.Trace({"/device:TPU:0": ops[:2]}, [])
    assert flash.read(run) is None  # no such kernel: no reading, never 0
