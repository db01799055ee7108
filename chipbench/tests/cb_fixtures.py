"""Shared by the benchmark's CPU tests: a tiny Yi-shaped configuration,
a small chat mix and a context that drives a cell's driver without the
device check (these tests never load a TPU library)."""
from __future__ import annotations

import argparse
import copy
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

TINY = {"name": "tiny", "arch": "yi-9b",
        "overrides": {"n_layers": 2, "d_model": 256, "n_heads": 4,
                      "n_kv_heads": 2, "d_ff": 512, "vocab": 1024},
        "hidden_size": 256, "intermediate_size": 512,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
        "num_hidden_layers": 2, "vocab_size": 1024, "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0}

SMALL_CHAT = {
    "rate_per_s": 4.0, "warmup_s": 1.0, "tail_s": 30.0, "check_tokens": 40,
    "trace_from_s": 0.5, "trace_s": 1.0,
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8, "min": 4,
               "max": 100},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.8, "min": 2,
               "max": 20},
    "engine": {"max_batch": 4, "prefill_chunk": 8, "page_size": 16,
               "kv_dtype": "bfloat16", "max_len": 128, "n_pages": None}}


# A training cell's limits at the tiny size: its readings there are
# larger than at the cell's own size (fewer elements to average over),
# and every fault still reads ten times these or more.
TINY_TRAIN_LIMITS = {"loss_rel_gap": 3e-4, "grad_norm_gap": 0.02,
                     "grad_diff": 0.05, "change_norm_gap": 0.02}


def context(cell: str, seed: int = 2**33 + 7, seconds: float = 2.0,
            trace: int = 0, config: dict = TINY) -> dict:
    """A cell's run context at a CPU-sized configuration."""
    import jax

    from chipbench import harness as H

    ctx = H.load_cell(cell)
    ctx["config"] = copy.deepcopy(config)
    mix = ctx["traffic"]
    if mix["kind"] == "serve_open_loop":
        mix.update(copy.deepcopy(SMALL_CHAT))
    else:
        mix.update(batch=2, seq=512)
    ctx["limits"] = (H.load_limits(cell) if mix["kind"] == "serve_open_loop"
                     else dict(TINY_TRAIN_LIMITS))
    ctx["clock"] = H.CompileClock()
    ctx["devs"] = jax.devices()[:1]
    ctx["peak"] = H.peaks("TPU v5 lite")
    ctx.update(args=argparse.Namespace(seed=seed, seconds=seconds,
                                       trace=trace),
               t_start=time.perf_counter(), base=H.HERE)
    return ctx
