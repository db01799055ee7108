#!/usr/bin/env python3
"""Chip smoke test: serve and train Yi-9B widths once on a TPU, through
the normal entry point (``repro.run.cli.main``), in this one process.

    python chip_smoke.py             # one chip: device, kernels, serve, train
    python chip_smoke.py --chips 4   # four-chip host: C1 wus train, 2x2 vs 1x1

Phases on one chip:

  device   the first device is a TPU and the kernel registry routes to
           compiled Pallas kernels (``REPRO_USE_PALLAS=interpret`` fails);
  kernels  the paged kernel (bf16 pool, page 16, C = 8 and C = 1) and the
           flash kernel's output and gradients at S = 2048 agree with
           each op's jnp implementation (``dispatch.get(name).jnp``);
  serve    ``--mode serve`` at Yi-9B widths, 8 layers, paged bf16 KV;
           every request gets its tokens, and the compiled chunk step
           holds a ``tpu_custom_call`` (the paged kernel);
  train    ``--mode train`` at Yi-9B widths, 2 layers, vocab sliced to a
           quarter; 3 steps with finite loss.

With ``--chips 4`` only the train phase runs, under weight-update
sharding (``wus``) on the host's 2x2 (data, model) mesh, against the
same steps on a 1x1 mesh; the losses must agree and the state must span
the four devices as the sharding rules say.

Set-up lines report compile seconds, phase seconds and peak HBM; they are
set-up facts, not speeds. The last line of a run in which every phase
passed is ``{"ok": true, "device": {...}}``; any failure raises, exits
non-zero and prints no such line. The persistent compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` in the
checkout, so a second run compiles less.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Kernel agreement: normalized error max|kernel - jnp| / max|jnp|. Both
# paths read the same bf16 inputs and accumulate in fp32; outputs round
# to bf16 (2^-8 relative) and a TPU matmul may round fp32 operands such
# as the scaled queries and the probabilities to bf16 (2^-9 each), so
# the two may differ by about 1% of the output's scale, and not more.
KERNEL_TOL = 2e-2
# Loss agreement, 2x2 against 1x1: the same math, but matmul partials
# and gradients are summed across devices in another order, in bf16
# activations (2^-8 relative), compounding over 3 steps.
LOSS_RTOL = 1e-2


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class CompileClock:
    """Seconds spent in XLA compilation (cache retrievals included),
    from JAX's own compile-duration events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_hbm(devices) -> str:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        out.append(f"{d.id}:" + ("n/a" if peak is None
                                 else f"{peak / 2**30:.2f} GiB"))
    return " ".join(out)


def run_phase(name, fn, clock, devices):
    c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
    result = fn()
    log(f"[{name}] passed: {time.perf_counter() - t0:.1f} s wall, "
        f"{clock.seconds - c0:.1f} s compiling, "
        f"{clock.cache_hits - h0} compile-cache hits; peak HBM so far "
        f"{peak_hbm(devices)}")
    return result


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def agree(name, got, want, tol=KERNEL_TOL) -> None:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != "
          f"{want.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite values")
    err = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))
    log(f"  {name}: normalized max error {err:.3e} (tolerance {tol:g})")
    check(err <= tol, f"{name}: kernel and jnp disagree ({err:.3e} > {tol})")


# --------------------------------------------------------------------------- #
# Phases.
# --------------------------------------------------------------------------- #
def phase_device(want_count: int):
    import jax

    from repro.kernels import dispatch

    devs = jax.devices()
    d = devs[0]
    log(f"device {d.platform} / {d.device_kind} x {len(devs)}")
    check(d.platform == "tpu", f"no TPU: JAX's first device is {d.platform}")
    mode = dispatch.pallas_mode()
    check(mode == "tpu", f"kernels must run compiled on the chip; "
          f"REPRO_USE_PALLAS routes them to {mode!r}")
    check(len(devs) == want_count,
          f"expected {want_count} device(s), JAX sees {len(devs)}")
    return d


def phase_kernels(*, B=8, H=32, K=4, D=128, page=16, max_len=320,
                  train_batch=4, seq=2048, interpret=False):
    """The two attention kernels against their jnp implementations at
    the serve and train shapes below."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import dispatch
    from repro.kernels import ops  # noqa: F401 — registers the ops

    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    bf16 = jnp.bfloat16

    # Paged decode: a ragged mixed batch over a shuffled bf16 pool.
    paged = dispatch.get("paged_attention")
    npg = -(-max_len // page)
    P = B * npg + 1
    kp = jax.random.normal(keys[0], (P, K, page, D), bf16)
    vp = jax.random.normal(keys[1], (P, K, page, D), bf16)
    lens = rng.integers(1, max_len + 1, size=B)
    pt = np.full((B, npg), -1, np.int32)
    free = list(rng.permutation(P - 1))
    for b in range(B):
        pt[b, :-(-lens[b] // page)] = [free.pop() for _ in
                                       range(-(-lens[b] // page))]
    kw = dict(window=None, scale=None, kp_scale=None, vp_scale=None)
    kern = jax.jit(lambda *a, **k: paged.pallas_impl()(
        *a, interpret=interpret, **kw, **k))
    ref = jax.jit(lambda *a, **k: paged.jnp(*a, **kw, **k))
    for C in (8, 1):
        nv = np.minimum(lens, C).astype(np.int32)
        pos = (lens - nv).astype(np.int32)
        q = jax.random.normal(keys[2], (B, C, H, D), bf16)
        args = (q, kp, vp, jnp.asarray(pt))
        got = kern(*args, pos=jnp.asarray(pos), n_valid=jnp.asarray(nv))
        want = ref(*args, pos=jnp.asarray(pos), n_valid=jnp.asarray(nv))
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        sel = np.arange(C)[None, :] < nv[:, None]  # rows' valid tokens
        agree(f"paged_attention C={C}", got[sel], want[sel])

    # Flash: forward and gradients of a random projection of the output.
    attn = dispatch.get("attention")
    q = jax.random.normal(keys[3], (train_batch, seq, H, D), bf16)
    k = jax.random.normal(keys[4], (train_batch, seq, K, D), bf16)
    v = jax.random.normal(keys[5], (train_batch, seq, K, D), bf16)
    ct = jax.random.normal(keys[6], (train_batch, seq, H, D), jnp.float32)
    opts = dict(causal=True, window=None, q_offset=0, k_offset=0,
                scale=None)
    impls = {
        "flash": lambda q, k, v: attn.pallas_impl()(
            q, k, v, interpret=interpret, **opts),
        "jnp": lambda q, k, v: attn.jnp(q, k, v, chunk=512, **opts),
    }
    res = {}
    for name, f in impls.items():
        # ct is an argument, not a closure constant: baked into the
        # program it would make the executable too large to cache.
        loss = lambda q, k, v, ct, f=f: jnp.sum(
            f(q, k, v).astype(jnp.float32) * ct)
        out = jax.jit(f)(q, k, v)
        grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v, ct)
        res[name] = (out,) + tuple(grads)
    for label, got, want in zip(("out", "dq", "dk", "dv"), res["flash"],
                                res["jnp"]):
        agree(f"flash_attention {label} S={seq}", got, want)


def _cli(argv):
    from repro.run import dispatch
    from repro.run.cli import main

    log("repro " + " ".join(argv[1:]))
    rc = main(argv)
    check(rc == 0, f"repro run exited {rc}")
    result = dispatch.LAST_RESULT
    dispatch.LAST_RESULT = None  # the caller owns (and frees) it
    return result


SERVE_TOKENS = 4
SERVE_ARGS = ["--arch", "yi-9b", "--full", "--set", "model.n_layers=8",
              "--set", "serve.kv.layout=paged", "--set",
              "serve.kv.page_size=16", "--set", "serve.kv.dtype=bfloat16",
              "--set", "serve.batch=4", "--set", "serve.max_batch=8",
              "--set", "serve.prompt_len=256",
              "--set", f"serve.tokens={SERVE_TOKENS}"]
TRAIN_STEPS = 3
TRAIN_ARGS = ["--arch", "yi-9b", "--full", "--set", "model.n_layers=2",
              "--set", "model.vocab=16000", "--set", "trainer.batch=4",
              "--set", "trainer.seq=2048",
              "--set", f"trainer.total_steps={TRAIN_STEPS}",
              "--set", "trainer.log_every=1"]


def phase_serve(args=SERVE_ARGS, tokens=SERVE_TOKENS):
    import jax.numpy as jnp

    res = _cli(["run", "--mode", "serve"] + args)
    report, engine = res["report"], res["engine"]
    vocab = engine.cfg.vocab
    check(engine.layout == "paged", f"engine layout {engine.layout}")
    for r in report.requests:
        check(len(r.tokens) == tokens,
              f"request {r.id}: {len(r.tokens)} tokens, want {tokens}")
        check(all(0 <= t < vocab for t in r.tokens),
              f"request {r.id}: token outside the vocabulary")
    log(f"  served {len(report.requests)} requests, "
        f"{report.tokens_generated} tokens, prompts "
        f"{sorted(r.prompt_len for r in report.requests)}")

    # Compile proof: the chunk step the engine ran holds the paged kernel.
    sc = engine.scfg
    B, C = sc.max_batch, sc.prefill_chunk
    zeros = jnp.zeros((B,), jnp.int32)
    text = engine._chunk_jit.lower(
        engine.params, jnp.zeros((B, C), jnp.int32), engine._cache,
        jnp.asarray(engine._ptab), zeros, zeros + 1).compile().as_text()
    n = text.count("tpu_custom_call")
    log(f"  compiled serve step holds {n} tpu_custom_call site(s)")
    check(n > 0, "the compiled serve step holds no tpu_custom_call")
    del res, report, engine, text
    gc.collect()


def train_losses(args, steps=TRAIN_STEPS):
    res = _cli(["run", "--mode", "train"] + args)
    losses = [float(r["loss"]) for r in res["history"]]
    check(len(losses) == steps, f"{len(losses)} train steps, want {steps}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    log(f"  losses {losses}")
    return losses, res["trainer"]


def phase_train(args=TRAIN_ARGS):
    log("train: vocab sliced to 16000 of 64000 — one chip's quarter under "
        "four-way vocab parallelism")
    _, trainer = train_losses(args)
    del trainer
    gc.collect()


def phase_wus(args=TRAIN_ARGS):
    """C1 on four chips: the train phase under wus on the 2x2 local mesh
    against the same steps on a 1x1 mesh."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    wus = args + ["--set", "model.param_sharding=wus"]
    log("train: vocab sliced to 16000 of 64000")
    ref, trainer = train_losses(wus + ["--mesh", "single"])
    del trainer
    gc.collect()
    got, trainer = train_losses(wus + ["--mesh", "local"])
    mesh = trainer.mesh
    check(dict(mesh.shape) == {"data": 2, "model": 2},
          f"local mesh is {dict(mesh.shape)}, want 2x2")
    rel = max(abs(a - b) / abs(b) for a, b in zip(got, ref))
    log(f"  2x2 vs 1x1 loss: max relative difference {rel:.3e} "
        f"(tolerance {LOSS_RTOL:g})")
    check(rel <= LOSS_RTOL, f"2x2 losses {got} disagree with 1x1 {ref}")

    # The state spans the four devices as the rules lay it out.
    devs = jax.devices()
    held = {d.id: 0 for d in devs}
    state = trainer.state
    flat = jax.tree_util.tree_leaves_with_path(state)
    specs = jax.tree_util.tree_leaves(
        trainer.state_specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    check(len(flat) == len(specs), "state and spec trees differ")
    split = 0
    for (path, leaf), spec in zip(flat, specs):
        check(leaf.sharding.is_equivalent_to(NamedSharding(mesh, spec),
                                             leaf.ndim),
              f"{jax.tree_util.keystr(path)}: sharded {leaf.sharding}, "
              f"rules say {spec}")
        check({s.device.id for s in leaf.addressable_shards} == set(held),
              f"{jax.tree_util.keystr(path)} is not on all four devices")
        for s in leaf.addressable_shards:
            held[s.device.id] += s.data.nbytes
        if "opt" in jax.tree_util.keystr(path) and leaf.ndim >= 2:
            shard = leaf.addressable_shards[0].data.shape
            split += int(np.prod(shard)) * len(devs) == leaf.size
    total = sum(leaf.nbytes for _, leaf in flat)
    log("  state bytes per device: " + " ".join(
        f"{i}:{b / 2**30:.2f} GiB" for i, b in held.items())
        + f" (logical total {total / 2**30:.2f} GiB)")
    check(split > 0, "no optimizer moment is split four ways")
    check(min(held.values()) > 0.5 * max(held.values()),
          "state is not spread over the four devices")
    check(max(held.values()) < 0.5 * total,
          "a device holds half the state or more: not sharded")
    del trainer, state, flat
    gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    from repro.run.cli import use_compile_cache

    cache = use_compile_cache()
    import jax

    clock = CompileClock()
    log(f"compile cache {cache}")
    dev = run_phase("device", lambda: phase_device(args.chips), clock,
                    jax.devices()[:1])
    devices = jax.devices()
    log(f"set-up {time.perf_counter() - t0:.1f} s (imports, runtime)")
    if args.chips == 4:
        run_phase("train wus 2x2 vs 1x1", phase_wus, clock, devices)
    else:
        run_phase("kernels", phase_kernels, clock, devices)
        run_phase("serve", phase_serve, clock, devices)
        run_phase("train", phase_train, clock, devices)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s, "
        f"{clock.seconds:.1f} s compiling, {clock.cache_hits} "
        "compile-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
