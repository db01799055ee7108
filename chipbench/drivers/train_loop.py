"""Training loop: ``repro.train.Trainer`` on seeded synthetic tokens.

Set-up builds one trainer, gives it the benchmark's seeded weights (and
the optimizer's fresh state for them), and drives its compiled step
through the first ``check_steps`` steps with ``Trainer.fit``, the call
and feed the window uses; those steps compile the program and give the
readings that decide ``correct``. The window then hands the same
trainer batch after batch while the host clock is inside it, keeping
the mix's ``in_flight`` steps dispatched ahead of the one it waits for
(a hook waits for the loss of the step that many back before the next
batch is drawn), so that the chip stays fed while the host stands
still. When the time is up no batch is sent; ``fit`` waits for every
step sent, and the clock is read after that wait. ``train_tok_s`` is
every token of every step the window sent over the time from its start
to the last of them completing.

``correct`` compares the first steps with the plain float32 reference
(``chipbench.reference.train_steps``) from the same weights and
batches: each step's loss, the norm of the first step's gradient as the
optimizer received it (Adam's first moment after one step, over
1 - b1) and the norm of its difference from the reference's gradient,
and the norm of each leaf's change over the steps, each leaf held
against the reference's leaf or the median leaf, whichever is larger.
"""
from __future__ import annotations

import collections
import gc
import time

import numpy as np

from chipbench import harness as H
from chipbench import program
from chipbench import weights as W


def batches(mix: dict, vocab: int, seed: int):
    """Endless seeded batches of uniform token ids; all rows differ."""
    rng = np.random.default_rng(int(seed))
    shape = (mix["batch"], mix["seq"])
    while True:
        yield {"tokens": rng.integers(0, vocab, shape, dtype=np.int32)}


class Train:
    def __init__(self, config: dict, mix: dict, seed: int, chips: int = 1):
        import jax
        from jax.sharding import NamedSharding

        from repro.launch.mesh import local_mesh, single_device_mesh
        from repro.train import Trainer, TrainerConfig

        self.config, self.mix, self.seed = config, mix, seed
        self.mc = program.model_config(config, mix.get("overrides"))
        program.check_layout(self.mc, {k: s for k, (s, _) in
                                       W.shapes(config).items()})
        mesh = local_mesh() if mix["mesh"] == "local" else single_device_mesh()
        if mesh.devices.size != chips:
            raise H.Failure(f"mesh of {mesh.devices.size} devices for a "
                            f"{chips}-chip cell")
        opt = mix["optimizer"]
        self.trainer = t = Trainer(self.mc, mesh, TrainerConfig(
            total_steps=opt["total_steps"], log_every=1 << 30, seed=0))
        t.state = None  # the trainer's own weights: replaced by the seeded
        gc.collect()
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t.state_specs)
        build, opt_init = W.build(config), t.optimizer.init

        def state(key):
            params = program.to_tree(build(key))
            return {"params": params, "opt": opt_init(params)}

        with mesh:
            t.state = jax.jit(state, out_shardings=shardings)(
                W.seed_key(seed))
        self.feed = batches(mix, config["vocab_size"], seed)
        self.first = []  # the batches of the first steps

    def first_steps(self) -> dict:
        """The first ``check_steps`` steps, through ``fit``: their losses,
        the first gradient (its leaf norms, and the leaves on the host)
        and the change's leaf norms."""
        import jax

        from chipbench import reference as R

        n = self.mix["check_steps"]
        b1 = self.mix["optimizer"]["b1"]
        self.first = [next(self.feed) for _ in range(n)]
        hist = self.trainer.fit(iter(self.first[:1]), hooks=[])
        m = program.from_tree(self.trainer.state["opt"]["m"])
        g1 = {k: float(v) / (1 - b1) for k, v in R.leaf_norms(m).items()}
        g1_host = {k: np.asarray(v, np.float32) / np.float32(1 - b1)
                   for k, v in m.items()}
        del m
        hist += self.trainer.fit(iter(self.first[1:]), hooks=[])
        p = program.from_tree(self.trainer.state["params"])
        change = R.change_norms(self.config, self.seed, p)
        jax.block_until_ready(self.trainer.state)
        return {"losses": [h["loss"] for h in hist], "grad": g1,
                "g1": g1_host, "change": change}

    def window(self, seconds: float, trace_dir: str = "") -> dict:
        """Steps while the host clock is inside the window. Traced, the
        profiler records its first ``trace_s`` seconds (span
        ``chipbench.traced``)."""
        import jax

        from repro.train.hooks import Hook

        trace_s = self.mix.get("trace_s", seconds)
        depth = self.mix.get("in_flight", 1)

        class InFlight(Hook):
            """Wait for the loss of the step ``depth`` back, so that many
            stay in flight beyond it; stop the profiler once the traced
            part has passed."""
            sent = collections.deque()
            tracing = bool(trace_dir)

            def on_step(self, trainer, step, record):
                self.sent.append(record["loss"])
                if len(self.sent) > depth:
                    with jax.profiler.TraceAnnotation("chipbench.wait"):
                        jax.block_until_ready(self.sent.popleft())
                if self.tracing and time.perf_counter() >= t0 + trace_s:
                    traced.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    self.tracing = False

        def feed(deadline):
            while time.perf_counter() < deadline:
                with jax.profiler.TraceAnnotation("chipbench.batch"):
                    b = next(self.feed)
                yield b

        hook = InFlight()
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
            # a span records only if the profiler runs when it is made
            traced = jax.profiler.TraceAnnotation("chipbench.traced")
            traced.__enter__()
        t0 = time.perf_counter()
        hist = self.trainer.fit(feed(t0 + seconds), hooks=[hook])
        t1 = time.perf_counter()
        if hook.tracing:
            traced.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return {"t0": t0, "t1": t1, "steps": len(hist),
                "losses": [h["loss"] for h in hist]}

    def free(self) -> None:
        """Drop the trainer's state before the reference runs on the
        same chip(s)."""
        if self.trainer is not None:
            self.trainer.state = None
        self.trainer = None
        gc.collect()

    def reference(self, precision: str = "f32", rows: int = 0,
                  against=None, keep_grad: bool = False) -> dict:
        """The plain reference over the first steps' batches
        (``chipbench.reference.train_steps``); ``rows`` keeps only that
        many rows of each (the half-batch fault)."""
        from chipbench import reference as R

        keep = rows or self.mix["batch"]
        return R.train_steps(
            self.config, self.seed, [b["tokens"][:keep] for b in self.first],
            self.mix["optimizer"], precision, against, keep_grad)


def _leaf_gap(got: dict, want: dict, leaves) -> float:
    """Worst leaf: |norm got - norm want| over the larger of the
    reference's norm of that leaf and the median leaf's."""
    med = float(np.median([want[k] for k in leaves]))
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in leaves)


def compare(prog: dict, ref: dict, diff: dict) -> dict:
    """The compared numbers; ``diff`` holds the per-leaf norms of the
    first gradients' difference. Leaves whose reference gradient is
    under a thousandth of the median leaf's move by round-off alone and
    are left out of the change."""
    leaves = list(ref["grad"])
    g_med = float(np.median(list(ref["grad"].values())))
    moving = [k for k in leaves if ref["grad"][k] >= 1e-3 * g_med]
    loss = max(abs(a - b) / abs(b) for a, b in
               zip(prog["losses"], ref["losses"]))
    return {"loss_rel_gap": loss,
            "grad_norm_gap": _leaf_gap(prog["grad"], ref["grad"], leaves),
            "grad_diff": max(diff[k] / max(ref["grad"][k], g_med)
                             for k in leaves),
            "change_norm_gap": _leaf_gap(prog["change"], ref["change"],
                                         moving)}


def run(ctx: dict) -> dict:
    args, cell, mix = ctx["args"], ctx["cell"], ctx["traffic"]
    tr = Train(ctx["config"], mix, args.seed, cell["chips"])
    prog = tr.first_steps()
    setup_s = time.perf_counter() - ctx["t_start"]
    compile_setup = ctx["clock"].seconds()
    tdir = H.trace_dir(cell["name"]) if args.trace else ""
    m = tr.window(args.seconds, tdir)
    peak = H.peak_bytes(ctx["devs"])
    n_comp = ctx["clock"].count(m["t0"], m["t1"])
    tokens = m["steps"] * mix["batch"] * mix["seq"]
    H.log(f"first steps' losses {prog['losses']}")
    H.log(f"window {m['t1'] - m['t0']:.6f} s ({args.seconds} s asked), "
          f"{m['steps']} steps, {tokens} tokens, last loss "
          f"{m['losses'][-1] if m['losses'] else None}")
    H.log(f"compilations inside the window: {n_comp}; peak HBM "
          f"{peak / 2**30:.3f} GiB on the fullest device; set-up "
          f"{setup_s:.3f} s, {compile_setup:.3f} s compiling")
    if n_comp:
        raise H.Failure(f"{n_comp} compilations inside the measured window")
    metrics, extra = {}, {}
    if args.trace:
        from chipbench import trace as T

        trc = T.load(T.find(tdir))
        lo, hi = trc.window("chipbench.traced")
        run_rec = {"kind": "train", "config": ctx["config"],
                   "peak": ctx["peak"], "trace": trc, "window": (lo, hi),
                   "chips": cell["chips"], "mix": mix, "steps": m["steps"],
                   "window_s": m["t1"] - m["t0"], "compile_s": compile_setup}
        metrics = H.read_per_layer(ctx["bench"], cell["name"], run_rec,
                                   ctx["base"])
        extra = {"device": H.device_summary(trc, lo, hi),
                 "breakdown": H.breakdown(trc, lo, hi)}
    else:
        metrics["train_tok_s"] = {"value": tokens / (m["t1"] - m["t0"]),
                                  "unit": "tokens/s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    tr.free()
    ref = tr.reference(against=prog.pop("g1"))
    got = compare(prog, ref, ref["diff"])
    H.log(f"reference losses {ref['losses']}")
    limits = ctx["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in got.items()}
    correct = all(v <= limits[k] for k, v in got.items())
    device = H.device_info(ctx["devs"], peak)
    device.update(extra.get("device", {}))
    return {"correct": correct, "attempted": m["steps"], "failed": 0,
            "metrics": metrics, "device": device, "checks": checks,
            "breakdown": extra.get("breakdown")}
