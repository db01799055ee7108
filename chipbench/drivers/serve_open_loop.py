"""Open-loop serving: requests fall due on the host clock at the mix's
rate, whatever the server does, and every time is taken from the
moment a request was due.

Set-up builds the paged engine (``repro.serve.Engine``) on the seeded
weights, warms its one chunk program, and then serves the mix's
``warmup_s`` seconds of traffic, so that the window starts with the
engine as full as the load keeps it. One client loop drives
``Engine.submit`` and ``Engine.step`` from the warm-up on: before each
step it submits every request now due (its lateness is the
generator's), after each step it stamps each new token on the host
clock. After the window the generator keeps sending on schedule until
every request due in the window has its first token, or ``tail_s`` has
passed; one still without a token then counts as failed.

End to end, over every request due in the window: ``ttft_p50_ms``, the
median time from due to first token (a few tens of requests fall due,
too few for a tail), and ``itl_p95_ms``, the 95th percentile of the
gaps between a request's successive tokens, as observed until the loop
ends. ``correct``: a seeded sample of the requests finished by then,
the longest among them, is scored by the plain float32 reference
(``chipbench.reference.served_gaps``): the widest gap by which a served
token's logit lies below the reference's best must stay under the
cell's limit.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import generator
from chipbench import harness as H
from chipbench import program
from chipbench import weights as W


class Serve:
    def __init__(self, config: dict, mix: dict, seed: int, chips: int = 1):
        from repro.dist import Rules
        from repro.launch.mesh import single_device_mesh
        from repro.serve import Engine, ServeConfig

        if chips != 1:
            raise H.Failure("the open-loop serving driver runs one chip")
        self.config, self.mix, self.seed = config, mix, seed
        self.mc = program.model_config(config)
        e = mix["engine"]
        self.scfg = ServeConfig(
            max_batch=e["max_batch"], max_len=e["max_len"],
            prefill_len=e["max_len"], temperature=0.0, seed=0,
            kv_layout="paged", page_size=e["page_size"],
            prefill_chunk=e["prefill_chunk"], n_pages=e.get("n_pages"),
            kv_dtype=e["kv_dtype"])
        program.check_layout(self.mc, {k: s for k, (s, _) in
                                       W.shapes(config).items()})
        self.mesh = single_device_mesh()
        self.rules = Rules(self.mesh, self.mc.param_sharding)
        self.params = program.to_tree(W.make(config, seed))
        with self.mesh:
            self.engine = Engine(self.mc, self.params, self.rules, self.scfg)
            self._warm()

    def _warm(self) -> None:
        """One prefill of two chunks, then decode steps: the one chunk
        program and the sampling op, at the window's shapes."""
        from repro.serve.request import Request

        C = self.scfg.prefill_chunk
        for i in range(2):
            self.engine.submit(Request(prompt=[1 + i] * (C + 3),
                                       max_new_tokens=3))
        self.engine.drain()
        self.engine.finalize(time.perf_counter())

    # ------------------------------------------------------------------ #
    def window(self, seconds: float, seed: int, trace_dir: str = "",
               rate: float = 0.0) -> dict:
        """Serve one window; returns the measurements (requests, steps)."""
        import jax

        from repro.serve.request import Request

        mix = dict(self.mix)
        if rate:
            mix["rate_per_s"] = rate
        planned = generator.plan(mix, self.config["vocab_size"], seconds,
                                 seed)
        warm = mix.get("warmup_s", 0.0)
        reqs = [Request(prompt=p.prompt, max_new_tokens=p.max_new)
                for p in planned]
        tail_s = mix.get("tail_s", 0.0)
        trace_from = mix.get("trace_from_s", 0.0)
        trace_to = trace_from + mix.get("trace_s", seconds)
        eng = self.engine
        C = self.scfg.prefill_chunk

        seen = {}                  # request index -> tokens observed
        stamps = [[] for _ in reqs]  # host time of each token
        first_step = [None] * len(reqs)
        submitted = [None] * len(reqs)
        steps = []                 # (t_start, t_end, traced)
        produced = []              # per step: [(request index, n new)]
        nxt = 0
        no_token = set(i for i, p in enumerate(planned) if p.in_window)
        # The profiler starts 5 s before the window (its start stalls
        # the host for a second or more); the traced span then marks the
        # part of the window that is read.
        tracing = traced_span = None
        to_start = bool(trace_dir)
        t0 = time.perf_counter() + 0.05 + max(warm, 5.0 if to_start else 0)
        with self.mesh:
            while True:
                now = time.perf_counter()
                if now >= t0 + seconds and (
                        not no_token or now >= t0 + seconds + tail_s):
                    break
                if to_start and now >= t0 - 5.0:
                    jax.profiler.start_trace(trace_dir)
                    to_start, tracing = False, False
                if tracing is False and now >= t0 + trace_from:
                    traced_span = jax.profiler.TraceAnnotation(
                        "chipbench.traced")
                    traced_span.__enter__()
                    tracing = True
                if tracing and now >= t0 + trace_to:
                    traced_span.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    tracing = None
                with jax.profiler.TraceAnnotation("chipbench.generate"):
                    while (nxt < len(reqs)
                           and t0 + planned[nxt].due_s <= now):
                        reqs[nxt].arrival_step = eng.current_step
                        eng.submit(reqs[nxt])
                        submitted[nxt] = time.perf_counter()
                        seen[nxt] = 0
                        nxt += 1
                if not seen:
                    due = t0 + planned[nxt].due_s if nxt < len(reqs) else now
                    with jax.profiler.TraceAnnotation("chipbench.sleep"):
                        time.sleep(max(0.0, min(due - now, 0.01)))
                    continue
                s0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("chipbench.step"):
                    eng.step()
                s1 = time.perf_counter()
                steps.append((s0, s1, bool(tracing)))
                with jax.profiler.TraceAnnotation("chipbench.client"):
                    new = []
                    for i in list(seen):
                        n = len(reqs[i].tokens)
                        if n > seen[i]:
                            if seen[i] == 0:
                                first_step[i] = len(steps) - 1
                                no_token.discard(i)
                            stamps[i].extend([s1] * (n - seen[i]))
                            new.append((i, n - seen[i]))
                            seen[i] = n
                        if reqs[i].t_done is not None:
                            del seen[i]
                    produced.append(new)
            if tracing:
                traced_span.__exit__(None, None, None)
            if tracing is not None:
                jax.profiler.stop_trace()
            t_end = time.perf_counter()
            report = eng.finalize(t0)

        win = [i for i, p in enumerate(planned) if p.in_window]
        ttft, itl, late = [], [], []
        for i in win:
            if submitted[i] is not None:
                late.append(submitted[i] - (t0 + planned[i].due_s))
            if stamps[i]:
                ttft.append(stamps[i][0] - (t0 + planned[i].due_s))
                itl.extend(np.diff(stamps[i]).tolist())
        return {
            "t0": t0, "t_window_end": t0 + seconds, "t_end": t_end,
            "planned": planned, "requests": reqs, "window": win,
            "ttft_s": ttft, "itl_s": itl, "late_s": late,
            "failed": len(no_token), "steps": steps, "produced": produced,
            "first_step": first_step, "report": report, "chunk": C,
        }

    # ------------------------------------------------------------------ #
    def rows(self, m: dict) -> list:
        """Per step, the rows it fed: (pos, n_valid, emits). Rebuilt from
        the tokens observed: a request's prompt streams in chunks over
        the steps just before its first token, then each later step
        feeds its previous token. Valid when nothing was preempted."""
        C = m["chunk"]
        rows = [[] for _ in m["steps"]]
        for i, req in enumerate(m["requests"]):
            k = m["first_step"][i]
            if k is None:
                continue
            P = req.prompt_len
            n_chunks = -(-P // C)
            for j in range(n_chunks):
                s = k - n_chunks + 1 + j
                if s >= 0:
                    rows[s].append((j * C, min(C, P - j * C),
                                    j == n_chunks - 1))
        count = [0] * len(m["requests"])  # tokens produced so far
        for s, new in enumerate(m["produced"]):
            for i, n in new:
                if m["first_step"][i] == s:  # the prefill row emitted it
                    count[i] += 1
                    n -= 1
                for _ in range(n):  # token j fed back at position P + j
                    rows[s].append((m["requests"][i].prompt_len + count[i]
                                    - 1, 1, True))
                    count[i] += 1
        return rows

    # ------------------------------------------------------------------ #
    def free(self) -> None:
        """Drop the engine's weights and KV pool before the reference
        runs on the same chip."""
        if self.engine is not None:
            self.engine.params = self.engine._cache = None
        self.engine = self.params = None
        gc.collect()

    def sample(self, m: dict, seed: int) -> list:
        """The window's requests finished when the loop ended: the
        longest, then others drawn from the seed until ``check_tokens``
        served tokens."""
        done = [i for i in m["window"] if m["requests"][i].t_done is not None]
        if not done:
            return []
        reqs = m["requests"]
        longest = max(done, key=lambda i: reqs[i].prompt_len
                      + len(reqs[i].tokens))
        rng = np.random.default_rng(int(seed) + 1)
        order = [longest] + [i for i in rng.permutation(done)
                             if i != longest]
        out, n = [], 0
        for i in order:
            out.append(i)
            n += len(reqs[i].tokens)
            if n >= self.mix["check_tokens"]:
                break
        return out

    def check(self, m: dict, seed: int, precisions=("f32",)) -> dict:
        """Widest logit gap over the sample (engine freed first), for
        the served tokens ("f32") and for each control precision."""
        from chipbench import reference as R

        picks = self.sample(m, seed)
        self.free()
        w = W.make(self.config, self.seed)
        widest = dict.fromkeys(precisions, 0.0)
        n_tok = 0
        for i in picks:
            r = m["requests"][i]
            for p in precisions:
                g = R.served_gaps(w, self.config, r.prompt, r.tokens, p)
                widest[p] = max(widest[p], float(np.max(g)))
            n_tok += len(r.tokens)
        del w
        gc.collect()
        return {"max_logit_gap": widest, "requests": len(picks),
                "tokens": n_tok}


def _p95_ms(xs) -> float:
    return float(np.percentile(np.asarray(xs) * 1e3, 95))


def run(ctx: dict) -> dict:
    args, cell = ctx["args"], ctx["cell"]
    limits = ctx["limits"]
    serve = Serve(ctx["config"], ctx["traffic"], args.seed, cell["chips"])
    tdir = H.trace_dir(cell["name"]) if args.trace else ""
    m = serve.window(args.seconds, args.seed, tdir)
    setup_s = m["t0"] - ctx["t_start"]  # the warm-up traffic included
    compile_setup = ctx["clock"].seconds(t1=m["t0"])
    peak = H.peak_bytes(ctx["devs"])
    n_comp = ctx["clock"].count(m["t0"], min(m["t_window_end"], m["t_end"]))
    rep = m["report"]
    H.log(f"window {args.seconds} s + tail {m['t_end'] - m['t_window_end']:.3f}"
          f" s (bound {ctx['traffic'].get('tail_s', 0.0)} s); requests due "
          f"{len(m['window'])}, without a first token {m['failed']}; steps "
          f"{len(m['steps'])}; tokens {rep.tokens_generated}; preemptions "
          f"{rep.preemptions}")
    if m["late_s"]:
        H.log(f"generator lateness p95 {_p95_ms(m['late_s']):.3f} ms, max "
              f"{max(m['late_s']) * 1e3:.3f} ms")
    H.log(f"compilations inside the window: {n_comp}; peak HBM "
          f"{peak / 2**30:.3f} GiB on the fullest device; set-up "
          f"{setup_s:.3f} s, {compile_setup:.3f} s compiling")
    if n_comp:
        raise H.Failure(f"{n_comp} compilations inside the measured window")

    metrics, extra = {}, {}
    if args.trace:
        from chipbench import trace as T

        tr = T.load(T.find(tdir))
        lo, hi = tr.window("chipbench.traced")
        run_rec = {"kind": "serve", "config": ctx["config"],
                   "peak": ctx["peak"], "trace": tr, "window": (lo, hi),
                   "chips": cell["chips"], "steps": m["steps"],
                   "rows": serve.rows(m) if rep.preemptions == 0 else None,
                   "report": rep, "compile_s": compile_setup,
                   "engine": ctx["traffic"]["engine"]}
        metrics = H.read_per_layer(ctx["bench"], cell["name"], run_rec,
                                   ctx["base"])
        extra = {"device": H.device_summary(tr, lo, hi),
                 "breakdown": H.breakdown(tr, lo, hi)}
    else:
        if m["ttft_s"]:
            metrics["ttft_p50_ms"] = {
                "value": float(np.median(m["ttft_s"]) * 1e3), "unit": "ms"}
        if m["itl_s"]:
            metrics["itl_p95_ms"] = {"value": _p95_ms(m["itl_s"]),
                                     "unit": "ms"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        H.log(f"ttft samples {len(m['ttft_s'])}, itl samples "
              f"{len(m['itl_s'])}")

    c = serve.check(m, args.seed)
    H.log(f"scored {c['requests']} requests, {c['tokens']} served tokens")
    gap, gap_limit = c["max_logit_gap"]["f32"], limits["max_logit_gap"]
    checks = {"max_logit_gap": {"value": gap, "limit": gap_limit},
              "served_tokens_scored": {"value": c["tokens"],
                                       "limit": ctx["traffic"]["check_tokens"]}}
    correct = (gap <= gap_limit
               and c["tokens"] >= ctx["traffic"]["check_tokens"])
    device = H.device_info(ctx["devs"], peak)
    device.update(extra.get("device", {}))
    return {"correct": correct, "attempted": len(m["window"]),
            "failed": m["failed"], "metrics": metrics, "device": device,
            "checks": checks, "breakdown": extra.get("breakdown")}
