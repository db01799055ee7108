"""Spec dispatcher: resolve a :class:`RunSpec` to config, mesh and
subsystem, and run it.

    run_spec(spec) -> result dict (always carries "exit_code")

One runner per mode:

  * ``train`` — hook-based :class:`repro.train.Trainer` over synthetic
    LM batches (optionally resuming from a checkpoint, optionally
    emitting a ``BENCH_*.json`` of the run via ``BenchRecordHook``);
  * ``eval``  — the distributed-eval loop (C4) alone, on fresh or
    resumed parameters;
  * ``serve`` — the continuous-batching ``serve.Engine`` in an MLPerf-
    Inference scenario (offline | server | single_stream |
    multi_stream), optionally with SLO classes (``serve.slo_classes``);
  * ``bench`` — the registered benchmark suite, spec-addressable via
    ``bench.only``, artifact in the versioned BENCH schema;
  * ``dryrun`` — AOT lower+compile on the production meshes (the
    512-device XLA flag must be set before jax initializes — the CLI
    does this; see ``run.cli``).

Everything jax-touching is imported lazily inside the runners so spec
construction and validation stay import-cheap (and jax-free).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from repro.run.spec import RunSpec

# Result of the most recent run_spec() in this process — lets in-process
# callers of a CLI entry point (tests, notebooks) reach the structured
# result (history, reports, artifacts) behind the printed output.
LAST_RESULT: Optional[Dict[str, Any]] = None


def resolve_config(spec: RunSpec):
    """arch -> ModelConfig, after ``reduced()`` and model overrides (in
    that order, so a spec override beats the smoke-variant defaults)."""
    from repro.configs import base as config_base
    from repro.configs import get_config

    cfg = get_config(spec.arch)
    if spec.reduced:
        cfg = cfg.reduced()
    if spec.model:
        cfg = config_base.apply_overrides(cfg, spec.model)
    return cfg


def build_mesh(spec: RunSpec):
    from repro.launch.mesh import (
        local_mesh,
        make_production_mesh,
        single_device_mesh,
    )

    if spec.mesh == "single":
        return single_device_mesh()
    if spec.mesh == "local":
        return local_mesh()
    return make_production_mesh(multi_pod=spec.mesh == "multipod")


def run_spec(spec: RunSpec) -> Dict[str, Any]:
    global LAST_RESULT
    LAST_RESULT = None  # release the previous run's state (Trainer/Engine
    #                     trees are large) before this one allocates
    runner = {
        "train": _run_train,
        "eval": _run_eval,
        "serve": _run_serve,
        "bench": _run_bench,
        "dryrun": _run_dryrun,
    }[spec.mode]
    result = runner(spec)
    result.setdefault("exit_code", 0)
    LAST_RESULT = result
    return result


# --------------------------------------------------------------------------- #
# train / eval
# --------------------------------------------------------------------------- #
def _make_trainer(spec: RunSpec):
    from repro.train import Trainer, TrainerConfig

    t = spec.trainer
    tcfg = TrainerConfig(
        total_steps=t.total_steps,
        eval_every=t.eval_every,
        checkpoint_every=t.checkpoint_every,
        checkpoint_dir=t.checkpoint_dir,
        log_every=t.log_every,
        seed=spec.seed,
        metrics=t.metrics,
        async_checkpoint=t.async_checkpoint,
        double_buffer=t.data.pipeline == "async",
        metrics_out=t.metrics_out,
    )
    return Trainer(resolve_config(spec), build_mesh(spec), tcfg)


def _run_train(spec: RunSpec) -> Dict[str, Any]:
    import itertools

    from repro.data.pipeline import synthetic_eval_set, synthetic_lm_batches
    from repro.train.hooks import BenchRecordHook

    t = spec.trainer
    trainer = _make_trainer(spec)
    start = trainer.resume(t.resume) if t.resume else 0
    pipeline = None
    if t.data.pipeline == "async":
        # Streaming pipeline: shard-addressed source (per-shard RNG, so
        # the resume seek below is O(1)) -> optional checksum-verified
        # cache -> background prefetch. A resumed run starts at the
        # stream position its checkpointed steps had consumed, so
        # interrupted + resumed == uninterrupted, step for step.
        from repro.data import Pipeline, SyntheticShardSource

        source = SyntheticShardSource(
            trainer.cfg, batch=t.batch, seq=t.seq,
            n_batches=t.total_steps, shard_size=t.data.shard_size,
            seed=spec.seed,
        )
        pipeline = Pipeline(
            source, cache_dir=t.data.cache_dir or None,
            prefetch_depth=t.data.prefetch_depth, start_batch=start,
            verify_cache=t.data.verify_cache,
        )
        batches = pipeline
    else:
        # One deterministic stream for the whole run: a resumed run skips
        # the batches the checkpointed steps already consumed, so
        # interrupted + resumed == uninterrupted, step for step.
        batches = synthetic_lm_batches(
            trainer.cfg, batch=t.batch, seq=t.seq, steps=t.total_steps,
            seed=spec.seed,
        )
        if start:
            batches = itertools.islice(batches, start, None)
    eval_fn = None
    if t.eval_every:
        eval_fn = synthetic_eval_set(trainer.cfg, batch=t.batch, seq=t.seq)
    hooks = trainer.default_hooks(eval_fn)
    if t.bench_out:
        hooks.append(BenchRecordHook(t.bench_out, arch=trainer.cfg.name,
                                     tag=f"train-{spec.arch}"))
    try:
        history = trainer.fit(batches, eval_fn, hooks=hooks)
    finally:
        if pipeline is not None:
            pipeline.close()
    print("done", history[-1] if history else "")
    return {"history": history, "trainer": trainer}


def _run_eval(spec: RunSpec) -> Dict[str, Any]:
    from repro.data.pipeline import synthetic_eval_set

    t = spec.trainer
    trainer = _make_trainer(spec)
    if t.resume:
        trainer.resume(t.resume)
    eval_fn = synthetic_eval_set(trainer.cfg, batch=t.batch, seq=t.seq)
    record = trainer.evaluate(eval_fn)
    print(f"eval {trainer.cfg.name}"
          f"{' @ step ' + str(trainer.start_step) if t.resume else ''}: "
          f"nll={record['eval_nll']:.4f}")
    return {"eval": record, "trainer": trainer}


# --------------------------------------------------------------------------- #
# serve
# --------------------------------------------------------------------------- #
def _run_serve(spec: RunSpec) -> Dict[str, Any]:
    import jax

    from repro.dist import Rules, split_tree, use_rules
    from repro.serve import Engine, ServeConfig
    from repro.serve.engine import synthetic_requests
    from repro.serve.scenarios import make_trace, scenario_driver
    from repro.train.steps import ModelAPI

    s = spec.serve
    scenario = spec.scenario or "offline"
    cfg = resolve_config(spec)
    mesh = build_mesh(spec)
    rules = Rules(mesh, s.serve_mode or cfg.param_sharding)
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(spec.seed)))

    n_media = cfg.n_media_tokens if cfg.frontend == "vision_patches" else 0
    kv = s.kv
    scfg = ServeConfig(
        max_batch=s.batch if s.max_batch is None else s.max_batch,
        max_len=n_media + s.prompt_len + s.tokens,
        prefill_len=s.prompt_len,
        temperature=s.temperature,
        seed=spec.seed,
        kv_layout=kv.layout,
        page_size=kv.page_size,
        prefill_chunk=kv.prefill_chunk,
        n_pages=kv.n_pages,
        prefix_cache=kv.prefix_cache,
        kv_dtype=kv.dtype,
        spec_decode=kv.spec_decode,
        draft_len=kv.draft_len,
    )
    reqs = make_trace(
        cfg, scenario=scenario, n=s.batch, tokens=s.tokens,
        prompt_len=s.prompt_len, seed=spec.seed, rate=s.arrival_rate,
        pattern=s.arrival_pattern, query_size=s.query_size,
        query_interval=s.query_interval, slo_classes=s.slo_classes,
        shared_prefix_len=s.shared_prefix_len, n_templates=s.n_templates)

    if spec.fleet.n_replicas >= 1:
        return _run_fleet(spec, cfg, mesh, rules, params, scfg, reqs)

    with mesh, use_rules(rules):
        engine = Engine(cfg, params, rules, scfg)
        if s.warmup:
            # compile the prefill/decode programs (both prefill argument
            # layouts) so the reported metrics measure serving, not XLA
            scenario_driver("offline")(engine, synthetic_requests(
                cfg, n=min(2, scfg.max_batch), tokens=2,
                prompt_len=s.prompt_len, scenario="offline",
                seed=spec.seed + 1))
        report = scenario_driver(scenario)(engine, reqs)

    print(f"{spec.arch} [{scenario}, mode="
          f"{s.serve_mode or cfg.param_sharding}, "
          f"slots={scfg.max_batch}, "
          f"kv={engine.layout}{'/' + kv.dtype if kv.dtype else ''}]: "
          f"{report.format()}")
    if report.prefix_hit_rate is not None:
        print(f"  prefix cache: hit_rate {report.prefix_hit_rate:.3f}, "
              f"{report.pages_shared} pages shared, "
              f"{report.prefill_tokens_skipped} prefill tokens skipped, "
              f"{report.cow_copies} cow copies")
    if report.spec_accept_rate is not None:
        print(f"  speculative: accept_rate {report.spec_accept_rate:.3f}, "
              f"{report.draft_tokens} draft tokens proposed")
    if s.slo_classes:
        print(f"  slo: goodput {report.slo_goodput:.3f}, "
              f"{report.slo_violations} violation(s)")
        for name, m in sorted(report.per_class().items()):
            print(f"    {name}: n={m['requests']} "
                  f"p99 {m['p99_ms']:.1f}ms "
                  f"ttft_p99 {m['ttft_p99_ms']:.1f}ms "
                  f"violations {m['violations']} "
                  f"goodput {m['goodput']:.3f}")
    for req in sorted(report.requests, key=lambda r: r.id):
        print(f"  req {req.id}: prompt {req.prompt_len} -> "
              f"{len(req.tokens)} tokens {req.tokens}")
    return {"report": report, "engine": engine}


def _run_fleet(spec: RunSpec, cfg, mesh, rules, params, scfg,
               reqs) -> Dict[str, Any]:
    """Serve-mode fleet path: the same workload over ``fleet.n_replicas``
    identical engines behind the prefix-affinity router, with the spec's
    seeded chaos plan (if any) injected mid-run."""
    from repro.dist import use_rules
    from repro.fleet import ChaosPlan, Fleet, FleetConfig
    from repro.serve import Engine
    from repro.serve.engine import synthetic_requests

    f = spec.fleet
    s = spec.serve
    chaos = ChaosPlan.from_spec(
        f.chaos, chaos_step=f.chaos_step, stall_steps=f.stall_steps,
        seed=spec.seed)
    fcfg = FleetConfig(routing=f.routing,
                       heartbeat_timeout=f.heartbeat_timeout)
    with mesh, use_rules(rules):
        engines = [Engine(cfg, params, rules, scfg)
                   for _ in range(f.n_replicas)]
        if s.warmup:
            from repro.serve.scenarios import scenario_driver
            for e in engines:
                scenario_driver("offline")(e, synthetic_requests(
                    cfg, n=min(2, scfg.max_batch), tokens=2,
                    prompt_len=s.prompt_len, scenario="offline",
                    seed=spec.seed + 1))
        fleet = Fleet(engines, fcfg, chaos)
        report = fleet.run(reqs)

    print(f"{spec.arch} [fleet x{f.n_replicas}, routing={f.routing}"
          f"{', chaos=' + f.chaos if f.chaos else ''}, "
          f"slots={scfg.max_batch}/replica, kv={engines[0].layout}]: "
          f"{report.format()}")
    if s.slo_classes:
        for name, m in sorted(report.per_class().items()):
            print(f"    {name}: n={m['requests']} "
                  f"p99 {m['p99_ms']:.1f}ms "
                  f"violations {m['violations']} "
                  f"goodput {m['goodput']:.3f}")
    for req in sorted(report.merged.requests, key=lambda r: r.id):
        print(f"  req {req.id}: prompt {req.prompt_len} -> "
              f"{len(req.tokens)} tokens {req.tokens}")
    return {"report": report, "fleet": fleet}


# --------------------------------------------------------------------------- #
# bench
# --------------------------------------------------------------------------- #
def _run_bench(spec: RunSpec) -> Dict[str, Any]:
    import time

    from repro.bench import schema
    from repro.bench.registry import Context
    from repro.bench.run import run_suite

    b = spec.bench
    t0 = time.perf_counter()
    entries, failures = run_suite(
        smoke=b.smoke, only=list(b.only) or None, warmup=b.warmup,
        iters=b.iters, verbose=not b.quiet,
    )
    elapsed = time.perf_counter() - t0

    probe = Context(smoke=b.smoke, warmup=b.warmup, iters=b.iters,
                    verbose=False)
    artifact = schema.make_artifact(
        entries, tag=b.tag, smoke=b.smoke,
        warmup=probe.warmup, iters=probe.iters,
    )
    out = b.out or f"BENCH_{b.tag}.json"
    schema.dump(artifact, out)

    n_rec = sum(len(e["records"]) for e in entries.values())
    print(f"\n{len(entries) - failures}/{len(entries)} benchmarks ok, "
          f"{n_rec} records, {elapsed:.1f}s -> {out}", flush=True)
    return {"out": out, "artifact": artifact, "failures": failures,
            "exit_code": 1 if failures else 0}


# --------------------------------------------------------------------------- #
# dryrun
# --------------------------------------------------------------------------- #
def _run_dryrun(spec: RunSpec) -> Dict[str, Any]:
    import json
    import os

    if spec.fleet.n_replicas >= 1:
        # A fleet dryrun renders Kubernetes manifests (pure dicts, no
        # cluster, no jax, no placeholder devices) instead of AOT
        # compiling — the deploy-side twin of the serve-mode fleet.
        from repro.launch import k8s

        text = k8s.render(spec)
        if spec.fleet.k8s_out:
            with open(spec.fleet.k8s_out, "w") as fh:
                fh.write(text)
            print(f"k8s manifests ({spec.fleet.n_replicas} replica(s)) "
                  f"-> {spec.fleet.k8s_out}")
        else:
            print(text, end="")
        return {"manifests": k8s.render_manifests(spec), "yaml": text}

    from repro.configs import INPUT_SHAPES, list_archs
    from repro.launch import dryrun as D

    d = spec.dryrun
    multi_pod = spec.mesh == "multipod"
    archs = list_archs() if d.all else [spec.arch]

    # Importing repro.launch.dryrun (above) set the 512-placeholder-device
    # XLA flag before ITS jax import, but that is too late if this process
    # already initialized jax (notebook, pytest) — fail clearly instead of
    # with a device-count error deep inside mesh construction.
    import jax

    from repro.launch import MULTIPOD_DEVICES, POD_DEVICES

    need = MULTIPOD_DEVICES if multi_pod else POD_DEVICES
    if jax.device_count() < need:
        raise RuntimeError(
            f"dryrun needs {need} placeholder CPU devices but jax is "
            f"initialized with {jax.device_count()}; the dry-run must own "
            "the process — run `python -m repro run --mode dryrun ...` "
            "as its own command"
        )

    if d.specs:
        tables = []
        for arch in archs:
            meta, rows = D.print_spec_table(
                arch, multi_pod=multi_pod,
                mode=os.environ.get("REPRO_SERVE_MODE"),
            )
            tables.append({**meta, "rows": [
                {**r, "shape": list(r["shape"]), "axes": list(r["axes"])}
                for r in rows
            ]})
            print()
        if d.json_out:
            with open(d.json_out, "w") as f:
                json.dump(tables, f, indent=1)
        return {"tables": tables}

    results = []
    if d.all:
        for arch in archs:
            for shape in INPUT_SHAPES:
                try:
                    results.append(
                        D.dryrun_one(arch, shape, multi_pod=multi_pod)
                    )
                except Exception as e:  # noqa: BLE001 — report, keep going
                    print(f"FAILED {arch} x {shape}: {type(e).__name__}: {e}")
                    results.append({"arch": arch, "shape": shape,
                                    "multi_pod": multi_pod,
                                    "error": str(e)[:500]})
    else:
        results.append(D.dryrun_one(spec.arch, d.shape, multi_pod=multi_pod))
    if d.json_out:
        with open(d.json_out, "w") as f:
            json.dump(results, f, indent=1)
    if d.bench_out:
        from repro.bench import schema as bench_schema
        bench_schema.dump(
            bench_schema.dryrun_artifact(
                results, tag=d.bench_tag, multi_pod=multi_pod
            ),
            d.bench_out,
        )
        print(f"bench artifact -> {d.bench_out}")
    ok = sum(1 for r in results if "error" not in r)
    print(f"\n{ok}/{len(results)} dry-runs succeeded")
    return {"results": results, "exit_code": 0 if ok == len(results) else 1}
