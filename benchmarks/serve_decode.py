"""Continuous-batching serving benchmark (``repro.serve``).

Measures the decode step of the serving engine in the two MLPerf
Inference scenarios (Reddi et al., 2019, arXiv:1911.02549): *offline*
(whole workload available up front — throughput) and *server* (staggered
arrivals — latency tail under admission/eviction churn). The timed
record is the per-decode-step wall time; derived keys carry tokens/sec,
p50/p99 per-token latency and mean batch occupancy from the engine's
own step trace.

The ``*_paged_*`` rows run the same ragged workloads (every request a
different prompt length) through the paged-KV engine — chunked prefill
through one compiled program, pool sized below slab parity — and add
page-pool utilization (mean/peak) and the preemption count.

The ``*_prefix_*`` rows run a shared-prefix workload (every prompt
opens with one of two fixed templates — system-prompt-shaped traffic)
through the paged engine twice, cache off then on, and report the
cache's effect directly: prefix hit rate, pages shared, prefill tokens
skipped, and the TTFT delta vs the cache-off run of the *same*
workload (``ttft_delta_ms`` < 0 means the cache cut time-to-first-
token). The ``*_int8_*`` rows re-run the paged workloads on an int8
quantized pool of identical geometry (``tokens_per_s_vs_bf16`` is the
uplift against the paged twin), and the ``*_specdec_*`` rows turn on
ngram speculative decoding against the same non-spec twin
(``tokens_per_s_vs_plain``, accept rate, draft volume — outputs stay
token-identical). The ``*_fleet_*`` rows (PR 9) run a shared-prefix
workload over **two** fleet replicas of the exact paged-row pool
geometry with one seeded mid-run replica kill — fleet tokens/s,
prefix-affinity routing hit-rate, the goodput fraction charging the
kill's lost decode work, and ``tokens_per_s_vs_1rep`` against a
clean single-replica fleet on the same workload. All pre-existing rows
keep their exact workloads, so committed BENCH_* trajectories stay
comparable across PRs.

    PYTHONPATH=src python -m repro.bench.run --only serve_decode [--smoke]
"""
import jax

from repro.bench.registry import benchmark, timing_from_samples
from repro.configs import get_config
from repro.dist import Rules, split_tree, use_rules
from repro.launch.mesh import single_device_mesh
from repro.launch.serve import build_requests
from repro.serve import Engine, ServeConfig, run_offline, run_server
from repro.serve.engine import synthetic_requests
from repro.serve.scenarios import SCENARIOS, make_trace, scenario_driver
from repro.train.steps import ModelAPI

DERIVED = ("tokens_per_s", "ttft_p50_ms",
           "mean_batch_occupancy", "requests", "pool_util_mean",
           "pool_util_peak", "preemptions", "prefix_hit_rate",
           "pages_shared", "prefill_tokens_skipped", "cow_copies",
           "ttft_delta_ms", "slo_goodput", "slo_violations",
           "p99_ms_interactive", "p99_ms_batch", "tokens_per_s_vs_bf16",
           "tokens_per_s_vs_plain", "spec_accept_rate", "draft_tokens",
           "goodput", "routing_hit_rate", "lost_tokens", "reroutes",
           "fleet_replicas", "tokens_per_s_vs_1rep")


def _decode_timing(report):
    """Median/IQR per-decode-step wall time, or None (derived-only
    record) when the workload produced no decode steps. The first decode
    step (in trace order) may be compile-inflated and is dropped as
    warmup when there is more than one."""
    decode = [s.wall_s * 1e6 for s in report.steps if s.kind == "decode"]
    if not decode:
        return None
    warmup = 1 if len(decode) > 1 else 0
    return timing_from_samples(decode[warmup:], warmup=warmup)


@benchmark("serve_decode",
           paper_ref="MLPerf Inference (arXiv:1911.02549) offline/server",
           units="us", derived_keys=DERIVED)
def run(ctx):
    cfg = get_config("gemma-7b").reduced()
    n_req = 4 if ctx.smoke else 8
    tokens = 8 if ctx.smoke else 32
    prompt_len = 12 if ctx.smoke else 24

    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))
    mesh = single_device_mesh()
    rules = Rules(mesh, "tp2d")
    scfg = ServeConfig(max_batch=min(4, n_req),
                       max_len=prompt_len + tokens,
                       prefill_len=prompt_len, kv_layout="slab")
    # The paged rows pin a ragged spread (every request a different
    # prompt length) so they exercise per-row page occupancy; the slab
    # rows keep the original seeded workload so the committed BENCH_*
    # trajectory stays comparable across PRs.
    spread = tuple(max(1, prompt_len - 3 * i) for i in range(n_req))

    def ragged_workload(scenario):
        """The one ragged workload every paged-pool row family (paged /
        int8 / specdec) replays — same prompts, same spread, so their
        rows differ only in the engine knob under test."""
        return synthetic_requests(cfg, n=n_req, tokens=tokens,
                                  prompt_len=prompt_len, scenario=scenario,
                                  seed=0, prompt_lens=spread)

    with mesh, use_rules(rules):
        engine = Engine(cfg, params, rules, scfg)
        # throwaway workload compiles the prefill/decode programs so the
        # recorded scenarios measure serving, not XLA compile time; two
        # requests, because prefill specializes separately for the
        # fresh-slab and slab-from-jit-output argument layouts
        run_offline(engine, build_requests(
            cfg, n=2, tokens=2, prompt_len=prompt_len,
            scenario="offline", seed=1))
    for scenario, driver in (("offline", run_offline),
                             ("server", run_server)):
        reqs = build_requests(cfg, n=n_req, tokens=tokens,
                              prompt_len=prompt_len, scenario=scenario,
                              seed=0)
        with mesh, use_rules(rules):
            # engine reuse keeps the compiled programs across scenarios
            # (run() resets the workload state itself)
            report = driver(engine, reqs)
        s = report.summary()
        ctx.record(
            f"serve/{cfg.name}_{scenario}",
            _decode_timing(report),
            tokens_per_s=s["tokens_per_s"],
            ttft_p50_ms=s["ttft_p50_ms"],
            mean_batch_occupancy=s["mean_batch_occupancy"],
            requests=s["requests"],
        )

    # ---- paged KV + chunked prefill (one compiled program) ------------- #
    pcfg = ServeConfig(
        max_batch=min(4, n_req), max_len=prompt_len + tokens,
        kv_layout="paged", page_size=4, prefill_chunk=4,
        # sized below slab parity: admission runs by free-page budget
        n_pages=min(4, n_req) * ((prompt_len + tokens + 3) // 4) * 3 // 4,
    )
    with mesh, use_rules(rules):
        paged = Engine(cfg, params, rules, pcfg)
        run_offline(paged, build_requests(  # compile the chunk program
            cfg, n=2, tokens=2, prompt_len=prompt_len,
            scenario="offline", seed=1))
    paged_tps = {}  # bf16/non-spec twin tokens/s, keyed by scenario
    for scenario, driver in (("offline", run_offline),
                             ("server", run_server)):
        reqs = ragged_workload(scenario)
        with mesh, use_rules(rules):
            report = driver(paged, reqs)
        s = report.summary()
        paged_tps[scenario] = s["tokens_per_s"]
        ctx.record(
            f"serve/{cfg.name}_paged_{scenario}",
            _decode_timing(report),
            tokens_per_s=s["tokens_per_s"],
            ttft_p50_ms=s["ttft_p50_ms"],
            pool_util_mean=s["pool_util_mean"],
            pool_util_peak=s["pool_util_peak"],
            preemptions=report.preemptions,
            requests=s["requests"],
        )

    # ---- quantized pool: int8 pages, identical geometry ---------------- #
    # Same ragged workloads and the exact pool geometry of the paged rows
    # above, so tokens_per_s_vs_bf16 isolates what storing the pool int8
    # buys (halved decode-step KV bytes) — not a workload change. Token
    # identity is not the quantized contract (bounded logit error is,
    # tests/test_speculative.py); throughput and pool stats are.
    qcfg = ServeConfig(**{**pcfg.__dict__, "kv_dtype": "int8"})
    with mesh, use_rules(rules):
        q8 = Engine(cfg, params, rules, qcfg)
        run_offline(q8, build_requests(  # compile the quantized chunk
            cfg, n=2, tokens=2, prompt_len=prompt_len,
            scenario="offline", seed=1))
    for scenario, driver in (("offline", run_offline),
                             ("server", run_server)):
        reqs = ragged_workload(scenario)
        with mesh, use_rules(rules):
            report = driver(q8, reqs)
        s = report.summary()
        ctx.record(
            f"serve/{cfg.name}_int8_{scenario}",
            _decode_timing(report),
            tokens_per_s=s["tokens_per_s"],
            tokens_per_s_vs_bf16=round(
                s["tokens_per_s"] / max(paged_tps[scenario], 1e-9), 4),
            ttft_p50_ms=s["ttft_p50_ms"],
            pool_util_mean=s["pool_util_mean"],
            pool_util_peak=s["pool_util_peak"],
            preemptions=report.preemptions,
            requests=s["requests"],
        )

    # ---- speculative decoding: ngram draft/verify, identical geometry -- #
    # Non-spec twin = the paged rows above (same workload, same pool).
    # Greedy outputs are token-identical by construction (verified in
    # tests/test_speculative.py); the rows record the throughput side:
    # accept rate, draft volume and tokens_per_s_vs_plain.
    sconf = ServeConfig(**{**pcfg.__dict__,
                           "spec_decode": "ngram", "draft_len": 3})
    with mesh, use_rules(rules):
        spec = Engine(cfg, params, rules, sconf)
        run_offline(spec, build_requests(  # compile the full-logits chunk
            cfg, n=2, tokens=2, prompt_len=prompt_len,
            scenario="offline", seed=1))
    for scenario, driver in (("offline", run_offline),
                             ("server", run_server)):
        reqs = ragged_workload(scenario)
        with mesh, use_rules(rules):
            report = driver(spec, reqs)
        s = report.summary()
        ctx.record(
            f"serve/{cfg.name}_specdec_{scenario}",
            _decode_timing(report),
            tokens_per_s=s["tokens_per_s"],
            tokens_per_s_vs_plain=round(
                s["tokens_per_s"] / max(paged_tps[scenario], 1e-9), 4),
            spec_accept_rate=report.spec_accept_rate,
            draft_tokens=report.draft_tokens,
            ttft_p50_ms=s["ttft_p50_ms"],
            preemptions=report.preemptions,
            requests=s["requests"],
        )

    # ---- cross-request prefix cache (shared-prefix workload) ----------- #
    # Templates span 2/3 of each prompt; the later arrival waves of the
    # server scenario (and the second admission wave of offline) hit the
    # warm radix index, so the measured hit rate reflects steady traffic.
    shared = (prompt_len * 2 + 2) // 3
    xcfg = ServeConfig(
        max_batch=min(4, n_req), max_len=prompt_len + tokens,
        kv_layout="paged", page_size=4, prefill_chunk=4,
        prefix_cache=True,
    )
    rcfg = ServeConfig(**{**xcfg.__dict__, "prefix_cache": False})
    with mesh, use_rules(rules):
        prefix_engine = Engine(cfg, params, rules, xcfg)
        ref_engine = Engine(cfg, params, rules, rcfg)  # cache-off twin
        for e in (prefix_engine, ref_engine):
            run_offline(e, build_requests(
                cfg, n=2, tokens=2, prompt_len=prompt_len,
                scenario="offline", seed=1))
    for scenario, driver in (("offline", run_offline),
                             ("server", run_server)):
        def workload():
            return synthetic_requests(
                cfg, n=2 * n_req, tokens=tokens, prompt_len=prompt_len,
                scenario=scenario, seed=0, shared_prefix_len=shared,
                n_templates=2)
        with mesh, use_rules(rules):
            # cache-off twin on the SAME workload and pool geometry: the
            # ttft delta below isolates exactly what the cache buys
            baseline = driver(ref_engine, workload())
            report = driver(prefix_engine, workload())
        s = report.summary()
        ctx.record(
            f"serve/{cfg.name}_prefix_{scenario}",
            _decode_timing(report),
            tokens_per_s=s["tokens_per_s"],
            ttft_p50_ms=s["ttft_p50_ms"],
            ttft_delta_ms=round(
                s["ttft_p50_ms"] - baseline.summary()["ttft_p50_ms"], 3),
            prefix_hit_rate=s["prefix_hit_rate"],
            pages_shared=s["pages_shared"],
            prefill_tokens_skipped=s["prefill_tokens_skipped"],
            cow_copies=s["cow_copies"],
            preemptions=report.preemptions,
            requests=s["requests"],
        )

    # ---- SLO-tagged sweep: all four MLPerf-Inference scenarios --------- #
    # Reuses the paged engine (and its compiled chunk program) on the
    # same sub-parity pool, so the rows isolate scenario choice and
    # SLO-class churn — not a new engine geometry. Per-class latency
    # tails (interactive vs batch) are the fleet-goodput signal.
    for scenario in SCENARIOS:
        trace = make_trace(
            cfg, scenario=scenario, n=n_req, tokens=tokens,
            prompt_len=prompt_len, seed=0,
            slo_classes=("interactive", "standard", "batch"),
            query_size=2, query_interval=4)
        with mesh, use_rules(rules):
            report = scenario_driver(scenario)(paged, trace)
        s = report.summary()
        pc = report.per_class()
        ctx.record(
            f"serve/{cfg.name}_slo_{scenario}",
            _decode_timing(report),
            tokens_per_s=s["tokens_per_s"],
            ttft_p50_ms=s["ttft_p50_ms"],
            slo_goodput=s["slo_goodput"],
            slo_violations=s["slo_violations"],
            p99_ms_interactive=pc["interactive"]["p99_ms"],
            p99_ms_batch=pc["batch"]["p99_ms"],
            preemptions=report.preemptions,
            requests=s["requests"],
        )

    # ---- fleet: 2 replicas vs 1, identical per-replica pool geometry --- #
    # The same shared-prefix workload runs through a single-replica fleet
    # (clean) and a two-replica fleet with one seeded mid-run kill: the
    # row reports fleet tokens/s, the router's warm-cache hit rate, and
    # the goodput fraction charging the kill's abandoned decode tokens.
    # Each replica reuses the exact pcfg pool geometry of the paged rows,
    # so tokens_per_s_vs_1rep isolates fan-out + failover — not a pool
    # change. Completed outputs stay token-identical across all three
    # runs (tests/test_fleet.py pins this).
    from repro.fleet import ChaosEvent, ChaosPlan, Fleet

    def fleet_workload(scenario):
        return synthetic_requests(
            cfg, n=2 * n_req, tokens=tokens, prompt_len=prompt_len,
            scenario=scenario, seed=0, shared_prefix_len=shared,
            n_templates=2)

    with mesh, use_rules(rules):
        mate = Engine(cfg, params, rules, pcfg)  # paged's fleet twin
        run_offline(mate, build_requests(
            cfg, n=2, tokens=2, prompt_len=prompt_len,
            scenario="offline", seed=1))
    for scenario in ("offline", "server"):
        with mesh, use_rules(rules):
            solo = Fleet([paged]).run(fleet_workload(scenario))
            duo = Fleet([paged, mate], chaos=ChaosPlan(
                [ChaosEvent(step=6, kind="kill")], seed=0),
            ).run(fleet_workload(scenario))
        s = duo.summary()
        ctx.record(
            f"serve/{cfg.name}_fleet_{scenario}",
            _decode_timing(duo.merged),
            tokens_per_s=s["tokens_per_s"],
            tokens_per_s_vs_1rep=round(
                s["tokens_per_s"] / max(solo.tokens_per_s, 1e-9), 4),
            goodput=s["goodput"],
            routing_hit_rate=s["routing_hit_rate"],
            lost_tokens=s["lost_tokens"],
            reroutes=s["reroutes"],
            fleet_replicas=s["replicas"],
            ttft_p50_ms=s["ttft_p50_ms"],
            requests=s["requests"],
        )


if __name__ == "__main__":
    from benchmarks.common import standalone_context

    run(standalone_context())
