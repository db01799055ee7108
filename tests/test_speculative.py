"""Speculative decoding + quantized KV pools at the engine level.

The two PR invariants under test: (1) speculative decode is
token-identical to non-speculative greedy — including under pool-
pressure preemption and with the cross-request prefix cache on — while
the engine still compiles exactly one chunk program; (2) quantized KV
pools (int8 slab + paged, int4 paged) keep logits within quantization
tolerance of the bf16 pool across the arch families.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist import Rules, split_tree, use_rules
from repro.launch.mesh import single_device_mesh
from repro.serve import Engine, Request, ServeConfig, run_offline, run_server
from repro.serve.engine import synthetic_requests
from repro.serve.speculative import (
    DraftModelDrafter,
    NgramDrafter,
    get_drafter,
)
from repro.train.steps import ModelAPI


def _setup(arch, mode="replicated", kv_cache_dtype=None):
    cfg = get_config(arch).reduced()
    if kv_cache_dtype is not None:
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_cache_dtype)
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))
    mesh = single_device_mesh()
    return cfg, params, mesh, Rules(mesh, mode)


def _request_stream(cfg, seed, n=6):
    rng = np.random.RandomState(seed)
    return [
        Request(
            prompt=rng.randint(0, cfg.vocab,
                               size=int(rng.randint(2, 14))).tolist(),
            max_new_tokens=int(rng.randint(1, 8)),
            arrival_step=int(rng.randint(0, 8)),
        )
        for _ in range(n)
    ]


# --------------------------------------------------------------------------- #
# Drafters (pure python).
# --------------------------------------------------------------------------- #
def test_ngram_drafter_proposes_continuation_of_repeated_suffix():
    d = NgramDrafter(max_n=3)
    # ... 7 8 9 | 5 | 7 8 9 -> suffix (7,8,9) recurs, continuation is [5, 7]
    assert d.propose([1, 7, 8, 9, 5, 7, 8, 9], k=2) == [5, 7]
    # the most recent earlier occurrence wins over an older one
    assert d.propose([7, 8, 1, 7, 8, 2, 7, 8], k=1) == [2]
    # continuation truncates at the context end
    assert d.propose([3, 4, 3, 4], k=8) == [3, 4]
    # no repeated suffix -> no proposal
    assert d.propose([1, 2, 3, 4, 5], k=4) == []
    assert d.propose([1], k=4) == []
    assert d.propose([1, 1, 1], k=0) == []
    with pytest.raises(ValueError):
        NgramDrafter(max_n=0)


def test_draft_model_drafter_hook_and_factory():
    d = DraftModelDrafter(lambda ctx, k: [ctx[-1]] * (k + 3))
    assert d.propose([1, 2, 9], 2) == [9, 9]  # truncated to k
    assert get_drafter("off") is None and get_drafter("") is None
    assert isinstance(get_drafter("ngram"), NgramDrafter)
    with pytest.raises(ValueError, match="spec_decode"):
        get_drafter("medusa")


# --------------------------------------------------------------------------- #
# Token identity: speculative == plain greedy.
# --------------------------------------------------------------------------- #
@pytest.mark.slow
def test_spec_decode_token_identical_and_one_program():
    """ngram spec decode reproduces plain greedy token for token on a
    mixed-arrival server stream, accepts some drafts, and still
    compiles exactly one chunk program."""
    cfg, params, mesh, rules = _setup("gemma-7b", "tp2d")
    base = dict(max_batch=3, max_len=32, page_size=4, prefill_chunk=6,
                kv_layout="paged")
    with mesh, use_rules(rules):
        plain = Engine(cfg, params, rules, ServeConfig(**base))
        plain_report = run_server(plain, _request_stream(cfg, seed=11))
        spec = Engine(cfg, params, rules,
                      ServeConfig(**base, spec_decode="ngram", draft_len=3))
        report = run_server(spec, _request_stream(cfg, seed=11))
    # ids are global — compare the i-th submitted request of each run
    want = [r.tokens for r in sorted(plain_report.requests,
                                     key=lambda r: r.id)]
    got = [r.tokens for r in sorted(report.requests, key=lambda r: r.id)]
    assert got == want
    assert spec.compiled_programs() == {"chunk": 1}, (
        "speculative verify must ride the one chunk program")
    assert report.draft_tokens > 0
    assert 0.0 <= report.spec_accept_rate <= 1.0
    assert report.summary()["draft_tokens"] == report.draft_tokens
    # plain engine reports no speculative stats
    assert plain_report.spec_accept_rate is None


@pytest.mark.slow
def test_spec_decode_identity_under_preemption_and_prefix_cache():
    """Pool pressure (preemptions force re-prefill of accepted tokens)
    and the cross-request prefix cache both stay invisible to
    speculative greedy outputs. The prompts repeat 4-grams, so the
    n-gram drafter proposes; the first two requests send one whole
    prompt, so the second matches every page of it in the prefix cache
    and copy-on-writes its last page."""
    cfg, params, mesh, rules = _setup("gemma-7b", "tp2d")

    def mk():
        rng = np.random.RandomState(9)
        a, b, c = (rng.randint(0, cfg.vocab, size=4).tolist()
                   for _ in range(3))
        prompts = [a * 4, a * 4, b * 3 + c, c * 3, b * 4, c * 2 + b]
        return [Request(prompt=p, max_new_tokens=10, arrival_step=t)
                for p, t in zip(prompts, (0, 4, 5, 6, 7, 12))]

    base = dict(max_batch=3, max_len=32, kv_layout="paged", page_size=4,
                prefill_chunk=6, n_pages=12, prefix_cache=True)
    with mesh, use_rules(rules):
        plain = Engine(cfg, params, rules, ServeConfig(**base))
        want = [r.tokens for r in sorted(run_server(plain, mk()).requests,
                                         key=lambda r: r.id)]
        spec = Engine(cfg, params, rules,
                      ServeConfig(**base, spec_decode="ngram", draft_len=3))
        report = run_server(spec, mk())
    got = [r.tokens for r in sorted(report.requests, key=lambda r: r.id)]
    assert got == want
    assert report.preemptions > 0, (
        "12-page pool should have preempted; widen the workload if not")
    assert report.prefix_hit_rate is not None
    assert report.draft_tokens > 0
    assert report.cow_copies > 0


@pytest.mark.slow
def test_spec_decode_on_int8_pool_matches_plain_int8():
    """Speculation composes with quantized pools: int8+ngram == int8
    plain, greedy token for token (both read the same quantized pages)."""
    cfg, params, mesh, rules = _setup("gemma-7b", "tp2d")
    base = dict(max_batch=3, max_len=32, kv_layout="paged", page_size=4,
                prefill_chunk=6, kv_dtype="int8")
    with mesh, use_rules(rules):
        plain = Engine(cfg, params, rules, ServeConfig(**base))
        want = [r.tokens for r in sorted(
            run_offline(plain, _request_stream(cfg, seed=5)).requests,
            key=lambda r: r.id)]
        spec = Engine(cfg, params, rules,
                      ServeConfig(**base, spec_decode="ngram", draft_len=3))
        report = run_offline(spec, _request_stream(cfg, seed=5))
    got = [r.tokens for r in sorted(report.requests, key=lambda r: r.id)]
    assert got == want
    assert spec.compiled_programs() == {"chunk": 1}


# --------------------------------------------------------------------------- #
# Construction-time validation (the bugfix satellite: fail at Engine
# construction, not mid-step).
# --------------------------------------------------------------------------- #
def test_engine_validates_quantized_and_spec_combos_at_construction():
    cfg, params, _, _ = _setup("gemma-7b")
    rcfg, rparams, _, _ = _setup("rwkv6-3b")
    # int4 requires the paged layout (packed pools + per-page scales)
    with pytest.raises(ValueError, match="int4"):
        Engine(cfg, params, None,
               ServeConfig(max_batch=1, max_len=16, prefill_len=8,
                           kv_layout="slab", kv_dtype="int4"))
    with pytest.raises(ValueError, match="int4"):
        Engine(rcfg, rparams, None,
               ServeConfig(max_batch=1, max_len=16, prefill_len=8,
                           kv_dtype="int4"))  # recurrent -> slab
    # speculation needs greedy sampling and a paged layout
    with pytest.raises(ValueError, match="temperature"):
        Engine(cfg, params, None,
               ServeConfig(max_batch=1, max_len=16, kv_layout="paged",
                           page_size=4, spec_decode="ngram",
                           temperature=0.7))
    with pytest.raises(ValueError, match="paged"):
        Engine(rcfg, rparams, None,
               ServeConfig(max_batch=1, max_len=16, prefill_len=8,
                           spec_decode="ngram"))
    # draft_len + 1 verified tokens must fit the chunk program
    with pytest.raises(ValueError, match="draft_len"):
        Engine(cfg, params, None,
               ServeConfig(max_batch=1, max_len=16, kv_layout="paged",
                           page_size=4, prefill_chunk=4,
                           spec_decode="ngram", draft_len=4))
    # bad enum values die in ServeConfig itself
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeConfig(kv_dtype="fp8")
    with pytest.raises(ValueError, match="spec_decode"):
        ServeConfig(spec_decode="medusa")
    with pytest.raises(ValueError, match="draft_len"):
        ServeConfig(draft_len=0)


# --------------------------------------------------------------------------- #
# Quantized-vs-bf16 logit tolerance across the arch families.
# --------------------------------------------------------------------------- #
@pytest.mark.slow
@pytest.mark.parametrize("arch,mode", [("gemma-7b", "tp2d"),
                                       ("rwkv6-3b", "replicated"),
                                       ("whisper-medium", "replicated")])
def test_int8_kv_logits_close_to_bf16(arch, mode):
    """Slab decode with an int8 KV cache tracks the bf16 cache's logits
    within quantization tolerance (both runs fed the bf16 run's greedy
    tokens so inputs match step for step)."""
    cfg, params, mesh, rules = _setup(arch, mode)
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    api = ModelAPI(cfg)
    reqs = synthetic_requests(cfg, n=2, tokens=1, prompt_len=8,
                              prompt_lens=[8, 8], seed=3)
    batch = {"tokens": np.stack([r.prompt for r in reqs])}
    if reqs[0].media is not None:
        batch["media"] = np.stack([r.media for r in reqs])

    def run(c):
        api_c = ModelAPI(c)
        with mesh, use_rules(rules):
            logits, cache = api_c.prefill(params, batch, cache_len=16)
            out, pos = [logits], 8
            for t in feed:
                logits, cache = api_c.decode(params, t, cache, pos)
                out.append(logits)
                pos += 1
            return [np.asarray(o, np.float32) for o in out]

    # greedy tokens of the bf16 run drive both runs
    with mesh, use_rules(rules):
        logits, cache = api.prefill(params, batch, cache_len=16)
        feed, pos = [], 8
        for _ in range(3):
            t = np.argmax(np.asarray(logits), -1).astype(np.int32)[:, None]
            feed.append(t)
            logits, cache = api.decode(params, t, cache, pos)
            pos += 1

    ref_logits = run(cfg)
    q_logits = run(cfg8)
    for a, b in zip(ref_logits, q_logits):
        scale = max(1.0, float(np.abs(a).max()))
        assert float(np.abs(a - b).max()) / scale < 0.08, (
            "int8 KV cache drifted beyond quantization tolerance")


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype,tol", [("int8", 0.08), ("int4", 0.35)])
def test_quantized_paged_engine_runs_and_tracks_bf16(kv_dtype, tol):
    """The quantized paged engine completes the bf16 engine's workload
    and its decode logit trajectory stays within quantization tolerance
    (asserted indirectly: every request finishes with the right token
    count; int8 additionally reproduces bf16 tokens on this workload)."""
    cfg, params, mesh, rules = _setup("gemma-7b", "tp2d")
    base = dict(max_batch=3, max_len=32, kv_layout="paged", page_size=4,
                prefill_chunk=4)
    with mesh, use_rules(rules):
        bf16 = Engine(cfg, params, rules, ServeConfig(**base))
        want = [r.tokens for r in sorted(
            run_offline(bf16, _request_stream(cfg, seed=4)).requests,
            key=lambda r: r.id)]
        q = Engine(cfg, params, rules,
                   ServeConfig(**base, kv_dtype=kv_dtype))
        report = run_offline(q, _request_stream(cfg, seed=4))
    got = [r.tokens for r in sorted(report.requests, key=lambda r: r.id)]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert len(g) == len(w)
    if kv_dtype == "int8":
        # token identity is NOT the quantized contract (logits within
        # tolerance is — see the slab logit test), but int8 error is
        # small enough that greedy argmax rarely flips: require near-
        # identity so a broken dequant path (wholesale divergence)
        # still fails loudly.
        same = sum(int(a == b) for g, w in zip(got, want)
                   for a, b in zip(g, w))
        total = sum(len(w) for w in want)
        assert same / total >= 0.9, (got, want)
    assert q.compiled_programs() == {"chunk": 1}


def test_bench_compare_treats_int8_and_specdec_rows_as_new():
    """A BENCH artifact that adds ``*_int8_*`` / ``*_specdec_*`` serve
    rows diffs as additions — never regressions — against a pre-PR-8
    baseline."""
    from repro.bench.compare import diff_rows

    def artifact(names):
        return {"tag": "x", "benchmarks": {"serve_decode": {
            "status": "ok",
            "records": [{"name": n, "wall_us": None} for n in names]}}}

    old = artifact(["serve/g_offline", "serve/g_paged_offline"])
    new = artifact(["serve/g_offline", "serve/g_paged_offline",
                    "serve/g_int8_offline", "serve/g_int8_server",
                    "serve/g_specdec_offline", "serve/g_specdec_server"])
    rows, regressions = diff_rows(old, new)
    assert not regressions
    status = {r["name"]: r["status"] for r in rows}
    for n in ("int8_offline", "int8_server",
              "specdec_offline", "specdec_server"):
        assert status[f"serve_decode:serve/g_{n}"] == "new"
