"""repro.serve: scheduler invariants under random arrival orders,
continuous-batching vs sequential decode equivalence, KV-slot reuse
after retirement, and the paged layout — allocator invariants under
randomized admit/retire/overflow/preempt sequences, paged==slab token
identity, the one-compiled-program contract, and clean preemption."""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.configs import get_config
from repro.dist import Rules, split_tree, use_rules
from repro.launch.mesh import single_device_mesh
from repro.serve import (
    Engine,
    PagePool,
    PagedScheduler,
    Request,
    RequestState,
    Scheduler,
    ServeConfig,
    invalidate_beyond,
    percentile,
    read_slot,
    run_offline,
    run_server,
    write_slot,
)
from repro.serve.engine import synthetic_requests
from repro.train.steps import ModelAPI


# --------------------------------------------------------------------------- #
# Scheduler invariants (pure python).
# --------------------------------------------------------------------------- #
def _random_schedule_run(seed: int, max_batch: int, n_requests: int):
    """Drive submit/admit/retire in a random order; check invariants at
    every round. Returns the admission order."""
    rng = random.Random(seed)
    sched = Scheduler(max_batch)
    pending = [
        Request(prompt=[1] * rng.randint(1, 8),
                max_new_tokens=rng.randint(1, 4))
        for _ in range(n_requests)
    ]
    submitted, admitted_order = [], []
    while pending or sched.has_work:
        # random interleaving of submissions
        for _ in range(rng.randint(0, 2)):
            if pending:
                req = pending.pop(0)
                sched.submit(req)
                submitted.append(req)
        admitted = sched.admit()
        admitted_order.extend(r for _, r in admitted)

        # -- invariants -------------------------------------------------- #
        running = sched.running()
        assert len(running) <= max_batch
        slots_used = [i for i, _ in running]
        assert len(set(slots_used)) == len(slots_used), "slot shared"
        for i, r in running:
            assert r.state is RequestState.RUNNING
            assert r.slot == i
        if sched.n_queued:  # nobody waits while a slot is free
            assert sched.n_active == max_batch

        # randomly retire some running requests
        for i, r in list(running):
            if rng.random() < 0.5:
                out = sched.retire(i)
                assert out is r
                assert out.state is RequestState.FINISHED
                assert out.slot is None
                assert sched.slot_of(i) is None
    return submitted, admitted_order


@pytest.mark.parametrize("seed", range(5))
def test_scheduler_random_arrivals_fifo_and_exclusive(seed):
    submitted, admitted = _random_schedule_run(
        seed, max_batch=1 + seed % 3, n_requests=12)
    assert len(admitted) == len(submitted) == 12
    # FIFO: admission order == submission order
    assert [r.id for r in admitted] == [r.id for r in submitted]
    assert all(r.state is RequestState.FINISHED for r in submitted)


def test_scheduler_rejects_bad_transitions():
    sched = Scheduler(2)
    req = Request(prompt=[1, 2, 3])
    sched.submit(req)
    with pytest.raises(ValueError):
        sched.submit(req)  # already queued
    [(slot, _)] = sched.admit()
    with pytest.raises(ValueError):
        sched.submit(req)  # running
    sched.retire(slot)
    with pytest.raises(ValueError):
        sched.retire(slot)  # already free


def test_request_validation():
    with pytest.raises(ValueError):
        Request(prompt=[])
    with pytest.raises(ValueError):
        Request(prompt=[1], max_new_tokens=0)
    with pytest.raises(ValueError):
        ServeConfig(max_len=8, prefill_len=16)


def test_temperature_sampling_keyed_per_request_and_position():
    """Temperature draws are deterministic in (seed, request id,
    position): keys differ across requests at one position and across
    positions within one request, and the keying is independent of slot
    assignment — the batched row draw equals the single-row (prefill
    path) draw for the same request id."""
    cfg = get_config("gemma-7b").reduced()
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))
    eng = Engine(cfg, params, None,
                 ServeConfig(max_batch=4, max_len=16, prefill_len=8,
                             temperature=1.0))
    logits = jnp.zeros((4, cfg.vocab))  # identical rows: keys must differ
    rids = np.array([10, 11, 12, 13], np.uint32)
    pos = np.full((4,), 7, np.int32)
    a = np.asarray(eng._sample(logits, rids, pos))
    b = np.asarray(eng._sample(logits, rids, pos))
    np.testing.assert_array_equal(a, b)
    assert len(set(a.tolist())) > 1, "all requests drew with one key"
    # successive positions of one request use fresh keys
    seq = [int(np.asarray(eng._sample(logits[:1], 10, p))[0])
           for p in range(8)]
    assert len(set(seq)) > 1, "positions share a key"
    # slot-independent: single-row draw for request 12 == its batched row
    row = np.asarray(eng._sample(logits[2:3], 12, 7))
    assert row[0] == a[2], "keying depends on row/slot, not request"


def test_percentile_nearest_rank():
    xs = [float(i) for i in range(1, 101)]
    assert percentile(xs, 50) == 50.0
    assert percentile(xs, 99) == 99.0
    assert percentile([], 50) == 0.0
    assert percentile([7.0], 99) == 7.0


# --------------------------------------------------------------------------- #
# Slab cache ops.
# --------------------------------------------------------------------------- #
def test_write_read_slot_roundtrip_and_invalidate():
    cfg = get_config("gemma-7b").reduced()
    api = ModelAPI(cfg)
    slab = api.init_cache(3, 8)
    one = jax.tree_util.tree_map(
        lambda a: jnp.ones(a.shape[:1] + (1,) + a.shape[2:], a.dtype), slab)
    slab2 = write_slot(slab, one, jnp.int32(1))
    got = read_slot(slab2, 1)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(one)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # neighbours untouched
    for a, b in zip(jax.tree_util.tree_leaves(read_slot(slab2, 0)),
                    jax.tree_util.tree_leaves(read_slot(slab, 0))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # invalidate_beyond masks exactly the pad tail of each row
    marked = invalidate_beyond(slab2, jnp.array([2, 5, 8], jnp.int32))
    sp = np.asarray(marked[0]["slot_pos"])  # (n_blocks, 3, 8)
    one_sp = np.asarray(one[0]["slot_pos"])
    assert (sp[:, 1, :5] == one_sp[:, 0, :5]).all()
    assert (sp[:, 1, 5:] == -1).all()


# --------------------------------------------------------------------------- #
# Engine equivalence: continuous batching == sequential decode.
# --------------------------------------------------------------------------- #
def _sequential_reference(api, params, prompt, n_new, max_len):
    """Plain single-request prefill + greedy decode loop."""
    toks = jnp.asarray([prompt], jnp.int32)
    P = len(prompt)
    logits, cache = api.prefill(params, {"tokens": toks}, cache_len=max_len)
    out = [int(jnp.argmax(logits, -1)[0])]
    for i in range(n_new - 1):
        logits, cache = api.decode(
            params, jnp.array([[out[-1]]], jnp.int32), cache,
            jnp.int32(P + i))
        out.append(int(jnp.argmax(logits, -1)[0]))
    return out


def _mixed_arrival_requests(cfg, rng, n):
    return [
        Request(
            prompt=rng.randint(0, cfg.vocab,
                               size=int(rng.randint(3, 12))).tolist(),
            max_new_tokens=int(rng.randint(1, 6)),
            arrival_step=int(rng.randint(0, 8)),
        )
        for _ in range(n)
    ]


@pytest.mark.slow
@pytest.mark.parametrize("arch,mode", [("gemma-7b", "tp2d"),
                                       ("rwkv6-3b", "replicated")])
def test_continuous_batching_matches_sequential(arch, mode):
    """Mixed arrivals through a 3-slot engine produce token-identical
    outputs to running every request alone — for the padded-prefill path
    (attention: gemma) and the exact-length path (recurrent: rwkv6)."""
    cfg = get_config(arch).reduced()
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))
    mesh = single_device_mesh()
    rules = Rules(mesh, mode)
    rng = np.random.RandomState(1)
    reqs = _mixed_arrival_requests(cfg, rng, 6)
    want = {r.id: _sequential_reference(api, params, r.prompt,
                                        r.max_new_tokens, 32)
            for r in reqs}

    with mesh, use_rules(rules):
        engine = Engine(cfg, params, rules,
                        ServeConfig(max_batch=3, max_len=32, prefill_len=16))
        report = run_server(engine, reqs)

    assert len(report.requests) == len(reqs)
    for r in report.requests:
        assert r.tokens == want[r.id], (
            f"req {r.id}: engine {r.tokens} != sequential {want[r.id]}")
    # metrics are well-formed
    s = report.summary()
    assert s["tokens"] == sum(len(r.tokens) for r in reqs)
    assert s["tokens_per_s"] > 0
    # every prompt token is fed once, then each token but the last
    assert sum(st.n_fed for st in report.steps) == sum(
        r.prompt_len + len(r.tokens) - 1 for r in report.requests)


@pytest.mark.slow
def test_kv_slot_reuse_after_retirement():
    """A 1-slot engine forces every request through the same KV slot;
    outputs stay identical to sequential decode, proving retirement fully
    recycles the slot (no state leaks between occupants)."""
    cfg = get_config("gemma-7b").reduced()
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))
    mesh = single_device_mesh()
    rules = Rules(mesh, "tp2d")
    rng = np.random.RandomState(2)
    reqs = [
        Request(prompt=rng.randint(0, cfg.vocab, size=int(p)).tolist(),
                max_new_tokens=4)
        for p in (9, 5, 12)
    ]
    want = {r.id: _sequential_reference(api, params, r.prompt, 4, 32)
            for r in reqs}

    with mesh, use_rules(rules):
        engine = Engine(cfg, params, rules,
                        ServeConfig(max_batch=1, max_len=32, prefill_len=16))
        report = run_offline(engine, reqs)

    assert len(report.requests) == 3
    for r in report.requests:
        assert r.slot is None and r.state is RequestState.FINISHED
        assert r.tokens == want[r.id]
    # offline with one slot == strictly sequential completion order
    assert [r.id for r in report.requests] == [r.id for r in reqs]

    # reset() recycles the compiled programs: a fresh identical workload
    # through the same engine reproduces the same tokens
    with mesh, use_rules(rules):
        engine.reset()
        rng2 = np.random.RandomState(2)
        reqs2 = [
            Request(prompt=rng2.randint(0, cfg.vocab, size=int(p)).tolist(),
                    max_new_tokens=4)
            for p in (9, 5, 12)
        ]
        report2 = run_offline(engine, reqs2)
    assert [r.tokens for r in report2.requests] == [
        want[r.id] for r in reqs]


# --------------------------------------------------------------------------- #
# Paged engine: identity with the dense slab, one-program contract,
# preemption and defrag transparency.
# --------------------------------------------------------------------------- #
def _request_stream(cfg, seed, n=6):
    rng = np.random.RandomState(seed)
    return [
        Request(
            prompt=rng.randint(0, cfg.vocab,
                               size=int(rng.randint(2, 14))).tolist(),
            max_new_tokens=int(rng.randint(1, 6)),
            arrival_step=int(rng.randint(0, 8)),
        )
        for _ in range(n)
    ]


@pytest.mark.slow
@pytest.mark.parametrize("arch,mode", [("gemma-7b", "tp2d"),
                                       ("rwkv6-3b", "replicated")])
def test_paged_engine_token_identical_to_slab(arch, mode):
    """The default-layout engine (paged for attention stacks, slab-exact
    for recurrent ones) reproduces the PR 3 dense-slab engine token for
    token on the same mixed-arrival stream — and for the paged layout the
    whole run, spanning many distinct prompt lengths, compiles exactly
    one decode-shaped program (jit cache-miss counter stays at 1)."""
    cfg = get_config(arch).reduced()
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))
    mesh = single_device_mesh()
    rules = Rules(mesh, mode)
    with mesh, use_rules(rules):
        slab = Engine(cfg, params, rules,
                      ServeConfig(max_batch=3, max_len=32, prefill_len=16,
                                  kv_layout="slab"))
        want = {r.id: r.tokens for r in run_server(
            slab, _request_stream(cfg, seed=11)).requests}
        eng = Engine(cfg, params, rules,
                     ServeConfig(max_batch=3, max_len=32, prefill_len=16,
                                 page_size=4, prefill_chunk=4))
        report = run_server(eng, _request_stream(cfg, seed=11))
    got = {r.id: r.tokens for r in report.requests}
    assert len(got) == len(want) == 6
    # ids are sequential per stream: the i-th submitted request of each
    # run must generate the same tokens
    assert ([t for _, t in sorted(got.items())]
            == [t for _, t in sorted(want.items())])
    if arch == "gemma-7b":
        assert eng.layout == "paged"
        assert eng.compiled_programs() == {"chunk": 1}, (
            "per-prompt-length recompiles detected")
        # a second workload with fresh lengths still compiles nothing new
        with mesh, use_rules(rules):
            run_offline(eng, [Request(prompt=[5] * p, max_new_tokens=2)
                              for p in (1, 13, 6)])
        assert eng.compiled_programs() == {"chunk": 1}
        utils = [s.pool_util for s in report.steps
                 if s.pool_util is not None]
        assert utils and max(utils) <= 1.0
    else:
        assert eng.layout == "slab"


def test_paged_steps_count_fed_tokens():
    """A prompt of P tokens at chunk C streams in ceil(P / C) chunk
    steps; each of the k decode steps after it feeds one token: the
    steps' n_fed sum to P + k, each over the program's B x C positions."""
    cfg = get_config("gemma-7b").reduced()
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))
    eng = Engine(cfg, params, None,
                 ServeConfig(max_batch=2, max_len=32, kv_layout="paged",
                             page_size=4, prefill_chunk=4))
    P, k = 11, 3
    report = run_offline(eng, [Request(prompt=[7] * P,
                                       max_new_tokens=k + 1)])
    assert [s.kind for s in report.steps] == ["mixed"] * 3 + ["decode"] * k
    assert [s.n_fed for s in report.steps] == [4, 4, 3] + [1] * k
    assert sum(s.n_fed for s in report.steps) == P + k
    assert {s.n_positions for s in report.steps} == {2 * 4}


def test_paged_steps_count_kv_pages():
    """n_kv_pages is the page slots the paged kernel visits in a layer:
    the sum over active rows of ceil((pos + n_valid) / page). Prompts of
    11 and 6 tokens at page 4, chunk 4: row A feeds (pos, n) = (0, 4),
    (4, 4), (8, 3), then decodes at 11, 12, 13; row B feeds (0, 4),
    (4, 2), then decodes once at 6 and finishes."""
    cfg = get_config("gemma-7b").reduced()
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))
    eng = Engine(cfg, params, None,
                 ServeConfig(max_batch=2, max_len=32, kv_layout="paged",
                             page_size=4, prefill_chunk=4))
    report = run_offline(eng, [Request(prompt=[7] * 11, max_new_tokens=4),
                               Request(prompt=[5] * 6, max_new_tokens=2)])
    pages = lambda pos, n: -(-(pos + n) // 4)
    want = [pages(0, 4) + pages(0, 4), pages(4, 4) + pages(4, 2),
            pages(8, 3) + pages(6, 1), pages(11, 1), pages(12, 1),
            pages(13, 1)]
    assert [s.n_kv_pages for s in report.steps] == want == [2, 4, 5, 3, 4, 4]


@pytest.mark.slow
def test_paged_preemption_and_defrag_keep_tokens_identical():
    """A pool too small for the workload forces preemptions; preempted
    requests resume by re-prefilling prompt + tokens-so-far, so greedy
    outputs match the uncontended slab run exactly. A mid-run defrag
    (page compaction + table rewrite) is equally invisible."""
    cfg = get_config("gemma-7b").reduced()
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))

    def mk():
        rng = np.random.RandomState(7)
        return [Request(prompt=rng.randint(0, cfg.vocab, size=int(p)).tolist(),
                        max_new_tokens=6)
                for p in (9, 7, 12, 5)]

    slab = Engine(cfg, params, None,
                  ServeConfig(max_batch=4, max_len=32, prefill_len=16,
                              kv_layout="slab"))
    want = [r.tokens for r in sorted(run_offline(slab, mk()).requests,
                                     key=lambda r: r.id)]

    tiny = Engine(cfg, params, None,
                  ServeConfig(max_batch=4, max_len=32, kv_layout="paged",
                              page_size=4, prefill_chunk=4, n_pages=6))
    report = run_offline(tiny, mk())
    got = [r.tokens for r in sorted(report.requests, key=lambda r: r.id)]
    assert report.preemptions > 0, "6-page pool should have preempted"
    assert got == want
    # pool fully drained after the run (reset() rebuilt it)
    assert tiny._pool.free_pages == tiny._pool.n_pages

    eng = Engine(cfg, params, None,
                 ServeConfig(max_batch=4, max_len=32, kv_layout="paged",
                             page_size=4, prefill_chunk=4))
    for r in mk():
        eng.submit(r)
    for _ in range(5):
        eng.step()
    eng.defrag()  # compact mid-flight
    while eng._arrivals or eng.sched.has_work:
        eng.step()
    got2 = [r.tokens for r in sorted(eng._finished, key=lambda r: r.id)]
    assert got2 == want


@pytest.mark.slow
def test_paged_encdec_matches_slab():
    """Whisper under the paged layout (chunked decoder prefill + one
    fixed-shape encoder program per admission) matches the slab engine;
    no prompt-length specializations compile."""
    cfg = get_config("whisper-medium").reduced()
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))
    mk = lambda: synthetic_requests(cfg, n=4, tokens=4, prompt_len=10,
                                    scenario="server", seed=5)
    slab = Engine(cfg, params, None,
                  ServeConfig(max_batch=2, max_len=32, prefill_len=16,
                              kv_layout="slab"))
    want = sorted(tuple(r.tokens) for r in run_server(slab, mk()).requests)
    paged = Engine(cfg, params, None,
                   ServeConfig(max_batch=2, max_len=32, kv_layout="paged",
                               page_size=4, prefill_chunk=4))
    assert paged.layout == "paged"
    got = sorted(tuple(r.tokens) for r in run_server(paged, mk()).requests)
    assert got == want
    assert paged.compiled_programs() == {"chunk": 1, "encode": 1}


def test_engine_rejects_oversized_requests():
    cfg = get_config("gemma-7b").reduced()
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))
    # slab layout: prompts must fit the padded prefill compile shape
    slab = Engine(cfg, params, None,
                  ServeConfig(max_batch=1, max_len=16, prefill_len=8,
                              kv_layout="slab"))
    with pytest.raises(ValueError, match="exceeds max_len"):
        slab.submit(Request(prompt=[1] * 8, max_new_tokens=12))
    with pytest.raises(ValueError, match="exceeds prefill_len"):
        slab.submit(Request(prompt=[1] * 12, max_new_tokens=2))
    # paged layout: no prefill_len cap (chunked prefill), but max_len and
    # the pool's single-request capacity still bound a request
    paged = Engine(cfg, params, None,
                   ServeConfig(max_batch=1, max_len=16, kv_layout="paged",
                               page_size=4, n_pages=3))
    paged.submit(Request(prompt=[1] * 10, max_new_tokens=2))  # 3 pages: ok
    with pytest.raises(ValueError, match="exceeds max_len"):
        paged.submit(Request(prompt=[1] * 8, max_new_tokens=12))
    with pytest.raises(ValueError, match="pages"):
        paged.submit(Request(prompt=[1] * 10, max_new_tokens=4))  # 4 > 3
    with pytest.raises(ValueError, match="token ids only"):
        paged.submit(Request(prompt=[1, 2], max_new_tokens=1,
                             media=np.zeros((2, cfg.d_model))))


def test_paged_layout_requires_attention_only_stack():
    """Explicit kv_layout='paged' on a recurrent stack is an error;
    'auto' silently keeps such stacks on the slab layout."""
    cfg = get_config("rwkv6-3b").reduced()
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="attention-only"):
        Engine(cfg, params, None,
               ServeConfig(max_batch=1, max_len=16, kv_layout="paged"))
    eng = Engine(cfg, params, None,
                 ServeConfig(max_batch=1, max_len=16, prefill_len=8))
    assert eng.layout == "slab"
    with pytest.raises(ValueError, match="kv_layout"):
        ServeConfig(kv_layout="ragged")


# --------------------------------------------------------------------------- #
# Page pool + paged scheduler (pure python).
# --------------------------------------------------------------------------- #
def _check_pool(pool: PagePool, n_pages: int):
    """Global invariants: conservation, exclusive ownership."""
    owned = [p for s in pool._slots.values() for p in s]
    assert len(owned) == len(set(owned)), "page double-owned"
    assert len(owned) + pool.free_pages == n_pages, "pages leaked"
    assert set(owned).isdisjoint(pool._free)
    for slot in pool._slots:
        row = pool.table_row(slot, 8 + len(pool._slots[slot]))
        n = len(pool._slots[slot])
        assert row[:n].tolist() == pool._slots[slot]
        assert (row[n:] == -1).all()


@pytest.mark.parametrize("seed", range(4))
def test_page_pool_randomized_alloc_free_defrag(seed):
    """Random alloc/ensure/free/defrag sequences keep every invariant:
    no page double-owned, all-or-nothing allocation, freed pages reused,
    defrag compacts without changing any slot's page count/order."""
    rng = random.Random(seed)
    n_pages = rng.randint(4, 24)
    pool = PagePool(n_pages, page_size=rng.randint(1, 8))
    freed_ever, reused = set(), False
    for _ in range(200):
        op = rng.random()
        slot = rng.randint(0, 5)
        if op < 0.45:
            n = rng.randint(0, n_pages + 2)
            before = pool.free_pages
            ok = pool.alloc(slot, n)
            if ok:
                assert pool.free_pages == before - n
                if freed_ever & set(pool._slots.get(slot, ())):
                    reused = True
            else:  # all-or-nothing: a failed grant changes nothing
                assert pool.free_pages == before and n > before
        elif op < 0.75:
            freed_ever |= set(pool._slots.get(slot, ()))
            pool.free_slot(slot)
        elif op < 0.9:
            pool.ensure(slot, rng.randint(0, n_pages * pool.page_size))
        else:
            sizes = {s: len(p) for s, p in pool._slots.items()}
            perm = pool.defrag()
            assert sorted(perm[: n_pages].tolist()) == list(range(n_pages))
            assert perm[n_pages] == n_pages  # trash page pinned
            assert {s: len(p) for s, p in pool._slots.items()} == sizes
            # compaction: occupied pages are exactly the low indices
            owned = [p for s in pool._slots.values() for p in s]
            assert sorted(owned) == list(range(len(owned)))
        _check_pool(pool, n_pages)
    assert reused, "freed pages were never reused (workload too light?)"


def test_paged_scheduler_budget_admission_and_preempt():
    """Admission is by free-page budget with strict FIFO head-of-line
    blocking; preemption frees the pages and requeues at the front."""
    pool = PagePool(4, page_size=4)
    sched = PagedScheduler(2, pool, cost=lambda r: pool.pages_for(
        len(r.prompt) + len(r.tokens)))
    big = Request(prompt=[1] * 12, max_new_tokens=1)    # 3 pages
    small = Request(prompt=[2] * 4, max_new_tokens=1)   # 1 page
    tiny = Request(prompt=[3] * 2, max_new_tokens=1)    # 1 page
    for r in (big, small, tiny):
        sched.submit(r)
    admitted = sched.admit()
    # big (3 pages) + small (1 page) fill the pool; tiny blocks
    assert [r is big for _, r in admitted][0] and len(admitted) == 2
    assert pool.free_pages == 0 and tiny.state is RequestState.QUEUED
    # nothing admits while the pool is dry, even with a free slot
    sched.retire(small.slot if small.slot is not None else 1)
    assert sched.admit() == [(1, tiny)]  # small's page freed -> tiny fits
    # preempting big frees its 3 pages and requeues it at the front
    slot_big = big.slot
    out = sched.preempt(slot_big)
    assert out is big and big.state is RequestState.QUEUED
    assert pool.free_pages == 3 and big.slot is None
    assert sched.admit()[0][1] is big  # front of the FIFO


@given(st.integers(0, 9), st.integers(1, 3), st.integers(4, 12))
def test_scheduler_preemption_invariants_property(seed, max_batch, n_req):
    """Randomized arrival + preemption orders on the plain Scheduler:
    no slot is ever shared, admission always drains the queue in ticket
    (sched_seq) order — which is what makes a preempted request re-enter
    at the *front* of its band — and every request, preempted or not,
    eventually finishes."""
    rng = random.Random(seed * 1009 + max_batch * 31 + n_req)
    sched = Scheduler(max_batch)
    pending = [Request(prompt=[1] * (1 + i % 5)) for i in range(n_req)]
    all_reqs, preempted_ever = list(pending), set()
    rounds = 0
    while pending or sched.has_work:
        rounds += 1
        for _ in range(rng.randint(0, 2)):
            if pending:
                sched.submit(pending.pop(0))
        queued = sorted(r.sched_seq for r in sched._queue)
        admitted = sched.admit()
        # FIFO-front requeue: admissions are exactly the lowest tickets
        assert sorted(r.sched_seq for _, r in admitted) == \
            queued[: len(admitted)]
        running = sched.running()
        slots = [i for i, _ in running]
        assert len(set(slots)) == len(slots) <= max_batch
        assert len({id(r) for _, r in running}) == len(running), \
            "one request holds two slots"
        for i, r in running:
            assert r.state is RequestState.RUNNING and r.slot == i
        for i, r in list(running):
            roll = rng.random()
            if roll < 0.25 and rounds < 200:
                out = sched.preempt(i)
                assert out is r and r.state is RequestState.QUEUED
                assert r.slot is None
                preempted_ever.add(r)
            elif roll < 0.75 or rounds >= 200:
                sched.retire(i)
    assert all(r.state is RequestState.FINISHED for r in all_reqs), \
        "a request (possibly preempted) never finished"
    assert preempted_ever <= set(all_reqs)


@given(st.integers(0, 9), st.integers(1, 3), st.integers(3, 10))
def test_paged_scheduler_preemption_invariants_property(
        seed, max_batch, n_pages):
    """Same randomized schedule through the budgeted PagedScheduler:
    page accounting stays exact at every round (free + reserved ==
    pool), no physical page is mapped by two slots, preempted requests
    always resume and finish, and the pool drains back to empty."""
    rng = random.Random(seed * 7919 + max_batch * 13 + n_pages)
    pool = PagePool(n_pages, page_size=4)
    sched = PagedScheduler(
        max_batch, pool,
        cost=lambda r: pool.pages_for(r.prompt_len + len(r.tokens)))
    cap = 4 * min(n_pages, 3)  # every request fits the pool on its own
    pending = [Request(prompt=[1] * rng.randint(1, cap)) for _ in range(8)]
    all_reqs = list(pending)
    rounds = 0
    while pending or sched.has_work:
        rounds += 1
        for _ in range(rng.randint(0, 2)):
            if pending:
                sched.submit(pending.pop(0))
        sched.admit()
        running = sched.running()
        assert len({i for i, _ in running}) == len(running) <= max_batch
        reserved = [p for i, _ in running for p in pool.slot_pages(i)]
        assert len(set(reserved)) == len(reserved), "page double-mapped"
        assert pool.free_pages == n_pages - len(reserved), \
            "page accounting drifted"
        for i, r in running:
            assert len(pool.slot_pages(i)) == pool.pages_for(r.prompt_len)
        for i, r in list(running):
            roll = rng.random()
            if roll < 0.3 and rounds < 300:
                sched.preempt(i)
                assert r.state is RequestState.QUEUED
            elif roll < 0.8 or rounds >= 300:
                sched.retire(i)
    assert all(r.state is RequestState.FINISHED for r in all_reqs)
    assert pool.free_pages == n_pages, "retired pages leaked"


def test_synthetic_requests_prompt_lens_spread():
    cfg = get_config("gemma-7b").reduced()
    reqs = synthetic_requests(cfg, n=6, tokens=2, prompt_len=16,
                              prompt_lens=(3, 9, 14))
    assert [r.prompt_len for r in reqs] == [3, 9, 14, 3, 9, 14]
    # default draw is already a spread, never exceeding prompt_len
    reqs = synthetic_requests(cfg, n=12, tokens=2, prompt_len=16, seed=1)
    lens = {r.prompt_len for r in reqs}
    assert len(lens) > 1 and max(lens) <= 16 and min(lens) >= 8
