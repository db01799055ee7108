"""The program's names in a trace (chipbench.scopes) and the five readers
built on them, on a constructed trace and on one recorded on CPU around
a tiny paged engine."""
import types

import cb_fixtures  # noqa: F401  (puts the repo on sys.path)
import pytest

from chipbench import harness as H
from chipbench import scopes as S
from chipbench import trace as T

BODY = "jit(chunk_step)/while/body"


def _trace():
    """Window [0, 10] on one device: a call whose event encloses a
    kv_insert fusion and the kernel (the call's own path names
    attention, which only its leaves may count), an op with no scope, a
    backward op under transforms and an optimizer op. Busy 7 s; idle
    gaps (4, 5), (7, 8), (9, 10)."""
    ops = [S.Op("closed_call.1", 0, 4, scope=f"{BODY}/attention/closed_call"),
           S.Op("fusion.1", 0, 1,
                scope=f"{BODY}/kv_insert/dynamic_update_slice"),
           S.Op("paged_attention.2", 1, 3,
                scope=f"{BODY}/attention/paged_attention/pallas_call"),
           S.Op("fusion.3", 5, 6),
           S.Op("fusion.4", 6, 7,
                scope="jit(step)/transpose(jvp(attention_bwd))/mul"),
           S.Op("fusion.5", 8, 9, scope="jit(step)/optimizer/add")]
    program = [("repro.serve.device", 0.0, 3.9),
               ("repro.serve.schedule", 3.9, 4.2),
               ("repro.serve.prepare", 4.2, 4.3),
               ("repro.serve.device", 5.0, 7.0),
               ("repro.serve.emit", 7.5, 8.5),
               ("repro.serve.device", 12.0, 13.0)]
    return S.Trace({"/device:TPU:0": ops}, [("chipbench.traced", 0, 10)],
                   program)


def _run(kind, tr=None, **kw):
    tr = tr or _trace()
    return dict({"kind": kind, "trace": tr, "scoped": tr,
                 "window": (0.0, 10.0)}, **kw)


def _read(metric, run):
    return H.load_metric(metric).read(run)


def test_names_unwrap_transforms():
    assert S.names("jit(f)/transpose(jvp(attention_bwd))/mul") == {
        "f", "attention_bwd", "mul"}
    assert "kv_insert" in S.names(f"{BODY}/kv_insert/add")
    assert S.names("") == {""}


@pytest.mark.parametrize("metric,kind,want", [
    # leaves only: the enclosing call's 4 s under "attention" do not count
    ("train_step.attention_share", "train", 100.0 * 3 / 7),
    ("train_step.optimizer_share", "train", 100.0 * 1 / 7),
    ("chunk_step.kv_insert_share", "serve", 100.0 * 1 / 7),
])
def test_scope_shares_count_leaf_ops_over_busy_time(metric, kind, want):
    assert _read(metric, _run(kind)) == pytest.approx(want)
    other = "serve" if kind == "train" else "train"
    assert _read(metric, _run(other)) is None


def test_unscoped_ops_count_in_the_denominator_only():
    tr = _trace()
    ops = tr.devices["/device:TPU:0"]
    base = S.scope_share(tr, ("optimizer",), 0, 10)
    ops.append(S.Op("fusion.6", 9, 10))  # busy 8 s now, none of it scoped
    assert S.scope_share(tr, ("optimizer",), 0, 10) == pytest.approx(
        base * 7 / 8)
    assert S.scope_share(tr, ("nothing",), 0, 10) is None


def test_host_gap_intersects_idle_with_engine_spans():
    """(4, 5) meets schedule and prepare for 0.3 s; (7, 8) meets emit
    for 0.5 s (its midpoint lies in emit: a midpoint rule would give it
    all 1 s); two device spans start in the window, the third after."""
    run = _run("serve")
    got = _read("engine.host_gap_ms", run)
    assert got == pytest.approx(1e3 * (0.3 + 0.5) / 2)
    idle = sum(e - s for s, e in T.idle_gaps(
        ((o.start, o.end) for o in run["trace"].devices["/device:TPU:0"]),
        0, 10))
    assert got / 1e3 * 2 <= idle
    assert _read("engine.host_gap_ms", _run("train")) is None


def test_readers_read_nothing_where_the_program_names_nothing():
    """The parent program's trace: no program spans, no scopes."""
    bare = _trace()
    bare.program_spans = []
    for op in bare.devices["/device:TPU:0"]:
        op.scope = ""
    for metric, kind in [("engine.host_gap_ms", "serve"),
                         ("chunk_step.kv_insert_share", "serve"),
                         ("train_step.attention_share", "train"),
                         ("train_step.optimizer_share", "train")]:
        assert _read(metric, _run(kind, bare)) is None
    # an untraced run, and one whose trace is not on disk
    assert S.of_run({"kind": "serve"}) is None
    assert S.of_run({"kind": "serve", "trace": bare,
                     "window": (-1.0, -0.5)}) is None


def test_fed_share_reads_the_engine_count():
    from repro.serve.metrics import StepTrace

    steps = [StepTrace("mixed", 0.1, 1, n_fed=10, n_positions=16),
             StepTrace("decode", 0.1, 2, n_fed=2, n_positions=16),
             StepTrace("encode", 0.1, 0)]
    rep = types.SimpleNamespace(steps=steps)
    assert _read("chunk_step.fed_share",
                 {"kind": "serve", "report": rep}) == pytest.approx(37.5)
    old = types.SimpleNamespace(steps=[types.SimpleNamespace(kind="decode")])
    assert _read("chunk_step.fed_share",
                 {"kind": "serve", "report": old}) is None
    assert _read("chunk_step.fed_share", {"kind": "train"}) is None


def test_load_finds_engine_spans_and_scopes_of_a_recorded_trace(tmp_path):
    import jax

    from repro.configs import get_config
    from repro.dist import split_tree
    from repro.serve import Engine, Request, ServeConfig
    from repro.train.steps import ModelAPI

    cfg = get_config("gemma-7b").reduced()
    api = ModelAPI(cfg)
    params, _ = split_tree(api.init(cfg, jax.random.PRNGKey(0)))
    eng = Engine(cfg, params, None,
                 ServeConfig(max_batch=2, max_len=32, kv_layout="paged",
                             page_size=4, prefill_chunk=4))
    eng.submit(Request(prompt=[3] * 6, max_new_tokens=2))
    eng.drain()  # compiles the chunk program before the trace
    eng.submit(Request(prompt=[4] * 6, max_new_tokens=3))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.traced"):
        with jax.profiler.TraceAnnotation("chipbench.step"):
            eng.drain()
    jax.profiler.stop_trace()
    path = T.find(str(tmp_path))

    old, tr = T.load(path), S.load(path)
    assert {n for n, _, _ in old.spans} == {"chipbench.traced",
                                            "chipbench.step"}
    assert sorted(tr.spans) == sorted(old.spans)
    got = {}
    for name, _, _ in tr.program_spans:
        got[name] = got.get(name, 0) + 1
    # a 6-token prompt in chunks of 4, then two decode steps
    assert got == {"repro.serve.schedule": 4, "repro.serve.prepare": 4,
                   "repro.serve.device": 4, "repro.serve.emit": 4}
    paths = [p for mod in S.op_paths(open(path, "rb").read()).values()
             for p in mod.values()]
    for scope in ("kv_insert", "attention"):
        assert any(scope in S.names(p) for p in paths), scope
