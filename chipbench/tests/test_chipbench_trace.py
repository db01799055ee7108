"""Trace reduction on constructed traces and on one recorded on CPU."""
import cb_fixtures  # noqa: F401  (puts the repo on sys.path)
import pytest

from chipbench import trace as T
from chipbench import harness as H


def test_union_busy_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (10, 9)]
    assert T.union(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert T.busy(iv, 0.0, 5.0) == pytest.approx(3.0)
    assert T.busy(iv, 1.5, 3.5) == pytest.approx(1.0)
    assert T.idle_gaps(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert T.idle_gaps([], 0.0, 1.0) == [(0.0, 1.0)]


@pytest.mark.parametrize("lo,hi,want,n", [(0, 10, 2.5, 2), (1.5, 10, 1.5, 1),
                                          (9, 10, 0.0, 0)])
def test_kernel_time_by_name(lo, hi, want, n):
    ops = [T.Op("custom-call.1", 0, 1, "paged_attention"),
           T.Op("fusion.2", 1, 2, ""),
           T.Op("custom-call.3", 2, 3.5, "paged_attention"),
           T.Op("custom-call.4", 3, 4.5, "flash")]
    assert T.kernel_time(ops, "paged", lo, hi) == (pytest.approx(want), n)


def test_exposed_collectives():
    ops = [T.Op("fusion.1", 0, 2), T.Op("all-reduce.1", 1, 3),
           T.Op("all-gather-start.2", 5, 6), T.Op("fusion.3", 5.5, 7)]
    total, exposed = T.exposed(ops, 0, 10)
    assert total == pytest.approx(3.0)
    assert exposed == pytest.approx(1.5)  # (2, 3) and (5, 5.5)


def test_exposed_share_reader_averages_devices_and_skips_one_chip():
    read = H.load_metric("collective.exposed_share").read
    two = T.Trace({"/device:TPU:0": [T.Op("fusion.1", 0, 2),
                                     T.Op("all-reduce.1", 1, 3)],
                   "/device:TPU:1": [T.Op("fusion.1", 0, 2.5),
                                     T.Op("all-gather.2", 1, 3),
                                     T.Op("fusion.3", 2.5, 4)]}, [])
    run = {"kind": "train", "trace": two, "window": (0, 4)}
    assert read(run) == pytest.approx(100.0 * (1.0 + 0.0) / 2 / 4)
    one = T.Trace({"/device:TPU:0": [T.Op("fusion.1", 0, 2)]}, [])
    assert read(dict(run, trace=one)) is None
    assert read(dict(run, kind="serve")) is None


def test_attribute_gaps_to_innermost_span():
    spans = [("chipbench.window", 0, 10), ("chipbench.step", 1, 2),
             ("chipbench.client", 2, 2.5), ("chipbench.step", 3, 4)]
    gaps = [(1.2, 1.4), (2.1, 2.3), (2.6, 2.8), (3.5, 3.9), (11, 12)]
    got = T.attribute(gaps, spans)
    assert got == pytest.approx({"chipbench.step": 0.6,
                                 "chipbench.client": 0.2,
                                 "chipbench.window": 0.2, "none": 1.0})
    assert T.top(got, 2) == [["none", 1.0], ["chipbench.step",
                                             pytest.approx(0.6)]]


def test_summary_and_breakdown_on_constructed_trace():
    tr = T.Trace({"/device:TPU:0": [T.Op("a", 0, 1), T.Op("b", 2, 3)],
                  "/device:TPU:1": [T.Op("a", 0, 2)]},
                 [("chipbench.window", 0, 4), ("chipbench.wait", 1, 2)])
    assert tr.window("chipbench.window") == (0, 4)
    assert tr.window("chipbench.none") is None
    s = H.device_summary(tr, 0, 4)
    assert s == {"busy_s": pytest.approx(2.0), "window_s": 4}
    b = H.breakdown(tr, 0, 4)
    assert b["device_ops"] == [["a", 1.0], ["b", 1.0]]
    assert dict(map(tuple, b["idle_gaps"])) == pytest.approx(
        {"chipbench.wait": 1.0, "chipbench.window": 1.0})


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(T.find(str(tmp_path)))
    names = [n for n, _, _ in tr.spans]
    assert names.count("chipbench.step") == 3
    lo, hi = tr.window("chipbench.window")
    steps = [(s, e) for n, s, e in tr.spans if n == "chipbench.step"]
    assert all(lo <= s <= e <= hi for s, e in steps)
    with pytest.raises(FileNotFoundError):
        T.find(str(tmp_path / "none"))
