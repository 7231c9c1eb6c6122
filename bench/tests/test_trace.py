"""The trace reduction on a synthetic trace, and on a recorded one."""

import pytest

from bench import trace as T


def _trace():
    # Window 0..10 s. Chip 0: ops 1-3 and 2-4 overlap (busy 1-4), 6-7,
    # and 9.5-11 runs past the window's end. Chip 1: 0-5.
    return T.Trace(
        chips={0: [("fusion.1", 1.0, 3.0), ("fusion.2", 2.0, 4.0),
                   ("copy", 6.0, 7.0), ("fusion.1", 9.5, 11.0)],
               1: [("fusion.1", 0.0, 5.0)]},
        spans=[(T.WINDOW_SPAN, 0.0, 10.0), ("bench.submit", 0.0, 0.9),
               ("bench.wait", 4.0, 6.0), ("bench.generate", 7.0, 9.4)])


def test_union_merges_and_clips():
    assert T.union([(1, 3), (2, 4), (6, 7), (9.5, 11)], 0, 10) == [
        (1, 4), (6, 7), (9.5, 10)]


def test_busy_idle_and_ops():
    r = T.reduce(_trace(), [0, 1])
    assert r["window_s"] == 10.0
    assert r["busy_s_per_chip"] == pytest.approx([4.5, 5.0])
    assert r["busy_s"] == pytest.approx(4.75)
    assert T.idle_percent(r) == pytest.approx(52.5)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((2.0 + 0.5 + 5.0) / 2)
    assert list(ops)[0] == "fusion.1"


def test_gaps_labelled_by_host_span():
    r = T.reduce(_trace(), [0])
    gaps = r["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([2.5, 2.0, 1.0])
    assert [g[0] for g in gaps] == ["bench.generate", "bench.wait",
                                    "bench.submit"]


def test_nothing_to_read_gives_none():
    t = _trace()
    assert T.reduce(t, [7]) is None
    assert T.reduce(T.Trace(chips=t.chips, spans=[]), [0]) is None
    assert T.idle_percent(None) is None


def test_load_reads_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation(T.WINDOW_SPAN):
        with TraceAnnotation("bench.submit"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = T.load(str(tmp_path))
    names = [s[0] for s in t.spans]
    assert T.WINDOW_SPAN in names and "bench.submit" in names
    (w,) = [s for s in t.spans if s[0] == T.WINDOW_SPAN]
    (sub,) = [s for s in t.spans if s[0] == "bench.submit"]
    assert w[1] <= sub[1] <= sub[2] <= w[2]
    # A CPU run has no TPU plane: there is nothing to read.
    assert t.chips == {} and T.reduce(t, [0]) is None
