"""The reduction of the server's spans and device programs, on a
synthetic trace and on a recorded one."""

import threading

import pytest

from bench import spans as S
from bench import trace as T


def _traces():
    # Window 0..10 s. Thread 1 is the batcher: an assemble span that
    # starts before the window, one with a fill wait inside, a dispatch
    # with quantize and an enqueue wait inside, and a dispatch that runs
    # past the window's end. Threads 2 and 3 run stages 0 and 1, each
    # with a ready wait inside. Chip 0 is busy 1-2 and 5-6.
    spans = [
        ("serve.assemble", -1.0, 0.4, 1, {}),
        ("serve.assemble", 0.5, 1.5, 1, {}),
        ("serve.fill.wait", 0.6, 1.0, 1, {}),
        ("serve.dispatch", 1.5, 2.5, 1, {}),
        ("serve.quantize", 1.6, 2.0, 1, {"batch": 0}),
        ("serve.enqueue.wait", 2.0, 2.4, 1, {"batch": 0}),
        ("serve.dispatch", 9.8, 10.5, 1, {}),
        ("serve.enqueue.wait", 9.9, 10.3, 1, {"batch": 1}),
        ("serve.stage", 1.0, 3.0, 2, {"batch": 0, "stage": 0}),
        ("serve.stage.ready.wait", 1.5, 2.8, 2, {"batch": 0, "stage": 0}),
        ("serve.stage", 2.5, 4.0, 3, {"batch": 0, "stage": 1}),
        ("serve.stage.ready.wait", 3.0, 3.5, 3, {"batch": 0, "stage": 1}),
    ]
    modules = {0: [("jit_serve_m_0_2", 1.0, 2.0), ("jit_serve_m_0_2", 5.0, 6.0),
                   ("jit_copy", 9.5, 10.5), ("jit_serve_m_2_4", -0.5, 0.5)]}
    trace = T.Trace(chips={0: [("fusion.1", 1.0, 2.0), ("copy.7", 5.0, 6.0)]},
                    spans=[(T.WINDOW_SPAN, 0.0, 10.0)])
    return trace, S.ServerTrace(spans=spans, modules=modules)


def _rows(r):
    return {k: (v["n"], pytest.approx(v["s"]), pytest.approx(v["work_s"]))
            for k, v in r["server_spans"].items()}


def test_server_spans_clip_to_the_window_and_take_out_waits():
    r = S.reduce(*_traces(), [0])
    assert _rows(r) == {
        "serve.assemble": (1, 1.4, 1.0),
        "serve.fill.wait": (1, 0.4, 0.0),
        "serve.dispatch": (2, 1.2, 0.7),
        "serve.quantize": (1, 0.4, 0.4),
        "serve.enqueue.wait": (2, 0.5, 0.0),
        "serve.stage": (2, 3.5, 1.7),
        "serve.stage[0]": (1, 2.0, 0.7),
        "serve.stage[1]": (1, 1.5, 1.0),
        "serve.stage.ready.wait": (2, 1.8, 0.0),
        "serve.stage.ready.wait[0]": (1, 1.3, 0.0),
        "serve.stage.ready.wait[1]": (1, 0.5, 0.0),
    }


def test_waits_on_another_thread_are_not_taken_out():
    trace, server = _traces()
    # The stage's ready wait (thread 2) lies inside the batcher's
    # dispatch in time, not on its thread.
    server.spans = [s for s in server.spans
                    if s[3] != 1 or s[0] == "serve.dispatch"]
    r = S.reduce(trace, server, [0])
    assert r["server_spans"]["serve.dispatch"]["work_s"] == pytest.approx(1.2)


def test_idle_by_span_counts_spans_open_on_any_thread():
    r = S.reduce(*_traces(), [0])
    # Idle 0-1, 2-5, 6-10; stages open 1-4 over two threads.
    assert dict(r["idle_by_span"]) == pytest.approx({
        "serve.stage": 2.0, "serve.stage.ready.wait": 1.3,
        "serve.assemble": 0.9, "serve.dispatch": 0.7,
        "serve.enqueue.wait": 0.5, "serve.fill.wait": 0.4,
        "serve.quantize": 0.0})
    assert r["idle_by_span"][0][0] == "serve.stage"


def test_device_by_module_clips_seconds_and_counts_runs_in_the_window():
    r = S.reduce(*_traces(), [0])
    got = {n: (pytest.approx(s), pytest.approx(k))
           for n, s, k in r["device_by_module"]}
    assert got == {"jit_serve_m_0_2": (2.0, 2), "jit_copy": (0.5, 1),
                   "jit_serve_m_2_4": (0.5, 0)}
    assert r["device_by_module"][0][0] == "jit_serve_m_0_2"


def test_two_chips_are_averaged():
    trace, server = _traces()
    trace.chips[1] = [("fusion.1", 0.0, 10.0)]
    server.modules[1] = [("jit_serve_m_0_2", 0.0, 10.0)]
    r = S.reduce(trace, server, [0, 1])
    assert dict(r["idle_by_span"])["serve.stage"] == pytest.approx(1.0)
    (row,) = [m for m in r["device_by_module"] if m[0] == "jit_serve_m_0_2"]
    assert row[1:] == pytest.approx([6.0, 1.5])


def test_per_batch_readings():
    r = S.reduce(*_traces(), [0])
    assert S.per_batch_ms(r) == pytest.approx({
        "frontend.dispatch_ms": 850.0, "host.quantize_ms": 400.0,
        "pipeline.collect_ms": None, "stage.device_ms": 1000.0})


def test_nothing_to_read_gives_none():
    trace, server = _traces()
    assert S.reduce(T.Trace(chips=trace.chips, spans=[]), server, [0]) is None
    assert S.reduce(trace, S.ServerTrace(spans=[], modules={}), [0]) is None
    assert set(S.per_batch_ms(None).values()) == {None}
    # A program without stage names: no stage reading.
    r = S.reduce(trace, S.ServerTrace(spans=server.spans, modules={
        0: [("jit_chain", 1.0, 2.0)]}), [0])
    assert S.per_batch_ms(r)["stage.device_ms"] is None
    # No chip ran anything: the host spans still read.
    r = S.reduce(trace, server, [7])
    assert r["idle_by_span"] == [] and r["device_by_module"] == []
    assert r["server_spans"]["serve.quantize"]["n"] == 1


@pytest.mark.parametrize("text, name", [("jit_f(123)", "jit_f"),
                                        ("jit_serve_m_0_2", "jit_serve_m_0_2")])
def test_module_name_drops_the_program_id(text, name):
    assert S.module_name(text) == name


def test_load_reads_server_spans_of_a_recorded_trace(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    def stage():
        with TraceAnnotation("serve.stage", batch=4, stage=1):
            with TraceAnnotation("serve.stage.ready.wait", batch=4, stage=1):
                pass

    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation(T.WINDOW_SPAN):
        with TraceAnnotation("serve.dispatch"):
            with TraceAnnotation("serve.quantize", batch=4):
                pass
        t = threading.Thread(target=stage)
        t.start()
        t.join()
    jax.profiler.stop_trace()
    server = S.load(str(tmp_path))
    by_name = {s[0]: s for s in server.spans}
    assert set(by_name) == {"serve.dispatch", "serve.quantize",
                            "serve.stage", "serve.stage.ready.wait"}
    assert by_name["serve.stage"][4] == {"batch": 4, "stage": 1}
    assert by_name["serve.quantize"][4] == {"batch": 4}
    assert by_name["serve.dispatch"][3] == by_name["serve.quantize"][3]
    assert by_name["serve.stage"][3] != by_name["serve.dispatch"][3]
    assert server.modules == {}          # a CPU run has no TPU plane
    r = S.reduce(T.load(str(tmp_path)), server, [0])
    assert r["server_spans"]["serve.stage[1]"]["n"] == 1
    assert r["idle_by_span"] == [] and r["device_by_module"] == []
