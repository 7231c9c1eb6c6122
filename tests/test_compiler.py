"""Compiler front door (``repro.compiler``): JSON-spec ingestion, the
lowering contract (ReLU/pool folding, padding legalization, typed
rejection of engine-unrepresentable ops), cross-route int8 golden
parity for an imported non-paper CNN, and the registry-serve smoke that
pins the acceptance criterion — an imported model serves through
``build_server``/``Server.submit`` beside the paper models, with no
``onnx`` installed."""

import os

import numpy as np
import pytest

from repro import compiler
from repro.compiler import (GoldenMismatch, Graph, GraphError,
                            UnsupportedOpError, from_spec, import_source)
from repro.serving import (ProgramRegistry, ServerConfig, build_server,
                           synthetic_stream_like)

LENET_SPEC = os.path.join(os.path.dirname(__file__), os.pardir,
                          "examples", "lenet.json")


def tiny_spec(**over):
    spec = {
        "name": "tinynet",
        "input": {"hw": 8, "channels": 3},
        "nodes": [
            {"op": "conv", "name": "c1", "input": "input",
             "out_channels": 4, "kernel": 3, "padding": "same"},
            {"op": "relu", "name": "r1", "input": "c1"},
            {"op": "maxpool", "name": "p1", "input": "r1",
             "kernel": 2, "stride": 2},
            {"op": "flatten", "name": "fl", "input": "p1"},
            {"op": "fc", "name": "f1", "input": "fl",
             "out_features": 10},
        ],
    }
    spec.update(over)
    return spec


# ---------------------------------------------------------------------------
# Graph IR + spec ingestion
# ---------------------------------------------------------------------------


def test_spec_builds_validated_graph_with_shapes():
    g = from_spec(tiny_spec())
    assert isinstance(g, Graph)
    assert g.shapes["c1"] == (8, 8, 4)          # 'same' keeps hw
    assert g.shapes["p1"] == (4, 4, 4)          # k2 s2 halves
    assert g.shapes["fl"] == (64,)              # 4*4*4 flattened
    assert g.shapes["f1"] == (10,)
    assert g.output == "f1"


def test_unknown_op_is_typed_and_names_the_node():
    spec = tiny_spec()
    spec["nodes"][1] = {"op": "gelu", "name": "r1", "input": "c1"}
    with pytest.raises(UnsupportedOpError) as ei:
        import_source(spec)
    assert "r1" in str(ei.value) and "gelu" in str(ei.value)
    assert isinstance(ei.value, GraphError)     # one catchable base


def test_shape_mismatch_rejected_at_import_time():
    spec = tiny_spec()
    spec["nodes"][4]["in_features"] = 999       # producer has 64
    with pytest.raises(GraphError) as ei:
        import_source(spec)
    assert "999" in str(ei.value) and "64" in str(ei.value)


def test_structural_errors_rejected_at_import_time():
    spec = tiny_spec()
    spec["nodes"][0]["input"] = "ghost"         # undefined producer
    with pytest.raises(GraphError):
        import_source(spec)
    spec = tiny_spec()
    spec["nodes"][0]["kernell"] = 3             # typo'd attr, not default
    with pytest.raises(GraphError):
        import_source(spec)
    spec = tiny_spec()
    spec["nodes"].append({"op": "relu", "name": "dangling",
                          "input": "p1"})       # two unconsumed terminals
    with pytest.raises(GraphError):
        import_source(spec)


# ---------------------------------------------------------------------------
# Lowering: normalization onto the engine contract
# ---------------------------------------------------------------------------


def test_lowering_folds_relu_and_pool_into_engine_chain():
    model, params = import_source(tiny_spec())
    assert params is None                       # spec carries no weights
    assert [(l.name, l.kind) for l in model.layers] == \
        [("c1", "conv"), ("p1", "pool"), ("f1", "fc")]
    assert model.layers[2].in_ch == 64          # flatten folded into fc


def test_relu_folds_through_max_pool_exactly():
    """conv -> pool -> relu is legal: max and ReLU commute, so the fold
    into the conv's epilogue is semantics-preserving."""
    spec = tiny_spec()
    spec["nodes"] = [
        spec["nodes"][0],
        {"op": "maxpool", "name": "p1", "input": "c1",
         "kernel": 2, "stride": 2},
        {"op": "relu", "name": "r1", "input": "p1"},
        {"op": "flatten", "name": "fl", "input": "r1"},
        spec["nodes"][4],
    ]
    model, _ = import_source(spec)
    assert [l.name for l in model.layers] == ["c1", "p1", "f1"]


def test_engine_relu_contract_is_enforced():
    # A hidden layer without a ReLU lowers with relu=False: the engine
    # still requantizes its signed sum (a bottleneck's last conv).
    spec = tiny_spec()
    del spec["nodes"][1]
    spec["nodes"][1]["input"] = "c1"
    model, _ = import_source(spec)
    assert [(l.name, l.relu) for l in model.layers] == \
        [("c1", False), ("p1", False), ("f1", False)]
    # Trailing ReLU on the final layer: the final engine emits raw
    # accumulators.
    spec = tiny_spec()
    spec["nodes"].append({"op": "relu", "name": "r9", "input": "f1"})
    with pytest.raises(UnsupportedOpError) as ei:
        import_source(spec)
    assert "f1" in str(ei.value)


def _residual_spec(scale_b: float = 1.0):
    """conv c1 fans out to a conv branch and a 1x1 shortcut, summed by an
    add with a folded ReLU, then a global average pool and fc; weights
    ride on the nodes, the shortcut's scaled by ``scale_b``."""
    rng = np.random.default_rng(3)

    def conv(name, src, cin, cout, k, scale=1.0):
        return {"op": "conv", "name": name, "input": src,
                "out_channels": cout, "kernel": k, "padding": k // 2,
                "weight": scale * rng.standard_normal((k, k, cin, cout)),
                "bias": np.zeros(cout)}

    return {"name": "res", "input": {"hw": 8, "channels": 3}, "nodes": [
        conv("c1", "input", 3, 4, 3),
        {"op": "relu", "name": "r1", "input": "c1"},
        conv("a", "r1", 4, 4, 3),
        conv("b", "r1", 4, 4, 1, scale_b),
        {"op": "add", "name": "sum", "inputs": ["a", "b"]},
        {"op": "relu", "name": "r2", "input": "sum"},
        {"op": "avgpool", "name": "gap", "input": "r2", "kernel": 8},
        {"op": "flatten", "name": "fl", "input": "gap"},
        {"op": "fc", "name": "f1", "input": "fl", "out_features": 10,
         "weight": rng.standard_normal((4, 10)), "bias": np.zeros(10)},
    ]}


def test_engine_unrepresentable_ops_rejected_with_reason():
    """Average pooling and fan-out into a residual add lower and run; an
    add whose operands lie more than 23 bits apart cannot sum exactly in
    int32 and is refused at compile time, naming the add."""
    spec = tiny_spec()
    spec["nodes"][2] = {"op": "avgpool", "name": "p1", "input": "r1",
                        "kernel": 2, "stride": 2}
    model, _ = import_source(spec)
    assert [l.kind for l in model.layers] == ["conv", "avgpool", "fc"]

    model, params = import_source(_residual_spec())
    assert [(l.name, l.kind, l.inputs, l.relu) for l in model.layers] == [
        ("c1", "conv", ("input",), True), ("a", "conv", ("c1",), False),
        ("b", "conv", ("c1",), False), ("sum", "add", ("a", "b"), True),
        ("gap", "avgpool", ("sum",), False), ("f1", "fc", ("gap",), False)]
    prog = compiler.quantize(model, params, seed=0)
    frames = compiler.golden_frames(model, n=2, seed=0)
    runners = [prog.compile_runner(route=r) for r in ("f32", "oracle")]
    a, b = (np.asarray(r(r.quantize(frames))) for r in runners)
    np.testing.assert_array_equal(a, b)

    model, params = import_source(_residual_spec(scale_b=1e-9))
    with pytest.raises(UnsupportedOpError) as ei:
        compiler.quantize(model, params, seed=0)
    assert "'sum'" in str(ei.value) and "23 bits" in str(ei.value)


def test_illegal_padding_rejected_not_shifted():
    """Declared pads are honoured, never re-derived: k3 s2 p1 on 8 pads
    (1, 1) where the output arithmetic alone would derive (0, 1), and
    the served integers equal the plain reference's."""
    from bench.reference import cnn as reference
    spec = tiny_spec()
    spec["nodes"][0]["stride"] = 2
    spec["nodes"][0]["padding"] = 1
    spec["nodes"][2]["kernel"] = spec["nodes"][2]["stride"] = 1
    model, params = import_source(spec)
    assert model.layers[0].padding(8) == (1, 1)
    rng = np.random.default_rng(5)
    params = {l.name: {"w": rng.standard_normal(w).astype(np.float32),
                       "b": rng.standard_normal(w[-1]).astype(np.float32)}
              for l, w in ((model.layers[0], (3, 3, 3, 4)),
                           (model.layers[2], (64, 10)))}
    calib = rng.standard_normal((1, 8, 8, 3)).astype(np.float32)
    frames = rng.standard_normal((4, 8, 8, 3)).astype(np.float32)
    runner = compiler.quantize(model, params, calib=calib).compile_runner()
    net = reference.build({"graph": spec}, params, calib)
    np.testing.assert_array_equal(np.asarray(runner(runner.quantize(frames))),
                                  reference.accumulators(net, frames))


# ---------------------------------------------------------------------------
# The acceptance pin: import -> compile -> golden -> serve, no onnx
# ---------------------------------------------------------------------------


def test_lenet_round_trip_golden_bit_exact():
    """The examples/lenet.json spec (a non-paper CNN) compiles through
    compile_model and its int8 execution reproduces the generated
    golden bit-exactly across independent MAC routes (f32 generate,
    int32-oracle verify)."""
    model, params = import_source(LENET_SPEC)
    assert model.name == "lenet" and params is None
    prog = compiler.quantize(model, seed=0)
    golden = compiler.make_golden(prog, seed=0, route="f32")
    assert golden["acc_sample"].dtype == np.int32
    assert len(golden["acc_sample"]) == min(
        compiler.calibrate.N_ACC_SAMPLE, 10)   # 10 logits in frame 0
    # Bit-exact across routes — and deterministic from (spec, seed):
    # recompiling from scratch reproduces the identical artifact.
    compiler.check_golden(prog, golden, seed=0, route="oracle")
    prog2 = compiler.quantize(*import_source(LENET_SPEC), seed=0)
    golden2 = compiler.make_golden(prog2, seed=0, route="f32")
    assert int(golden["acc_crc"]) == int(golden2["acc_crc"])
    assert np.array_equal(golden["acc_sample"], golden2["acc_sample"])


def test_golden_mismatch_is_detected():
    model, _ = import_source(tiny_spec())
    prog = compiler.quantize(model, seed=0)
    golden = compiler.make_golden(prog, seed=0)
    bad = dict(golden)
    bad["acc_crc"] = int(golden["acc_crc"]) ^ 1
    with pytest.raises(GoldenMismatch) as ei:
        compiler.check_golden(prog, bad, seed=0)
    assert "acc_crc" in str(ei.value)


def test_golden_save_load_round_trip(tmp_path):
    model, _ = import_source(tiny_spec())
    prog = compiler.quantize(model, seed=0)
    golden = compiler.make_golden(prog, seed=0)
    path = tmp_path / "tiny_golden.npz"
    compiler.save_golden(path, golden)
    compiler.check_golden(prog, compiler.load_golden(path), seed=0)


def test_registry_serve_smoke_imported_model():
    """The end of the pipeline: register_imported puts the compiled +
    golden-checked program in the zoo, build_server serves it, and
    Server.submit resolves completed."""
    reg = ProgramRegistry()
    name, golden = reg.register_imported(tiny_spec(), seed=0)
    assert name == "tinynet" and name in reg
    assert int(golden["acc_crc"]) != 0
    with pytest.raises(ValueError):             # duplicate id refused
        reg.register_imported(tiny_spec(), seed=0)
    cfg = ServerConfig(batch=4, stages=1, calib_frames=12)
    srv = build_server(reg, cfg)                # no stream: derived from
    try:                                        # the imported model
        frames = synthetic_stream_like(reg.get(name).model, 3, seed=0)
        reqs = [srv.submit(name, f) for f in frames]
        for r in reqs:
            r.result(timeout=120)
        assert all(r.outcome == "completed" for r in reqs)
        st = srv.stats()
        assert st["models"][name]["completed"] == 3
    finally:
        srv.close()


def test_register_imported_golden_check_catches_broken_program(monkeypatch):
    """The cross-route check is live: if verification cannot reproduce
    the golden, the model never enters the zoo."""
    reg = ProgramRegistry()
    real = compiler.check_golden

    def sabotaged(prog, golden, **kw):
        bad = dict(golden)
        bad["acc_crc"] = int(golden["acc_crc"]) ^ 1
        real(prog, bad, **kw)

    monkeypatch.setattr("repro.compiler.check_golden", sabotaged)
    with pytest.raises(GoldenMismatch):
        reg.register_imported(tiny_spec(), seed=0)
    assert len(reg) == 0


# ---------------------------------------------------------------------------
# ONNX path (skips cleanly when onnx is absent)
# ---------------------------------------------------------------------------


def _make_lenet_onnx(path):
    import onnx
    from onnx import TensorProto, helper, numpy_helper

    rng = np.random.default_rng(0)

    def init(name, arr):
        return numpy_helper.from_array(arr.astype(np.float32), name)

    inits = [
        init("w1", rng.standard_normal((4, 1, 3, 3)) * 0.1),   # OIHW
        init("b1", rng.standard_normal((4,)) * 0.1),
        init("w2", rng.standard_normal((10, 64)) * 0.1),       # (out, in)
        init("b2", rng.standard_normal((10,)) * 0.1),
    ]
    nodes = [
        helper.make_node("Conv", ["x", "w1", "b1"], ["c1"], name="c1",
                         kernel_shape=[3, 3], pads=[1, 1, 1, 1]),
        helper.make_node("Relu", ["c1"], ["r1"], name="r1"),
        helper.make_node("MaxPool", ["r1"], ["p1"], name="p1",
                         kernel_shape=[2, 2], strides=[2, 2]),
        helper.make_node("Flatten", ["p1"], ["fl"], name="fl"),
        helper.make_node("Gemm", ["fl", "w2", "b2"], ["y"], name="fc",
                         transB=1),
    ]
    graph = helper.make_graph(
        nodes, "tiny_onnx",
        [helper.make_tensor_value_info("x", TensorProto.FLOAT,
                                       [1, 1, 8, 8])],
        [helper.make_tensor_value_info("y", TensorProto.FLOAT, [1, 10])],
        initializer=inits)
    model = helper.make_model(graph)
    onnx.save(model, str(path))


def test_onnx_import_matches_reference_float_forward(tmp_path):
    """ONNX round trip: NCHW/OIHW conventions translate so the lowered
    model + imported params reproduce a reference NHWC float forward
    (same conv/pool/fc arithmetic) to float tolerance."""
    onnx = pytest.importorskip("onnx")  # noqa: F841
    import jax.numpy as jnp

    from repro.core.program import float_forward

    path = tmp_path / "tiny.onnx"
    _make_lenet_onnx(path)
    model, params = import_source(str(path))
    assert params is not None                  # weights imported
    assert model.input_hw == 8 and model.input_ch == 1
    assert [l.kind for l in model.layers] == ["conv", "pool", "fc"]

    # Reference: the same arithmetic in NHWC numpy, weights straight
    # from the initializers the file was built with.
    rng = np.random.default_rng(0)
    w1 = (rng.standard_normal((4, 1, 3, 3)) * 0.1).astype(np.float32)
    b1 = (rng.standard_normal((4,)) * 0.1).astype(np.float32)
    w2 = (rng.standard_normal((10, 64)) * 0.1).astype(np.float32)
    b2 = (rng.standard_normal((10,)) * 0.1).astype(np.float32)
    x = rng.standard_normal((1, 8, 8, 1)).astype(np.float32)

    xp = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    conv = np.zeros((1, 8, 8, 4), np.float32)
    for i in range(8):
        for j in range(8):
            patch = xp[0, i:i + 3, j:j + 3, 0]          # (3, 3)
            for o in range(4):
                conv[0, i, j, o] = float((patch * w1[o, 0]).sum()) + b1[o]
    act = np.maximum(conv, 0.0)
    pool = act.reshape(1, 4, 2, 4, 2, 4).max(axis=(2, 4))
    flat_nchw = pool[0].transpose(2, 0, 1).reshape(-1)  # ONNX flatten order
    ref = flat_nchw @ w2.T + b2

    got = np.asarray(float_forward(params, model, jnp.asarray(x)))
    np.testing.assert_allclose(got[0], ref, rtol=1e-4, atol=1e-4)


def test_onnx_import_serves_end_to_end(tmp_path):
    pytest.importorskip("onnx")
    path = tmp_path / "tiny.onnx"
    _make_lenet_onnx(path)
    reg = ProgramRegistry()
    name, golden = reg.register_imported(str(path))
    assert name == "tiny"
    cfg = ServerConfig(batch=4, stages=1, calib_frames=12)
    srv = build_server(reg, cfg)
    try:
        frame = synthetic_stream_like(reg.get(name).model, 1, seed=0)[0]
        assert srv.submit(name, frame).result(timeout=120) is not None
    finally:
        srv.close()


def test_onnx_absent_raises_plain_import_error(monkeypatch):
    """The guarded path: with onnx unavailable the JSON pipeline is
    untouched and load_onnx raises ImportError, not a crash."""
    from repro.compiler import onnx_import
    monkeypatch.setattr(onnx_import, "onnx_available", lambda: False)
    with pytest.raises(ImportError):
        onnx_import.load_onnx("whatever.onnx")
    # and the dependency-free path still works end to end
    model, _ = import_source(tiny_spec())
    assert model.name == "tinynet"
