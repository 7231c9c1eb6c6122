"""Regenerate the golden int8-program outputs checked into tests/golden/.

  PYTHONPATH=src python tests/golden/generate.py [model ...]

One ``<model>.npz`` per model: :func:`repro.compiler.make_golden` over
the program the serve paths compile (``compile_for_serving``: seeded
params and calibration, both drawn and run on the host CPU device), run
on the jitted batched runner (route="f32" — bit-identical to the int32
oracle and the Pallas kernel) over the seeded golden frames. Because
the compile is host-side, the same record is the ground truth on every
platform; ``chip_smoke.py`` checks it on the TPU. Stored:

  acc_sample  first 32 raw int32 accumulators of frame 0
  acc_crc     crc32 of the full int32 accumulator buffer (both frames)
  top1        per-frame argmax class ids
  e_input     frozen input exponent
  e_out       per-compute-step frozen output exponents

``tests/test_executor.py::test_golden_int8_program`` replays the same
compile and compares bit-for-bit. Only regenerate when the quantization
semantics change *intentionally* — and say so in the commit.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.compiler import make_golden, save_golden        # noqa: E402
from repro.serving.server import compile_for_serving       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def golden_for(model_name: str) -> dict:
    return make_golden(compile_for_serving(model_name), route="f32")


def main(argv=None) -> int:
    models = (argv or sys.argv[1:]) or ["zf", "yolo"]
    for name in models:
        data = golden_for(name)
        out = os.path.join(HERE, f"{name}.npz")
        save_golden(out, data)
        print(f"wrote {out}: top1={data['top1'].tolist()} "
              f"crc={int(data['acc_crc'])} e_input={int(data['e_input'])}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
