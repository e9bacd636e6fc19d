"""The control: the float32 reference computed in the next precision
below the served bf16 (fp8 projections), put in the program's place at
the same prompts and tokens, comes out not correct by the result line
under the limits that sound runs pass."""
from bench import harness
from bench.tests.test_bench_faults import run


def test_the_fp8_control_is_not_correct(tmp_path):
    line, res = run(tmp_path, "tiny.chat", controls=("fp8",))
    assert line["correct"] is True
    ctrl = harness.result_line(harness.Bench(tmp_path),
                               harness.as_control(res, "fp8"), False)
    assert ctrl["correct"] is False
    assert ctrl["failed"] >= 1
    c = ctrl["checks"]["logit_err"]
    assert c["value"] > c["limit"]
