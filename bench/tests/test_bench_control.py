"""The comparison that decides ``correct`` tells the configured float32
program from the program's own int8 path (the control), driven through
a whole run at a tiny size on the CPU."""
import pytest

from . import _tiny


@pytest.mark.parametrize("name", ["resnet50_bulk", "squeezenet1_0_bulk"])
def test_float32_program_is_correct_and_int8_control_is_not(name):
    ok = _tiny.run(name, seed=2**33 + 11)
    assert ok["correct"], ok["checks"]
    control = _tiny.run(name, seed=2**33 + 11, precision="int8")
    assert not control["correct"]
    err = control["checks"]["logit_err"]
    assert err["value"] > err["limit"] > ok["checks"]["logit_err"]["value"]


def test_interactive_run_reports_latency_and_is_correct():
    res = _tiny.run("resnet50_interactive", seed=7)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert set(m) == {"latency_p50_ms", "latency_p95_ms", "setup_s"}
    assert 0 < m["latency_p50_ms"]["value"] <= m["latency_p95_ms"]["value"]
    assert res["attempted"] == 60 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_bf16_policy_is_caught_by_the_stated_dtype():
    """The bf16 policy's logits lie as close to the reference as float32's
    at the default matmul precision, so the stated dtype decides."""
    ok = _tiny.run("resnet50_bulk", seed=2**31 + 5)
    assert ok["checks"]["batches_off_dtype"]["value"] == 0
    control = _tiny.run("resnet50_bulk", seed=2**31 + 5, precision="bf16")
    assert not control["correct"]
    assert control["checks"]["batches_off_dtype"]["value"] > 0
