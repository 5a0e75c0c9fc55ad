"""A run refuses any device but a TPU: no fallback to the CPU."""
import io
import time

import pytest

from harness.main import NoDevice, device_stamp, execute


def test_cpu_is_refused():
    with pytest.raises(NoDevice):
        device_stamp(1)


def test_run_prints_no_result_without_a_tpu():
    out, err = io.StringIO(), io.StringIO()
    rc = execute(["--workload", "mc_grid.paper-63n", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], time.perf_counter(),
                 out=out, err=err)
    assert rc != 0
    assert out.getvalue() == ""
    assert "needs a TPU" in err.getvalue()


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    with pytest.raises(NoDevice, match="peaks"):
        device_stamp(1)
    with pytest.raises(NoDevice, match="chips"):
        device_stamp(4)
