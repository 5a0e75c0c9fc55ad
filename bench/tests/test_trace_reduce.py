"""The trace reduction on a trace recorded here, on the CPU, where the
XLA client's threads stand in for the device's op line."""
import time

import pytest

from trace_reduce import find_xplane, reduce_trace, start, union_length


def test_union_of_intervals():
    total, merged = union_length([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert total == 5
    assert merged == [(0, 3), (5, 7), (9, 9)]


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    where = tmp_path_factory.mktemp("trace")
    start(str(where))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_wait"):
                time.sleep(0.03)
    jax.profiler.stop_trace()
    return find_xplane(str(where))


def test_reduce_cpu_trace(cpu_trace):
    red = reduce_trace(
        cpu_trace, device_plane=lambda n: n == "/host:CPU",
        ops_line=lambda n: n.startswith("tf_XLAPjRtCpuClient"),
        modules_line=lambda n: False,
        keep_op=lambda name, s: s > 0)
    assert red.n_devices == 1
    assert 0.09 <= red.window_s < 5.0
    assert 0.0 < red.busy_s < red.window_s
    share = red.idle_share()
    assert 0.0 < share < 1.0
    bd = red.breakdown()
    assert bd["device_ops"] and all(s > 0 for _, s in bd["device_ops"])
    # ops overlap across the client's threads: their sum bounds the union
    assert sum(red.ops_s.values()) >= red.busy_s * (1 - 1e-9)
    idle = dict(bd["idle_gaps"])
    # the device waits most while the host sleeps between steps
    assert max(idle, key=idle.get) == "bench.host_wait"
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)


def test_no_device_plane_reads_nothing(cpu_trace):
    red = reduce_trace(cpu_trace)
    assert red.n_devices == 0 and red.busy_s == 0.0
