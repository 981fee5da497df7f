"""The trace reduction, checked on a trace recorded here on the CPU."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    from bench.run import _options

    def sort_twice(x):
        return jnp.sort(x)[::-1] * 2

    f = jax.jit(sort_twice)
    x = jnp.arange(1 << 16, dtype=jnp.int32)[::-1]
    f(x).block_until_ready()
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d), profiler_options=_options()):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
    sorter = trace_reduce.program(f.lower(x).compile())
    return trace_reduce.reduce_dir(d, 1, {"sorter": sorter})


def test_busy_and_idle_make_the_window(recorded):
    red = recorded
    assert 0 < red["busy_s"] <= red["window_s"]
    idle = sum(s for _, s in red["breakdown"]["idle_gaps"])
    assert red["busy_s"] + idle == pytest.approx(red["window_s"], rel=1e-6)


def test_named_ops_and_programs_are_found(recorded):
    names = [n for n, _ in recorded["breakdown"]["device_ops"]]
    assert any(n.startswith("jit_sort_twice:") and "sort" in n.split(":")[1]
               for n in names), names
    assert 0 < recorded["program_s"]["sorter"] <= recorded["busy_s"] + 1e-9
    assert len(recorded["breakdown"]["device_ops"]) <= 10
    assert recorded["collective_s"] == 0


def test_idle_gaps_are_labelled_by_host_activity(recorded):
    labels = [n for n, _ in recorded["breakdown"]["idle_gaps"]]
    assert any(n.startswith("bench.step") or n == "bench.window"
               for n in labels), labels


def test_a_trace_without_a_window_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp
    with jax.profiler.trace(str(tmp_path)):
        jnp.ones(4).block_until_ready()
    with pytest.raises(ValueError):
        trace_reduce.reduce_dir(tmp_path, 1)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        trace_reduce.peaks("cpu")
