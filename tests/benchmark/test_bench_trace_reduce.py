"""The trace reduction on small hand-made traces (benchmark/trace_reduce.py)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "benchmark"))
import trace_reduce as tr  # noqa: E402


def test_union_counts_overlapping_lines_once_and_gaps_not_at_all():
    spans = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.7)]
    assert tr.union(spans) == [(0.0, 3.0), (5.0, 6.0)]
    assert tr.measure(spans) == pytest.approx(4.0)  # a sum would say 6.2


def test_subtract_and_gaps():
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.gaps([(1, 2), (4, 9)], 0, 10) == [(0, 1), (2, 4), (9, 10)]


def test_self_time_of_an_enclosing_operation():
    events = [("%while", 0.0, 10.0), ("%attn.1 custom-call", 1.0, 3.0),
              ("%attn.2 custom-call", 4.0, 5.0), ("%fusion", 10.0, 12.0)]
    table = tr.self_times(events)
    assert table["%while"] == [1, pytest.approx(7.0)]
    assert table["%attn.1 custom-call"] == [1, pytest.approx(2.0)]
    assert table["%fusion"] == [1, pytest.approx(2.0)]


@pytest.mark.parametrize("text,want", [
    ("%attn.36 = (bf16[192,1024,64]{2,1,0:T(8,128)(2,1)}, f32[192,1024,1]"
     "{2,1,0}) custom-call(bf16[192,1024,64] %x)", "%attn.36 custom-call"),
    ("%fusion.101 = f32[50304,768]{1,0:T(8,128)} fusion(bf16[16,1024,768] %a)",
     "%fusion.101 fusion"),
    ("%all-reduce-start.3 = f32[2]{0} all-reduce-start(f32[2] %x)",
     "%all-reduce-start.3 all-reduce-start"),
    ("no hlo here", "no hlo here"),
])
def test_short_name(text, want):
    assert tr.short_name(text) == want


def _planes():
    """Two devices, three executions of the step program on device 0: the
    first recorded in part (tracing began inside it). Each whole step: a
    kernel 2 s, an all-reduce 3 s of which 1 s runs under a loop that also
    holds a fusion, then 1 s idle."""
    mods, ops = [("jit_step", 0.0, 4.0)], [("%tail fusion", 0.0, 4.0)]
    for s in (4.0, 14.0):
        mods.append(("jit_step", s, s + 10.0))
        ops += [("%attn.1 custom-call", s, s + 2.0),
                ("%all-reduce.1 all-reduce", s + 2.0, s + 5.0),
                ("%while while", s + 5.0, s + 9.0),
                ("%fusion.2 fusion", s + 5.0, s + 8.0),
                ("%all-reduce.2 all-reduce", s + 8.0, s + 9.0)]
    mods.append(("jit_other", 30.0, 30.5))
    other = [(n, a, b) for n, a, b in ops if "attn" in n]
    return {"/device:TPU:0": {"ops": ops, "modules": mods},
            "/device:TPU:1": {"ops": other, "modules": []}}


def test_reduce_planes_steps_busy_kernel_and_exposed_collective():
    r = tr.reduce_planes(_planes())
    assert r["steps"] == 2 and r["step_program"] == "jit_step"
    assert r["window_s"] == pytest.approx(20.0)
    d0 = r["device0"]
    assert d0["busy_s"] == pytest.approx(18.0)          # 9 of every 10
    assert r["busy_s"] == pytest.approx((18.0 + 4.0) / 2)  # mean over chips
    assert d0["ops"]["%attn.1 custom-call"] == [2, pytest.approx(4.0)]
    assert d0["ops"]["%while while"] == [2, pytest.approx(0.0)]
    assert d0["collective_s"] == pytest.approx(8.0)
    assert d0["collective_exposed_s"] == pytest.approx(8.0)
    assert r["breakdown"]["idle_gaps"][0] == ["unknown", pytest.approx(1.0)]
    assert r["breakdown"]["device_ops"][0][0] == "%all-reduce.1 all-reduce"


def test_collective_under_compute_on_another_line_is_hidden():
    planes = _planes()
    planes["/device:TPU:0"]["ops"].append(("%big fusion", 6.0, 7.0))
    d0 = tr.reduce_planes(planes)["device0"]
    assert d0["collective_exposed_s"] == pytest.approx(8.0 - 1.0)


def test_idle_share_reader():
    import readers

    trace = tr.reduce_planes({"/device:TPU:0": _planes()["/device:TPU:0"]})
    ctx = {"trace": trace, "chips": 1}
    assert readers.device_idle_pct(ctx) == pytest.approx(10.0)
    assert readers.step_device_ms(ctx) == pytest.approx(9000.0)
    assert readers.device_idle_pct({"trace": None}) is None


def test_a_trace_with_no_device_plane_is_refused_outside_the_rehearsal(
        tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(jnp.ones(8).sum())
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="/device:TPU:"):
        tr.reduce_trace(str(tmp_path), 1)
