"""The trace reduction on a small synthetic trace."""
import pytest

from perfbench import trace as T

DEV = "/device:TPU:0"


def _trace():
    ops = [  # a loop op containing two kernel calls, then a lone op
        ("%while.3 = (..) while(..)", 1.0, 1.5),
        ("%paged_decode_attention.7 = bf16[..] custom-call(..)", 1.1, 1.2),
        ("%paged_decode_attention.7 = bf16[..] custom-call(..)", 1.3, 1.4),
        ("%fusion.2 = f32[..] fusion(%paged_decode_attention.7)", 1.8, 1.9),
    ]
    modules = [("jit_serve_step(123)", 1.0, 1.5),
               ("jit_serve_step(123)", 3.0, 3.2),
               ("jit_sample(9)", 1.8, 1.9)]
    host = [(T.NAMES["step_span"], 0.9, 2.0),      # step 1
            ("$engine.py:600 sample_tokens", 1.5, 1.95),
            (T.NAMES["step_span"], 2.9, 3.3),      # step 2, device idle
            ("$scheduler.py:545 step", 2.9, 3.3)]
    return T.Trace(ops={DEV: ops}, modules={DEV: modules}, host=host)


def test_union_and_intersect():
    assert T.union([(3, 4), (1, 2), (1.5, 2.5)]) == [(1, 2.5), (3, 4)]
    assert T.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert T.length([(0, 1), (2, 4.5)]) == 3.5


def test_busy_counts_nested_ops_once():
    tr = _trace()
    assert T.busy(tr, DEV) == [(1.0, 1.5), (1.8, 1.9)]
    assert T.busy_seconds(tr) == pytest.approx(0.6)


def test_idle_share_is_over_step_spans_only():
    tr = _trace()
    steps = 1.1 + 0.4               # spans (0.9, 2.0) and (2.9, 3.3)
    busy_in_steps = 0.5 + 0.1
    assert T.idle_share(tr) == pytest.approx(1 - busy_in_steps / steps)


def test_kernel_time_by_declared_name():
    tr = _trace()
    # the fusion that reads the kernel's output is not the kernel
    assert T.kernel_seconds(tr, "paged_decode") == pytest.approx(0.2)
    t, n = T.module_time(tr, T.NAMES["programs"]["decode"])
    assert n == 2 and t == pytest.approx(0.7)


def test_missing_kernel_fails():
    with pytest.raises(T.MissingKernel):
        T.kernel_seconds(_trace(), "paged_prefill")


def test_idle_gaps_name_what_the_host_did():
    gaps = dict(T.idle_gaps(_trace()))
    assert gaps["$scheduler.py:545 step"] == pytest.approx(0.4)
    assert gaps["$engine.py:600 sample_tokens"] == pytest.approx(0.3 + 0.1)
    assert gaps[T.NAMES["step_span"]] == pytest.approx(0.1)
    top = T.top_ops(_trace())
    assert top[0][0] == "while"
    assert dict(top)["paged_decode_attention"] == pytest.approx(0.2)
