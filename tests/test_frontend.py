"""Tracing frontend tests."""

import numpy as np
import pytest

from repro.core import CGRA, map_dfg
from repro.core.frontend import trace_loop
from repro.core.simulate import check_equivalence, interpret_dfg


def test_trace_mac_loop_maps_and_executes():
    def body(ins, carried):
        acc = carried["acc"] + ins[0] * ins[1]
        return [acc], {"acc": acc}

    dfg = trace_loop(body, num_inputs=2, carried=["acc"], name="mac")
    assert dfg.ops.count("store") == 1
    assert dfg.carried_edges()
    res = map_dfg(dfg, CGRA(2, 2), time_budget_s=20)
    assert res.ok
    check_equivalence(res.mapping, num_iters=6)


def test_trace_semantics_mac():
    """The traced MAC must actually accumulate across iterations."""
    def body(ins, carried):
        acc = carried["acc"] + ins[0] * ins[1]
        return [acc], {"acc": acc}

    dfg = trace_loop(body, num_inputs=2, carried=["acc"])
    a = [1.0, 2.0, 3.0]
    b = [10.0, 10.0, 10.0]
    inputs = {v: (a if i == 0 else b) for i, v in enumerate(
        [n for n in dfg.nodes if dfg.ops[n] == "input"])}
    outs = interpret_dfg(dfg, inputs, 3)
    stream = next(iter(outs.values()))
    assert stream == [10.0, 30.0, 60.0]   # running sum of a*b


def test_trace_mixed_ops_and_constants():
    def body(ins, carried):
        x = (ins[0] + 2.0) * ins[1] - 1.0
        y = abs(-x).min(100.0)
        return [y], {}

    dfg = trace_loop(body, num_inputs=2)
    res = map_dfg(dfg, CGRA(3, 3), time_budget_s=20)
    assert res.ok
    check_equivalence(res.mapping, num_iters=4)


def test_trace_rejects_bad_carried():
    with pytest.raises(ValueError):
        trace_loop(lambda ins, c: ([ins[0]], {"other": ins[0]}),
                   num_inputs=1, carried=["acc"])
