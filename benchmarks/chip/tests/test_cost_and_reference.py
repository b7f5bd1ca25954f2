"""The algorithmic byte count of one loop, the plain reference's semantics,
and the mapping checker."""

import numpy as np
import pytest

import cost
import reference
from suite import ARITY, Graph, table3_graph

TABLE3 = {"fft": (20, 7), "gsm": (24, 4), "particlefilter": (38, 9), "backprop": (34, 5)}


def test_call_bytes_of_gsm():
    gsm = table3_graph("gsm", *TABLE3["gsm"])
    assert (len(gsm.nodes_of("input")), len(gsm.nodes_of("store"))) == (4, 1)
    # (4 inputs + 1 store) x 4 bytes x 2048 streams x 64 iterations + 16 bytes x 24 nodes
    assert cost.call_bytes(gsm, 2048, 64) == 5 * 4 * 2048 * 64 + 16 * 24 == 2_621_824
    assert cost.call_ops(gsm, 2048, 64) == (24 - 4) * 2048 * 64


@pytest.mark.parametrize("name", sorted(TABLE3))
def test_suite_graphs_keep_table3_statistics(name):
    g = table3_graph(name, *TABLE3[name])
    assert g.num_nodes == TABLE3[name][0]
    assert reference.rec_ii(g) == TABLE3[name][1]
    assert reference.min_ii(g, 16) == max(-(-g.num_nodes // 16), TABLE3[name][1])
    for v, op in enumerate(g.ops):
        assert len(reference.operands(g, v)) <= max(ARITY[op], 1)


def _accumulator() -> Graph:
    # x -> phi(x, carried) -> store ; phi's carried operand is its own last value
    return Graph("acc", ("input", "phi", "store"), ((0, 1, 0), (1, 1, 1), (1, 2, 0)), (0.0,) * 3)


def test_reference_accumulates_a_recurrence_in_f32():
    x = np.array([[0.1, 2.0], [0.2, 3.0], [0.3, -5.0]], np.float32)
    outs, bad = reference.interpret(_accumulator(), {0: x}, 3)
    want = np.cumsum(x, axis=0, dtype=np.float32)
    assert bad == 0 and np.array_equal(outs[2], want)


def test_reference_operand_order_and_bitwise_ops():
    # store(sub(in0, in1)) and store(shl(in0, in1)): operands by source node
    g = Graph("ops", ("input", "input", "sub", "shl", "store", "store"),
              ((1, 2, 0), (0, 2, 0), (0, 3, 0), (1, 3, 0), (2, 4, 0), (3, 5, 0)), (0.0,) * 6)
    outs, _ = reference.interpret(g, {0: np.float32([[7.9]]), 1: np.float32([[2.2]])}, 1)
    assert outs[4][0, 0] == np.float32(7.9) - np.float32(2.2)
    assert outs[5][0, 0] == (7 << 2) & 0xFFFF


def test_reference_counts_values_outside_the_guarantee():
    g = Graph("sq", ("input", "mul", "store"), ((0, 1, 0), (0, 1, 0), (1, 2, 0)), (0.0,) * 3)
    _, bad = reference.interpret(g, {0: np.float32([[1e20, 1.0]])}, 1)
    assert bad == 3        # 1e20 (input), 1e40 -> inf (mul), inf (store)


def test_bf16_control_differs():
    g = table3_graph("fft", *TABLE3["fft"])
    rng = np.random.default_rng(3)
    inputs = {v: rng.random((16, 256), dtype=np.float32) * 8 - 4 for v in g.nodes_of("input")}
    f32, _ = reference.interpret(g, inputs, 16)
    bf16, _ = reference.interpret(g, inputs, 16, bf16=True)
    assert sum(reference.mismatches(bf16[v], f32[v]) for v in f32) > 0


def test_check_mapping_accepts_a_valid_mapping_and_names_each_fault():
    g = _accumulator()                 # RecII 1
    ok = dict(ii=1, t_abs=[0, 1, 2], placement=[0, 1, 2])   # a row of a 4x4 mesh
    assert reference.check_mapping(g, 4, 4, **ok) == []
    far = reference.check_mapping(g, 4, 4, ii=1, t_abs=[0, 1, 2], placement=[0, 5, 2])
    assert any("not neighbours" in e for e in far)
    early = reference.check_mapping(g, 4, 4, ii=1, t_abs=[1, 1, 2], placement=[0, 1, 2])
    assert any("read before" in e for e in early)
    shared = reference.check_mapping(g, 4, 4, ii=2, t_abs=[0, 2, 3], placement=[0, 0, 1])
    assert any("share PE" in e for e in shared)
    assert reference.check_mapping(g, 4, 4, ii=1, t_abs=[0, 1], placement=[0, 1])
