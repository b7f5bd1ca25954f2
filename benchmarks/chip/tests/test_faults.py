"""The check catches a broken timed path: each fault below is planted under
the harness, the rest of a run goes as usual, and ``correct`` comes out
false. The cells run on one chip, so no exchange between chips can be left
out."""

import numpy as np
import pytest

import control
import sut
from test_rehearsal import TINY, rehearse  # noqa: F401  (fixture)


def _stale_execute(execute):
    """Every call of a program returns what its first call returned."""
    first = {}

    def run(program, inputs, iters):
        outs = execute(program, inputs, iters)
        return first.setdefault(id(program), outs)

    return run


def _half_batch_execute(execute):
    """Only the first half of the streams is computed; the rest reads 0."""

    def run(program, inputs, iters):
        streams = next(iter(inputs.values())).shape[1]
        half = {v: x[:, : streams // 2] for v, x in inputs.items()}
        outs = execute(program, half, iters)
        return {v: np.concatenate([o, np.zeros_like(o)], axis=1) for v, o in outs.items()}

    return run


def _altered_execute(execute):
    """One store value of each call is off by one."""

    def run(program, inputs, iters):
        outs = execute(program, inputs, iters)
        outs[next(iter(outs))][0, 0] += 1.0
        return outs

    return run


@pytest.mark.parametrize("fault", [_stale_execute, _half_batch_execute, _altered_execute])
@pytest.mark.parametrize("name", ["farm.mesh4x4", "farm.mesh20x20"])
def test_farm_fault_is_not_correct(rehearse, monkeypatch, name, fault):
    monkeypatch.setattr(sut, "execute", fault(sut.execute))
    result = rehearse(name)
    assert not result["correct"]
    assert result["checks"]["store_mismatches"]["value"] > 0


def _stale_compile(compile_loop):
    """Every request is answered with the previous request's mapping."""
    last = []

    def run(graph, rows, cols, profile):
        res = compile_loop(graph, rows, cols, profile)
        last.append(res)
        return last[-2] if len(last) > 1 else res

    return run


def _unanswered_compile(compile_loop):
    """Every second request of the window gets no mapping (set-up's are
    answered: set-up stops at the first it cannot map)."""
    count = []
    setup = len(TINY["recompile.mesh20x20"]["kernels"])

    def run(graph, rows, cols, profile):
        res = compile_loop(graph, rows, cols, profile)
        count.append(1)
        if len(count) > setup and len(count) % 2 == 0:
            res.ok, res.mapping, res.ii = False, None, None
        return res

    return run


def _moved_node_compile(compile_loop):
    """One node of each mapping sits on a PE that its operands cannot reach."""

    def run(graph, rows, cols, profile):
        res = compile_loop(graph, rows, cols, profile)
        m = res.mapping
        v = next(v for v in range(len(m.placement)) if graph.ops[v] != "input")
        m.placement[v] = (m.placement[v] + cols * (rows // 2) + cols // 2) % (rows * cols)
        return res

    return run


@pytest.mark.parametrize("fault", [_stale_compile, _unanswered_compile, _moved_node_compile])
def test_recompile_fault_is_not_correct(rehearse, monkeypatch, fault):
    monkeypatch.setattr(sut, "compile_loop", fault(sut.compile_loop))
    result = rehearse("recompile.mesh20x20", executed_sample=3)
    assert not result["correct"]


def test_recompile_altered_program_is_not_correct(rehearse, monkeypatch):
    """A lowered program whose first opcode is swapped computes wrong stores."""
    lower = sut.lower

    def altered(mapping):
        program = lower(mapping)
        k, pe = np.argwhere(program.op_id >= 4)[0]        # first computing op
        op = int(program.op_id[k, pe])
        swap = 5 if op == 4 else 4                         # add <-> sub
        program.op_sel[k, pe, op], program.op_sel[k, pe, swap] = 0.0, 1.0
        program.op_id[k, pe] = swap
        return program

    monkeypatch.setattr(sut, "lower", altered)
    result = rehearse("recompile.mesh20x20", executed_sample=3)
    assert not result["correct"]
    assert result["checks"]["store_mismatches"]["value"] > 0


@pytest.mark.parametrize("name", ["farm.mesh4x4", "farm.mesh20x20", "recompile.mesh20x20"])
def test_bf16_control_is_not_correct(rehearse, name):
    """The reference in bfloat16, put in the program's place, fails the check."""
    import run

    _, config, _, _ = run.load_cell(name)
    control.put_in_place(config)
    result = rehearse(name)
    assert not result["correct"]
    assert result["checks"]["store_mismatches"]["value"] > 0
