"""Route-through mapping (DESIGN.md §12) + per-PE register-pressure guarantee.

Covers the two PR-5 fixes end to end:

* multi-hop fabrics: a kernel whose producer/consumer banks are never
  adjacent (``onehop_split_4x4``) is unmappable under direct adjacency at
  every II, maps with ``max_route_hops <= 2``, and the routed mapping passes
  every independent validator and executes bit-identically to the *original*
  DFG's reference interpretation (movs are identity ops);
* the ``max_register_pressure`` guarantee is per-PE
  (``min(max_rp, registers_at(pe))``): a mapping whose scalar pressure fold
  passes but oversubscribes a smaller per-class file is rejected — including
  when it arrives through either mapping-cache layer (CACHE_VERSION 3).
"""

import numpy as np
import pytest

from repro.api import Compiler, CompileResult, resolve_options
from repro.core import CGRA, get_preset, map_dfg, splice_routes
from repro.core.benchsuite import route_stress_dfg
from repro.core.dfg import DFG, Edge
from repro.core.mapper import (
    Mapping,
    _cache_base_key,
    _cache_put,
    _pressure_offenders,
    clear_mapping_cache,
)
from repro.core.mono import check_monomorphism, check_routes
from repro.core.service.batch import JobReport
from repro.core.service.cache import CACHE_VERSION, DiskMappingCache
from repro.core.simulate import (
    check_equivalence,
    check_register_pressure,
    execute_mapping,
    interpret_dfg,
    register_pressure_by_pe,
)


@pytest.fixture(autouse=True)
def _fresh_caches(monkeypatch):
    monkeypatch.delenv("REPRO_CACHE_DIR", raising=False)
    clear_mapping_cache()
    yield
    clear_mapping_cache()


# ------------------------------------------------------------- reach masks

def test_reach_masks_extend_closed_masks():
    cgra = CGRA(4, 4)
    assert cgra.reach_masks(1) == cgra.closed_masks
    r2 = cgra.reach_masks(2)
    for pe in range(cgra.num_pes):
        assert r2[pe] & cgra.closed_masks[pe] == cgra.closed_masks[pe]
    # corner of a 4x4 mesh: 3 closed, 6 within 2 hops, full grid within 6
    assert cgra.closed_masks[0].bit_count() == 3
    assert r2[0].bit_count() == 6
    assert cgra.reach_masks(6)[0] == (1 << 16) - 1
    assert cgra.reach_degree(2) > cgra.connectivity_degree


def test_onehop_split_banks_never_adjacent():
    cgra = get_preset("onehop_split_4x4").cgra()
    mem = cgra.capability_masks["mem"]
    mul = cgra.capability_masks["mul"]
    for pe in range(cgra.num_pes):
        if (mem >> pe) & 1:
            assert cgra.closed_masks[pe] & mul == 0      # direct: impossible
            assert cgra.reach_masks(2)[pe] & mul != 0    # one mov: bridged


# ------------------------------------------------------------ DFG rewrite

def test_splice_routes_preserves_noncommutative_operand_order():
    dfg = DFG(num_nodes=4, ops=["input", "input", "sub", "store"],
              edges=[Edge(0, 2), Edge(1, 2), Edge(2, 3)], name="subtract")
    routed, routes = splice_routes(dfg, [(0, 2, 0, 1)])
    assert routed.num_nodes == 5 and routed.ops[4] == "mov"
    assert routes[0].movs == (4,)
    # the mov (id 4) replaces operand 0 of the sub; without port pinning it
    # would sort after input 1 and flip the subtraction
    inputs = {0: [5.0, 7.0], 1: [2.0, 3.0]}
    assert interpret_dfg(dfg, inputs, 2)[3] == [3.0, 4.0]
    assert interpret_dfg(routed, inputs, 2)[3] == [3.0, 4.0]
    # port-pinned edges survive the JSON round-trip
    again = DFG.from_json(routed.to_json())
    assert interpret_dfg(again, inputs, 2)[3] == [3.0, 4.0]
    assert again.stable_hash() == routed.stable_hash()


def test_splice_routes_rejects_unknown_edge():
    with pytest.raises(ValueError, match="no unrouted edge"):
        splice_routes(route_stress_dfg(), [(0, 4, 0, 1)])


# ----------------------------------------------------- route-through mapping

def test_route_kernel_unmappable_direct():
    cgra = get_preset("onehop_split_4x4").cgra()
    res = map_dfg(route_stress_dfg(), cgra, deterministic=True, max_ii=4)
    assert not res.ok


def test_route_through_maps_validates_and_executes():
    dfg = route_stress_dfg()
    cgra = get_preset("onehop_split_4x4").cgra()
    res = map_dfg(dfg, cgra, deterministic=True, max_route_hops=2, max_ii=6)
    assert res.ok, res.reason
    m = res.mapping
    assert m.routes and m.num_route_movs >= 2
    assert all(m.dfg.ops[v] == "mov" for r in m.routes for v in r.movs)
    # original node ids survive the rewrite
    assert list(m.original_nodes) == list(dfg.nodes)
    assert len(m.original_placement()) == dfg.num_nodes
    # every independent validator: monomorphism, routes, full validate
    assert check_monomorphism(m.dfg, cgra, m.labels, m.placement, m.ii) == []
    assert check_routes(m.dfg, cgra, m.t_abs, m.placement, m.ii, m.routes) == []
    assert m.validate(connectivity="strict") == []
    # the routed mapping computes the ORIGINAL kernel (movs are identity)
    check_equivalence(m)
    inputs = {0: [float(i) for i in range(6)]}
    ref = interpret_dfg(dfg, inputs, 6)
    rep = execute_mapping(m, inputs, 6)
    for v, stream in ref.items():
        assert rep.outputs[v][: len(stream)] == stream


def test_carried_edge_routes_with_distance_preserved():
    """A loop-carried cross-bank edge splices as src→mov (intra) + mov→dst
    (carrying the original distance) and still executes the original
    recurrence."""
    dfg = DFG(num_nodes=5, ops=["input", "load", "const", "mul", "store"],
              edges=[Edge(0, 1), Edge(1, 3, distance=1), Edge(2, 3),
                     Edge(3, 4)],
              name="carried_route")
    cgra = get_preset("onehop_split_4x4").cgra()
    res = map_dfg(dfg, cgra, deterministic=True, max_route_hops=2, max_ii=8)
    assert res.ok, res.reason
    m = res.mapping
    assert (1, 3, 1, 1) in m.routes_spec()     # the carried edge was routed
    assert m.validate() == []
    inputs = {0: [float(i + 1) for i in range(6)]}
    ref = interpret_dfg(dfg, inputs, 6)
    rep = execute_mapping(m, inputs, 6)
    for v, stream in ref.items():
        assert rep.outputs[v][: len(stream)] == stream


def test_route_escalation_is_deterministic():
    dfg = route_stress_dfg()
    cgra = get_preset("onehop_split_4x4").cgra()
    a = map_dfg(dfg, cgra, deterministic=True, max_route_hops=2, max_ii=6)
    b = map_dfg(dfg, cgra, deterministic=True, max_route_hops=2, max_ii=6)
    assert a.ok and b.ok
    assert a.mapping.t_abs == b.mapping.t_abs
    assert a.mapping.placement == b.mapping.placement
    assert a.mapping.routes_spec() == b.mapping.routes_spec()


def test_direct_embeddings_still_preferred_with_hops_allowed():
    """Escalation order: a kernel that embeds directly spends zero movs even
    when route-through is allowed."""
    from repro.core import running_example

    res = map_dfg(running_example(), CGRA(2, 2), deterministic=True,
                  max_route_hops=2)
    assert res.ok and res.mapping.routes == [] and res.mapping.ii == 4


def test_routed_mapping_through_pallas_program():
    """The cgra_sim program builder consumes routed mappings unchanged: the
    rewritten DFG is an ordinary DFG whose movs occupy real (PE, step) slots."""
    from repro.kernels.ops import cgra_run, compile_program

    dfg = route_stress_dfg()
    cgra = get_preset("onehop_split_4x4").cgra()
    res = map_dfg(dfg, cgra, deterministic=True, max_route_hops=2, max_ii=6)
    assert res.ok, res.reason
    prog = compile_program(res.mapping)
    num_iters, batch = 5, 8
    rng = np.random.default_rng(0)
    inputs = {0: rng.uniform(-4, 4, (num_iters, batch)).astype(np.float32).round(2)}
    outs, _trace = cgra_run(prog, inputs, num_iters, batch_tile=batch,
                            interpret=True)
    ref = interpret_dfg(
        dfg, {0: [float(x) for x in inputs[0][:, 0]]}, num_iters
    )
    for v, stream in ref.items():
        np.testing.assert_allclose(
            outs[v][:, 0], np.asarray(stream, np.float32), rtol=1e-6, atol=1e-6
        )


def test_batch_path_reconstructs_routed_mapping():
    comp = Compiler(
        "onehop_split_4x4",
        resolve_options("deterministic-ci", max_route_hops=2, max_ii=6),
    )
    batch = comp.compile_batch([route_stress_dfg()])
    assert batch.ok
    row = batch.results[0]
    assert row.mapping is not None and row.mapping.routes
    assert row.route_movs == row.mapping.num_route_movs >= 2
    assert row.as_dict()["route_movs"] == row.route_movs
    check_equivalence(row.mapping)


# ------------------------------------------- per-PE register-pressure fixes

#: A 12-node ring on the satmapit 4x4: node 0 on interior PE 5 produces a
#: value consumed 11 cycles later (node 11, adjacent to PE 5), so PE 5 holds
#: ~12 live values at II=1 — above the interior file (8), below the scalar
#: mem-file bound (16) the old scalar fold checked against.
_RING_PES = [5, 6, 7, 3, 2, 1, 0, 4, 8, 12, 13, 9]


def _ring_dfg() -> DFG:
    edges = [Edge(i, i + 1) for i in range(11)] + [Edge(0, 11)]
    return DFG(num_nodes=12, ops=["input"] + ["add"] * 11, edges=edges,
               name="pressure_ring")


def _poisoned_mapping(cgra) -> Mapping:
    return Mapping(dfg=_ring_dfg(), cgra=cgra, ii=1,
                   t_abs=list(range(12)), placement=list(_RING_PES))


def test_scalar_fold_passes_but_per_pe_bound_catches():
    cgra = get_preset("satmapit_edge_mem_4x4").cgra()
    m = _poisoned_mapping(cgra)
    by_pe = register_pressure_by_pe(m)
    assert by_pe[5] > cgra.registers_at(5)            # interior file (8) blown
    assert check_register_pressure(m) <= 16           # old scalar check passes
    assert _pressure_offenders(m, 16) == [5]
    assert any("register pressure" in e for e in m.validate())


def test_map_dfg_guarantee_is_per_pe():
    cgra = get_preset("satmapit_edge_mem_4x4").cgra()
    res = map_dfg(_ring_dfg(), cgra, deterministic=True,
                  max_register_pressure=16)
    assert res.ok, res.reason
    for pe, p in register_pressure_by_pe(res.mapping).items():
        assert p <= min(16, cgra.registers_at(pe)), (pe, p)
    assert res.mapping.validate() == []


def test_memory_cache_cannot_serve_oversubscribing_mapping():
    cgra = get_preset("satmapit_edge_mem_4x4").cgra()
    dfg = _ring_dfg()
    base_key = _cache_base_key(dfg, cgra, "strict", 16)
    _cache_put(base_key, _poisoned_mapping(cgra))
    res = map_dfg(dfg, cgra, max_register_pressure=16, time_budget_s=60)
    assert res.ok, res.reason
    assert not res.stats.cache_hit                    # poisoned entry dropped
    for pe, p in register_pressure_by_pe(res.mapping).items():
        assert p <= min(16, cgra.registers_at(pe))


def test_disk_cache_cannot_serve_oversubscribing_mapping(tmp_path):
    cgra = get_preset("satmapit_edge_mem_4x4").cgra()
    dfg = _ring_dfg()
    base_key = _cache_base_key(dfg, cgra, "strict", 16)
    poisoned = _poisoned_mapping(cgra)
    store = DiskMappingCache(str(tmp_path))
    store.put(base_key, 1, poisoned.t_abs, poisoned.placement)
    res = map_dfg(dfg, cgra, max_register_pressure=16, time_budget_s=60,
                  cache_dir=str(tmp_path))
    assert res.ok, res.reason
    assert not res.stats.disk_cache_hit
    for pe, p in register_pressure_by_pe(res.mapping).items():
        assert p <= min(16, cgra.registers_at(pe))


def test_cache_key_tracks_register_sizing():
    """Two same-shape grids with different register files must not alias under
    a pressure guarantee (they admit different mappings) — and must still
    share entries when no guarantee is requested (sizing can't matter then)."""
    dfg = _ring_dfg()
    small = CGRA(4, 4, registers_per_pe=4)
    big = CGRA(4, 4, registers_per_pe=16)
    assert (_cache_base_key(dfg, small, "strict", 12)
            != _cache_base_key(dfg, big, "strict", 12))
    assert (_cache_base_key(dfg, small, "strict", None)
            == _cache_base_key(dfg, big, "strict", None))
    # the route-hops allowance is keyed too: routed mappings carry movs a
    # direct-only caller cannot accept
    assert (_cache_base_key(dfg, big, "strict", None, 2)
            != _cache_base_key(dfg, big, "strict", None))


def test_cache_version_bumped_and_orphans_pre_fix_entries(tmp_path, monkeypatch):
    assert CACHE_VERSION >= 3     # per-PE pressure token + routes schema
    import repro.core.service.cache as cache_mod

    store = DiskMappingCache(str(tmp_path))
    key = store.entry_key("abc", 4, 4, "mesh", "strict", 16)
    monkeypatch.setattr(cache_mod, "CACHE_VERSION", CACHE_VERSION - 1)
    store.put(key, 2, [0, 1], [0, 1])                 # a "pre-fix" entry
    monkeypatch.setattr(cache_mod, "CACHE_VERSION", CACHE_VERSION)
    assert store.get(key, 1, 4) is None               # orphaned, never served
    assert store.prune() == 1


def test_batch_reconstruction_rejects_oversubscribing_worker_row():
    cgra = get_preset("satmapit_edge_mem_4x4").cgra()
    dfg = _ring_dfg()
    job = JobReport(name="ring", ok=True, ii=1, m_ii=1, wall_s=0.1,
                    t_abs=list(range(12)), placement=list(_RING_PES))
    # same per-PE bounds as the direct path: the row is flipped to a failure
    row = CompileResult.from_job_report(job, dfg, cgra,
                                        max_register_pressure=16)
    assert not row.ok and row.failure == "error" and row.mapping is None
    assert "PE 5" in row.reason
    # without a pressure guarantee the (structurally valid) row stays ok
    row2 = CompileResult.from_job_report(job, dfg, cgra)
    assert row2.ok and row2.mapping is not None
