"""Differential fuzz harness: portfolio vs exact joint backend (DESIGN.md §14.5).

This is a deterministic fuzz suite, not a ``hypothesis`` one:
:func:`repro.core.fuzz.random_dfg` turns a seed into a small valid DFG, and
every case id embeds the seed, fabric, and backend, so any failure is
replayable verbatim. The default budget is ``FUZZ_SEEDS`` seeds ×
6 (fabric, space-backend) configs ≥ 200 mapped cases; the nightly CI job
raises it via ``REPRO_FUZZ_CASES``.

Three oracles cross-check every accepted mapping:

* **Validity** — ``Mapping.validate()`` must be clean and the cycle-accurate
  executor must agree with the sequential interpreter
  (``check_equivalence``), on every fabric topology and space backend.
* **Joint parity** — the joint solver run *at the portfolio's achieved II*
  may never prove that II unsat: portfolio mappings are witnesses, so an
  unsat there is a soundness bug in one of the two independent encodings.
* **Certificate sanity** — certificates produced on portfolio mappings must
  re-verify (:func:`verify_certificate`), respect ``mII ≤ ii_opt ≤ ii``,
  and only claim ``optimal``/``better-found`` with full probe coverage.

Budgets are deliberately tiny (``det_space_cap=4000``) — differential
testing wants many shallow cases, not a few deep ones — and deterministic
mode keeps every mapper decision a pure function of the case tuple.
"""

from __future__ import annotations

import os

import pytest

from repro.core import CGRA, map_dfg, min_ii
from repro.core.exact_backends import (
    certify_mapping,
    solve_joint,
    verify_certificate,
)
from repro.core.fuzz import random_dfg
from repro.core.simulate import check_equivalence

# Seeds per (fabric, backend) config. 6 configs x 34 seeds = 204 mapped
# cases at the floor the harness promises; the nightly job scales it up.
FUZZ_SEEDS = max(34, int(os.environ.get("REPRO_FUZZ_CASES", "0")) // 6)
_CHUNK = 17  # seeds per test node: failures stay replayable, runtime ~2-8 s

_FABRICS = [
    pytest.param("mesh3x3", dict(rows=3, cols=3), id="mesh3x3"),
    pytest.param("torus4x4", dict(rows=4, cols=4, topology="torus"),
                 id="torus4x4"),
    pytest.param("onehop4x4", dict(rows=4, cols=4, topology="one-hop"),
                 id="onehop4x4"),
]
_BACKENDS = ["exact", "anneal"]
_CHUNKS = [
    (lo, min(lo + _CHUNK, FUZZ_SEEDS)) for lo in range(0, FUZZ_SEEDS, _CHUNK)
]

# Tight deterministic budgets: failures to embed under these caps simply
# yield ok=False rows (anneal is incomplete; that is not a violation).
_MAP_KW = dict(
    deterministic=True,
    use_cache=False,
    det_space_cap=4000,
    max_retries_per_window=1,
    max_slack=1,
)


def _compile(seed: int, fabric_kw: dict, backend: str):
    dfg = random_dfg(seed)
    cgra = CGRA(**fabric_kw)
    res = map_dfg(dfg, cgra, space_backend=backend, **_MAP_KW)
    return dfg, cgra, res


@pytest.mark.fuzz
@pytest.mark.parametrize("fabric_id,fabric_kw", _FABRICS)
@pytest.mark.parametrize("backend", _BACKENDS)
@pytest.mark.parametrize("lo,hi", _CHUNKS, ids=[f"seeds{lo}-{hi - 1}" for lo, hi in _CHUNKS])
def test_fuzz_valid_and_equivalent(fabric_id, fabric_kw, backend, lo, hi):
    """Every accepted mapping validates and executes correctly."""
    mapped = 0
    for seed in range(lo, hi):
        dfg, cgra, res = _compile(seed, fabric_kw, backend)
        if not res.ok:
            continue
        mapped += 1
        case = f"seed={seed} fabric={fabric_id} backend={backend}"
        problems = res.mapping.validate()
        assert problems == [], f"{case}: {problems}"
        check_equivalence(res.mapping)
        assert res.mapping.ii >= min_ii(dfg, cgra), (
            f"{case}: ii {res.mapping.ii} below the structural bound"
        )
    # tiny DFGs on 9-16 PE fabrics embed under these budgets in practice;
    # a collapse to zero would mean the harness stopped testing anything
    assert mapped > (hi - lo) // 2, f"only {mapped}/{hi - lo} cases mapped"


@pytest.mark.fuzz
@pytest.mark.parametrize("fabric_id,fabric_kw", _FABRICS)
@pytest.mark.parametrize("lo,hi", _CHUNKS, ids=[f"seeds{lo}-{hi - 1}" for lo, hi in _CHUNKS])
def test_fuzz_joint_never_refutes_a_witness(fabric_id, fabric_kw, lo, hi):
    """The joint encoding may never call a portfolio mapping's II unsat.

    The portfolio mapping *is* a satisfying assignment of the joint model,
    so ``unsat`` at that II contradicts it — whichever side is wrong, it is
    a real bug. ``unknown`` (budget) is acceptable and merely skipped.
    """
    for seed in range(lo, hi):
        dfg, cgra, res = _compile(seed, fabric_kw, "exact")
        if not res.ok or res.mapping.num_route_movs:
            continue
        out = solve_joint(dfg, cgra, res.mapping.ii, node_budget=200_000)
        assert out.status != "unsat", (
            f"seed={seed} fabric={fabric_id}: joint refuted II="
            f"{res.mapping.ii} but the portfolio holds a witness"
        )
        if out.status == "sat" and out.mapping is not None:
            assert out.mapping.validate() == [], f"seed={seed} joint witness invalid"
            check_equivalence(out.mapping)


@pytest.mark.fuzz
@pytest.mark.parametrize("lo,hi", _CHUNKS, ids=[f"seeds{lo}-{hi - 1}" for lo, hi in _CHUNKS])
def test_fuzz_certificates_verify(lo, hi):
    """Certificates on fuzz mappings re-verify and bound correctly."""
    for seed in range(lo, hi):
        dfg, cgra, res = _compile(seed, dict(rows=3, cols=3), "exact")
        if not res.ok:
            continue
        cert, better = certify_mapping(
            dfg, cgra, res.mapping, budget_s=3.0, deterministic=True
        )
        case = f"seed={seed} status={cert.status}"
        problems = verify_certificate(cert, dfg, cgra)
        assert problems == [], f"{case}: {problems}"
        assert cert.m_ii >= min_ii(dfg, cgra)
        if cert.ii_opt is not None:
            assert cert.m_ii <= cert.ii_opt <= res.mapping.ii, case
            final_ii = better.ii if better is not None else res.mapping.ii
            assert final_ii == cert.ii_opt, (
                f"{case}: final ii {final_ii} != certified optimum {cert.ii_opt}"
            )
        else:
            assert cert.status == "timeout", case
        if better is not None:
            assert better.validate() == []
            check_equivalence(better)
