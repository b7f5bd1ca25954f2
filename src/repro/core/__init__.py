"""Core library: monomorphism-based CGRA mapping via space/time decoupling.

The paper's contribution lives here: schedule.py (ASAP/ALAP/MobS/KMS/mII),
time_smt.py (SMT time solution), space_backends/ (pluggable space solution:
exact bitset monomorphism + annealing/clustered placement, DESIGN.md §13),
mapper.py (the decoupled pipeline), baseline.py (joint SAT-MapIt-style
comparison target), benchsuite.py (Table III DFG suite), opcodes.py (the op
table: each opcode's code, arity, capability class and semantics),
simulate.py (functional validation), arch/ (declarative heterogeneous
architecture specs: capability classes, topology families, memory ports —
DESIGN.md §10).
"""

from .arch import ArchSpec, get_preset, list_presets, resolve_arch
from .cgra import CAP_CLASSES, CGRA, MRRG, op_class
from .dfg import DFG, Edge, Route, running_example, splice_routes
from .mapper import Mapping, MapResult, map_dfg
from .mono import check_monomorphism, check_routes, find_monomorphism
from .schedule import (
    KMS,
    MobilitySchedule,
    alap_schedule,
    asap_schedule,
    min_ii,
    mobility_schedule,
    rec_ii,
    res_ii,
)
from .space_backends import (
    SpaceBudget,
    available_space_backends,
    resolve_space_backend,
)
from .time_smt import (
    TimeSolution,
    TimeSolver,
    available_backends,
    check_time_solution,
)

__all__ = [
    "ArchSpec", "get_preset", "list_presets", "resolve_arch",
    "CAP_CLASSES", "op_class",
    "CGRA", "MRRG", "DFG", "Edge", "Route", "running_example", "splice_routes",
    "Mapping", "MapResult", "map_dfg",
    "check_monomorphism", "check_routes", "find_monomorphism",
    "SpaceBudget", "available_space_backends", "resolve_space_backend",
    "KMS", "MobilitySchedule", "alap_schedule", "asap_schedule",
    "min_ii", "mobility_schedule", "rec_ii", "res_ii",
    "TimeSolution", "TimeSolver", "check_time_solution", "available_backends",
]
