"""repro: monomorphism-based CGRA mapping (space/time decoupled), whose
mapped loops execute on a TPU.

Subpackages
-----------
api        the stable public compiler surface: Compiler sessions, typed
           CompileOptions profiles, structured CompileResult (DESIGN.md §11)
core       the paper's mapping algorithm (SMT time + monomorphism space),
           the op table and the host executors
kernels    the cgra_sim Pallas TPU kernel that executes a mapped loop over
           many streams, and its lowering
obs        spans and counters, exported to Perfetto and the JAX profiler

Entry points: ``python -m repro.compile`` (batch compiles) and
``python -m repro.daemon`` (the resident compile service).
"""

__version__ = "1.1.0"

# the api surface is re-exported lazily so `import repro` stays light
_API_EXPORTS = (
    "Compiler", "CompileOptions", "CompileResult", "BatchResult",
    "PROFILES", "resolve_options",
)


def __getattr__(name):
    if name in _API_EXPORTS:
        from repro import api

        return getattr(api, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
