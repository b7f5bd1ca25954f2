"""Shared fixtures."""

import contextlib
import glob
import os
import warnings

import pytest


@pytest.fixture
def profiled(tmp_path):
    """``with profiled() as events:`` collects a JAX profiler trace of the
    block; on exit ``events`` holds the host plane's events as
    ``(name, start_ns, end_ns, stats)`` in start order."""

    @contextlib.contextmanager
    def collect():
        import jax
        from jax.profiler import ProfileData

        events: list = []
        jax.profiler.start_trace(str(tmp_path))
        try:
            yield events
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                            recursive=True)
        with warnings.catch_warnings():   # the stats type warns on use
            warnings.simplefilter("ignore", DeprecationWarning)
            for plane in ProfileData.from_file(path).planes:
                if plane.name == "/host:CPU":
                    for line in plane.lines:
                        events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                                    dict(e.stats)) for e in line.events]
        events.sort(key=lambda e: (e[1], -e[2]))   # a parent before its child

    return collect
