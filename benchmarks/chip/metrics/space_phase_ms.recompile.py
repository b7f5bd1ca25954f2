"""Mean space-phase milliseconds per served request of the window, from the
compiler's own phase timing (``CompileResult.phases.space_s``)."""


def read(run):
    served = [u["space_s"] for u in run.units if u.get("ok")]
    return 1e3 * sum(served) / len(served) if served else None
