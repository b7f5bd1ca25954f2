"""Mean milliseconds per served request spent lowering the mapping to a
device program (``compile_program``), timed by the harness around the call."""


def read(run):
    served = [u["lower_s"] for u in run.units if u.get("ok")]
    return 1e3 * sum(served) / len(served) if served else None
