"""Share of the traced window, in %, in which the device runs nothing and
no ``cgra_run`` call of the program is open: the harness's own time between
calls (drawing inputs, its bookkeeping). No ``cgra_run`` span in the
window, no value."""

import program_spans
import trace


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    lo, hi = t.window
    program = program_spans.in_window(t, program_spans.PROGRAM)
    if not program:
        return None
    device = [(max(s, lo), min(e, hi)) for ops in t.device_ops.values()
              for _, s, e in ops if e > lo and s < hi]
    covered = trace.union_length([(s, min(e, hi)) for s, e in program] + device)
    return 100.0 * (1.0 - covered / (hi - lo))
