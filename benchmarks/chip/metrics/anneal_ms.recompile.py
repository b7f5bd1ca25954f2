"""Mean milliseconds per served request of the window spent in the annealing
space engine: the summed duration of the program's ``space.anneal`` spans
(one per placement attempt) that start in the window, over the served
requests. A program without the span, or a fabric the exact engine places,
gives no value."""

import program_spans


def read(run):
    if run.trace is None:
        return None
    spans = program_spans.in_window(run.trace, "space.anneal")
    served = sum(1 for u in run.units if u.get("ok"))
    if not spans or not served:
        return None
    return sum(e - s for s, e in spans) / served / 1e6
