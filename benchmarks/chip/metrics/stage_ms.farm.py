"""Mean milliseconds a call spends staging its streams on the host
(``build_injection``): the program's ``cgra_run.stage`` span."""

import program_spans


def read(run):
    return program_spans.mean_ms(run, "cgra_run.stage")
