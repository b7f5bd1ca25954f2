"""Mean milliseconds a call spends bringing the kernel's whole trace back
to the host (the host's layout change included): the program's
``cgra_run.to_host`` span."""

import program_spans


def read(run):
    return program_spans.mean_ms(run, "cgra_run.to_host")
