"""Mean milliseconds a call spends putting the program tables and its
streams on the device and waiting for them (the host's layout change
included): the program's ``cgra_run.to_device`` span."""

import program_spans


def read(run):
    return program_spans.mean_ms(run, "cgra_run.to_device")
