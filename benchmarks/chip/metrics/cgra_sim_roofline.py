"""cgra_sim's share of its roofline in the traced window, in %.

The least time is the larger of the algorithm's bytes over the chip's HBM
bandwidth and its node firings over the chip's peak (``peaks.json``), summed
over every call of the window (``cost.py``: each input value read and each
store value written per stream-iteration, plus the program's encoding). The
bytes bound it by far: a firing is one float32 operation per stream, and the
published peak is bf16 matrix work, so that term is a loose lower bound of
no weight. The kernel's time is the summed device time of the ``cgra_sim``
operations in the trace. No kernel time, no value.
"""

import cost


def read(run):
    kernel_s = run.trace.kernel_s("cgra_sim") if run.trace else 0.0
    if kernel_s <= 0:
        return None
    nbytes = ops = 0
    for u in run.units:
        graph = run.graphs[u["kernel"]]
        nbytes += cost.call_bytes(graph, u["streams"], u["iterations"])
        ops += cost.call_ops(graph, u["streams"], u["iterations"])
    least = max(nbytes / run.peaks["hbm_bytes_per_s"], ops / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / kernel_s
