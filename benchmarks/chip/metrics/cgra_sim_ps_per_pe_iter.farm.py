"""Picoseconds of ``cgra_sim`` device time per PE, stream and iteration in
the traced window: the kernel's summed device time over the sum, across the
window's calls, of the fabric's PEs x streams x iterations. The kernel
simulates the whole fabric every cycle, so this shows whether its cost per
PE stays flat as the fabric grows. No kernel time, no value."""


def read(run):
    kernel_s = run.trace.kernel_s("cgra_sim") if run.trace else 0.0
    if kernel_s <= 0:
        return None
    pes = run.config["fabric"]["pes"]
    work = sum(pes * u["streams"] * u["iterations"] for u in run.units)
    return 1e12 * kernel_s / work if work else None
