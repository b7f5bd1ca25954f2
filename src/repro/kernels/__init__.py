"""The Pallas TPU kernel that executes mapped CGRA loops.

cgra_sim.py          batched execution of mapped CGRA programs (the paper's
                     compute substrate as a TPU kernel: crossbar -> neighbour
                     shifts and VPU selects, register files -> VMEM ring buffer)
ops.py               program compilation + jit'd wrappers
ref.py               the numpy oracle the kernel's trace is held to

The kernels run compiled for the TPU by default. The tests force the CPU
(JAX_PLATFORMS=cpu) and pass interpret=True; chip_smoke.py at the repo root
runs the compiled cgra_sim path on a chip.
"""
