"""Pallas TPU kernels.

cgra_sim.py          batched execution of mapped CGRA programs (the paper's
                     compute substrate as a TPU kernel: crossbar -> one-hot
                     MXU matmuls, register files -> VMEM ring buffer)
flash_attention.py   fused attention (causal/sliding-window/softcap/GQA) —
                     the TPU hot path behind the model zoo's blocked-attention
                     jnp fallback

ops.py               program compilation + jit'd wrappers
ref.py               pure-jnp / numpy oracles (kernels assert against these)

The kernels run compiled for the TPU by default. The tests force the CPU
(JAX_PLATFORMS=cpu) and pass interpret=True; chip_smoke.py at the repo root
runs the compiled cgra_sim path on a chip.
"""
