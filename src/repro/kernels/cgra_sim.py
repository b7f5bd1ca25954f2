"""Pallas TPU kernel: batched functional execution of a mapped CGRA program.

Executes the steady-state modulo schedule produced by the paper's mapper on a
PE grid, vectorised over a batch of independent loop instances (the common
CGRA deployment: the same accelerated loop applied to many data streams).

Hardware adaptation (CGRA -> TPU), per DESIGN.md §3:

  * the PE grid's crossbar/neighbour reads become **one-hot routing matmuls**
    on the MXU: operand_a = route_a[k] @ ring_state — a gather expressed as a
    dense matmul, the TPU-idiomatic form;
  * the per-PE ALU opcode select becomes a **one-hot select** on the VPU:
    one ``where`` per opcode picks op(a, b) — no data-dependent control flow;
  * PE register files become a **ring buffer in VMEM scratch**, rolled one
    slot per cycle so operand addresses are static per kernel step;
  * the cycle loop is the sequential grid dimension; the batch is tiled to
    128-lane blocks.

VMEM: ``vmem_footprint`` is what one grid step holds (one kernel step's
tables, double-buffered, not all II of them) and is passed as the kernel's
``vmem_limit_bytes``; on a TPU a program that needs more than the chip's
VMEM is refused before compiling.

Exactness: the routing matmuls run at fp32 contract precision, so a one-hot
row copies its operand exactly, and the select copies one candidate. Every
ALU op (incl. 16-bit-masked bitwise) yields an f32-representable value, so
the trace is compared with the reference for equality. This holds while
values stay finite and below 2**31 in magnitude: one inf in the ring turns
every routed read into NaN (0 * inf), and f32 -> int32 saturates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Fixed opcode ordering shared with core.simulate.OPCODES (asserted in ops.py).
KERNEL_OPS = (
    "input", "const", "load", "store", "add", "sub", "mul", "div",
    "and", "or", "xor", "shl", "shr", "min", "max", "neg", "not",
    "abs", "mov", "phi", "cmp",
)
NOPS = len(KERNEL_OPS)


def _residual(a: jax.Array, b: jax.Array, q: jax.Array) -> jax.Array:
    """``a - q*b`` without the rounding of ``q*b``, for ``q`` within a few ulp
    of ``a/b``: Dekker's two-product (Veltkamp split, no FMA needed) gives the
    product's exact error, and ``a - q*b`` is then exact by Sterbenz."""

    def split(x):
        t = x * 4097.0                  # 2**12 + 1: 12-bit halves of a float32
        hi = t - (t - x)
        return hi, x - hi

    p = q * b
    qh, ql = split(q)
    bh, bl = split(b)
    err = ((qh * bh - p) + qh * bl + ql * bh) + ql * bl    # q*b == p + err
    return (a - p) - err


def _round_quotient(a: jax.Array, b: jax.Array, q: jax.Array) -> jax.Array:
    """``a / b`` rounded to nearest, as IEEE division is, from a quotient
    ``q`` that is a few ulp off; ``b`` is nonzero.

    A TPU divides through an approximate reciprocal: some quotients come out
    1-2 ulp off. One correction step makes ``q`` faithful (one of the two
    floats around ``a/b``); the exact residuals then pick the nearer of it and
    its neighbour towards ``a/b`` (an exact tie cannot occur). Exact while
    operands and quotient are normal floats below about 2**115.
    """
    q = q + _residual(a, b, q) / b
    r = _residual(a, b, q)
    # one ulp away from zero where a/b lies beyond q, else one towards zero
    outward = ((r > 0) == (b > 0)) == (q > 0)
    bits = jax.lax.bitcast_convert_type(q, jnp.int32)
    q2 = jax.lax.bitcast_convert_type(
        bits + jnp.where(outward, 1, -1), jnp.float32
    )
    return jnp.where(jnp.abs(_residual(a, b, q2)) < jnp.abs(r), q2, q)


def _alu_all(
    a: jax.Array, b: jax.Array, imm: jax.Array, inj: jax.Array
) -> list[jax.Array]:
    """All candidate op results, one [pes, bt] array per opcode (f32-exact)."""
    ia = jnp.abs(a).astype(jnp.int32) & 0xFFFF
    ib = jnp.abs(b).astype(jnp.int32) & 0xFFFF
    sh = ib % 8
    safe_b = jnp.where(b != 0, b, 1.0)
    f = jnp.float32
    return [
        inj,                                        # input
        jnp.broadcast_to(imm, a.shape),             # const
        a,                                          # load
        a,                                          # store
        a + b,                                      # add
        a - b,                                      # sub
        a * b,                                      # mul
        jnp.where(b != 0, _round_quotient(a, safe_b, a / safe_b), 0.0),  # div
        (ia & ib).astype(f),                        # and
        (ia | ib).astype(f),                        # or
        (ia ^ ib).astype(f),                        # xor
        ((ia << sh) & 0xFFFF).astype(f),            # shl
        (ia >> sh).astype(f),                       # shr
        jnp.minimum(a, b),                          # min
        jnp.maximum(a, b),                          # max
        -a,                                         # neg
        (~ia & 0xFFFF).astype(f),                   # not
        jnp.abs(a),                                 # abs
        a,                                          # mov
        a + b,                                      # phi (carried accumulate)
        (a > b).astype(f),                          # cmp
    ]


def _cgra_sim_kernel(
    # inputs (blocked)
    route_a_ref,   # [1, pes, ring*pes]   routing one-hot for step k=c%II (op a)
    route_b_ref,   # [1, pes, ring*pes]
    op_sel_ref,    # [1, pes, NOPS]       opcode one-hot for step k
    imm_ref,       # [1, 1, pes]          immediates for step k
    inj_ref,       # [1, pes, bt]         input-node injections for cycle c
    active_ref,    # [1, 1, pes]          1.0 where a node fires at cycle c
    # outputs
    trace_ref,     # [1, pes, bt]         value produced at (c, pe)
    # scratch
    ring_ref,      # [ring, pes, bt]      register-file ring buffer
):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        ring_ref[...] = jnp.zeros_like(ring_ref)

    ring, pes, bt = ring_ref.shape
    state = ring_ref[...].reshape(ring * pes, bt)

    # crossbar: one-hot routing matmuls (MXU)
    hi = jax.lax.Precision.HIGHEST
    a = jnp.dot(route_a_ref[0], state, precision=hi, preferred_element_type=jnp.float32)
    b = jnp.dot(route_b_ref[0], state, precision=hi, preferred_element_type=jnp.float32)

    imm = imm_ref[0].T                             # [pes, 1]
    inj = inj_ref[0]
    sel = op_sel_ref[0]                            # [pes, NOPS]
    # opcode select: one VPU select per opcode, so the result is the chosen
    # candidate bit for bit and an inf among the others cannot leak into it
    val = jnp.zeros_like(a)
    for op, cand in enumerate(_alu_all(a, b, imm, inj)):
        val = jnp.where(sel[:, op:op + 1] > 0, cand, val)
    val = jnp.where(active_ref[0].T > 0, val, 0.0)

    # roll the register ring by one cycle; newest value enters slot 0
    if ring > 1:  # static: ring==1 means every operand is consumed next cycle
        shifted = ring_ref[: ring - 1]
        ring_ref[1:] = shifted
    ring_ref[0] = val
    trace_ref[0] = val


def _tile_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of one f32 [rows, cols] slab, padded to the (8, 128) tile."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * 4


def vmem_footprint(pes: int, ring: int, batch_tile: int) -> int:
    """VMEM bytes one grid step of the kernel needs; its ``vmem_limit_bytes``.

    Every pipelined block is double-buffered: the two routing blocks
    ``[pes, ring*pes]``, the opcode block ``[pes, NOPS]``, the imm and active
    rows ``[1, pes]`` and the injection and trace blocks ``[pes, bt]``. The
    ring scratch ``[ring, pes, bt]`` comes once. The body's values take the
    rest: the NOPS candidate results plus a few operand views, each
    ``[pes, bt]``, and up to seven ring-sized working copies of the state for
    the fp32-precision routing matmuls (reshaped, split into bf16 pieces).
    Those two terms bound what the TPU compiler asked for when compiling
    this kernel for a v5e at 4x4..20x20 and batch tiles of 128 and 256.
    """
    value = _tile_bytes(pes, batch_tile)
    blocks = (
        2 * _tile_bytes(pes, ring * pes)
        + _tile_bytes(pes, NOPS)
        + 2 * _tile_bytes(1, pes)
        + 2 * value
    )
    return 2 * blocks + ring * value + (NOPS + 4) * value + 7 * ring * value


@functools.partial(
    jax.jit,
    static_argnames=("ii", "ring", "num_cycles", "batch_tile", "interpret"),
)
def cgra_sim_pallas(
    route_a: jax.Array,   # [II, pes, ring*pes] f32 one-hot
    route_b: jax.Array,
    op_sel: jax.Array,    # [II, pes, NOPS] f32 one-hot
    imm: jax.Array,       # [II, 1, pes] f32
    inj: jax.Array,       # [C, pes, B] f32
    active: jax.Array,    # [C, 1, pes] f32
    *,
    ii: int,
    ring: int,
    num_cycles: int,
    batch_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Run the program; returns the full trace [C, pes, B]."""
    pes = route_a.shape[1]
    batch = inj.shape[2]
    bt = min(batch_tile, batch)
    if batch % bt:
        raise ValueError(f"batch {batch} not divisible by tile {bt}")
    nb = batch // bt

    vmem = vmem_footprint(pes, ring, bt)
    if not interpret and jax.default_backend() == "tpu":
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
        if vmem > capacity:
            raise ValueError(
                f"cgra_sim needs {vmem / 2**20:.1f} MiB of VMEM per grid step "
                f"(pes={pes}, ring={ring}, batch_tile={bt}); the chip has "
                f"{capacity / 2**20:.1f} MiB"
            )

    grid = (nb, num_cycles)  # batch tiles outer, cycles inner (sequential)
    return pl.pallas_call(
        _cgra_sim_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, pes, ring * pes), lambda b, c: (c % ii, 0, 0)),
            pl.BlockSpec((1, pes, ring * pes), lambda b, c: (c % ii, 0, 0)),
            pl.BlockSpec((1, pes, NOPS), lambda b, c: (c % ii, 0, 0)),
            pl.BlockSpec((1, 1, pes), lambda b, c: (c % ii, 0, 0)),
            pl.BlockSpec((1, pes, bt), lambda b, c: (c, 0, b)),
            pl.BlockSpec((1, 1, pes), lambda b, c: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, pes, bt), lambda b, c: (c, 0, b)),
        out_shape=jax.ShapeDtypeStruct((num_cycles, pes, batch), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ring, pes, bt), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
        name="cgra_sim",
    )(route_a, route_b, op_sel, imm, inj, active)
