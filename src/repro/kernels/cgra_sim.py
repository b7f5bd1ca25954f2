"""Pallas TPU kernel: batched functional execution of a mapped CGRA program.

Executes the steady-state modulo schedule produced by the paper's mapper on a
PE grid, vectorised over a batch of independent loop instances (the common
CGRA deployment: the same accelerated loop applied to many data streams).

Hardware adaptation (CGRA -> TPU), per DESIGN.md §17:

  * the PE grid's neighbour reads become **neighbour shifts**: each of the
    program's route pairs (a cycle delay and a PE offset) rolls one ring
    slot along the PE axis, and every PE picks its operand from the pair
    its integer code names with a VPU select. The work per cycle is
    O(pes x pairs x bt), with at most ring x D_M pairs (D_M = 5 on a mesh),
    where a dense one-hot gather would be O(pes**2 x ring x bt);
  * the per-PE ALU opcode select becomes a **one-hot select** on the VPU:
    one ``where`` per opcode picks op(a, b) — no data-dependent control flow;
  * PE register files become a **ring buffer in VMEM scratch**: cycle c
    writes slot c % ring, so the value produced delta cycles ago is slot
    (c - delta) % ring and nothing moves between cycles;
  * the cycle loop is the sequential grid dimension; the batch is tiled to
    128-lane blocks.

VMEM: ``vmem_footprint`` is what one grid step holds (one kernel step's
codes, double-buffered, and the ring) and is passed as the kernel's
``vmem_limit_bytes``; on a TPU a program that needs more than the chip's
VMEM is refused before compiling. It grows as pes, so a 50x50 fabric at
ring 11 and batch tile 128 fits a v5e.

Exactness: routing and the opcode select are selects, which copy the chosen
value bit for bit; no matmul touches a value. Every ALU op (incl. 16-bit-
masked bitwise) yields an f32-representable value, so the trace is compared
with the reference for equality. This holds while values stay below 2**31 in
magnitude, where f32 -> int32 saturates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.opcodes import OPS, operands

NOPS = len(OPS)


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
    """All candidate op results, one [pes, bt] array per opcode in code
    order: the op table's semantics on jnp blocks (f32-exact), with the
    quotient rounded as IEEE division rounds it."""
    v = operands(a, b, imm, inj, xp=jnp, to_int=lambda x: x.astype(jnp.int32),
                 word=lambda x: x.astype(jnp.float32),
                 divide=lambda a, b: _round_quotient(a, b, a / b))
    return [op.semantics(v) for op in OPS]


def _cgra_sim_kernel(
    # inputs
    pairs_ref,     # [2 * P] int32 (SMEM)  per route pair: delta, then PE shift
    route_ref,     # [1, pes, 2] int32    route pair of operand a, b for step k=c%II
    op_sel_ref,    # [1, pes, NOPS]       opcode one-hot for step k
    imm_ref,       # [1, 1, pes]          immediates for step k
    inj_ref,       # [1, pes, bt]         input-node injections for cycle c
    active_ref,    # [1, 1, pes]          1.0 where a node fires at cycle c
    # outputs
    trace_ref,     # [1, pes, bt]         value produced at (c, pe)
    # scratch
    ring_ref,      # [ring, pes8, bt]     register-file ring buffer (pes8: _pes8)
    opnd_ref,      # [2, pes, bt]         operands a and b being routed
    *,
    unroll: bool,  # route the pairs in straight-line code, not a loop
):
    c = pl.program_id(1)
    ring = ring_ref.shape[0]
    pes = inj_ref.shape[1]

    @pl.when(c == 0)
    def _init():
        ring_ref[...] = jnp.zeros_like(ring_ref)

    # neighbour shifts: the value produced delta cycles ago sits in ring slot
    # (c - delta) % ring; rolled along the PE axis, row pe holds PE
    # pe+offset's value, and each PE selects the pair its code names. A code
    # only names a pair whose source PE exists, so no wrapped row is ever
    # selected, and the selects copy values bit for bit.
    route = route_ref[0]                           # [pes, 2]
    code_a, code_b = route[:, 0:1], route[:, 1:2]
    opnd_ref[...] = jnp.zeros_like(opnd_ref)
    newest = c % ring                              # the slot cycle c writes

    def pair(p, carry):
        delta, shift = pairs_ref[2 * p], pairs_ref[2 * p + 1]
        slot = newest - delta
        slot = jnp.where(slot < 0, slot + ring, slot)
        x = pltpu.roll(ring_ref[slot], shift, 0)[:pes]
        opnd_ref[0] = jnp.where(code_a == p, x, opnd_ref[0])
        opnd_ref[1] = jnp.where(code_b == p, x, opnd_ref[1])
        return carry

    n_pairs = pairs_ref.shape[0] // 2
    jax.lax.fori_loop(0, n_pairs, pair, 0, unroll=n_pairs if unroll else 1)
    a, b = opnd_ref[0], opnd_ref[1]

    imm = imm_ref[0].T                             # [pes, 1]
    inj = inj_ref[0]
    sel = op_sel_ref[0]                            # [pes, NOPS]
    # opcode select: one VPU select per opcode, so the result is the chosen
    # candidate bit for bit and an inf among the others cannot leak into it
    val = jnp.zeros_like(a)
    for op, cand in enumerate(_alu_all(a, b, imm, inj)):
        val = jnp.where(sel[:, op:op + 1] > 0, cand, val)
    val = jnp.where(active_ref[0].T > 0, val, 0.0)

    # the newest value overwrites the one produced ring cycles ago, which
    # no operand reads any more
    ring_ref[newest, :pes] = val
    trace_ref[0] = val


def _tile_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of one f32 [rows, cols] slab, padded to the (8, 128) tile."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * 4


# A PE axis of at most this many rows is a few vregs a value. There the pair
# loop's own overhead outweighs its work, so its pairs are unrolled into
# straight-line code (three times faster at 4x4 on a v5e), at the price of
# VMEM for each pair's rolled value.
UNROLL_PES = 64


def vmem_footprint(pes: int, ring: int, batch_tile: int, pairs: int) -> int:
    """VMEM bytes one grid step of the kernel needs; its ``vmem_limit_bytes``.

    Every pipelined block is double-buffered: the route codes ``[pes, 2]``
    and the opcode block ``[pes, NOPS]`` (lanes padded to 128), the imm and
    active rows ``[1, pes]`` and the injection and trace blocks ``[pes, bt]``.
    The ring scratch ``[ring, pes, bt]`` and the two operands being routed
    come once. The body's values take the rest: the NOPS candidate results
    plus a few operand views, each ``[pes, bt]``. Above ``UNROLL_PES`` the
    route pairs are routed one at a time, so nothing grows with their number
    or with pes**2; at or below it each unrolled pair adds four values. This
    bounds what the TPU compiler asked for when compiling the kernel for a
    v5e from 4x4 to 50x50, ring 2 to 11, batch tiles of 128 and 256.
    """
    value = _tile_bytes(pes, batch_tile)
    blocks = (
        _tile_bytes(pes, 2)
        + _tile_bytes(pes, NOPS)
        + 2 * _tile_bytes(1, pes)
        + 2 * value
    )
    body = NOPS + 4 + (4 * pairs if pes <= UNROLL_PES else 0)
    return 2 * blocks + (ring + 2) * value + body * value


def _pes8(pes: int) -> int:
    """Rows of the ring's PE axis: ``pes`` rounded up to whole sublane tiles,
    the shape a TPU rolls by a dynamic amount."""
    return -(-pes // 8) * 8


@functools.partial(
    jax.jit,
    static_argnames=("ii", "ring", "num_cycles", "batch_tile", "interpret"),
)
def cgra_sim_pallas(
    pairs: jax.Array,     # [P, 2] int32 route pairs: (delta, offset)
    route: jax.Array,     # [II, pes, 2] int32 route pair of operand a, b (-1 = none)
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
    """Run the program; returns the full trace [C, pes, B].

    Route pair ``p`` reads the value produced ``pairs[p, 0]`` cycles ago
    (1..ring) at PE ``pe + pairs[p, 1]``; ``route[k, pe]`` names the pair of
    each operand at kernel step ``k``. The pairs are data, not part of the
    compiled kernel: programs of one shape share one compile.
    """
    pes = route.shape[1]
    batch = inj.shape[2]
    bt = min(batch_tile, batch)
    if batch % bt:
        raise ValueError(f"batch {batch} not divisible by tile {bt}")
    nb = batch // bt

    n_pairs = max(1, pairs.shape[0])
    vmem = vmem_footprint(pes, ring, bt, n_pairs)
    if not interpret and jax.default_backend() == "tpu":
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
        if vmem > capacity:
            raise ValueError(
                f"cgra_sim needs {vmem / 2**20:.1f} MiB of VMEM per grid step "
                f"(pes={pes}, ring={ring}, batch_tile={bt}, pairs={n_pairs}); "
                f"the chip has {capacity / 2**20:.1f} MiB"
            )

    pes8 = _pes8(pes)
    if pairs.shape[0] == 0:      # no operand reads; SMEM holds no empty array
        pairs = jnp.ones((1, 2), jnp.int32)
    # SMEM table, per pair: its delta, then the roll that brings PE
    # pe+offset's row to row pe
    table = jnp.stack([pairs[:, 0], (-pairs[:, 1]) % pes8], axis=1).reshape(-1)
    grid = (nb, num_cycles)  # batch tiles outer, cycles inner (sequential)
    return pl.pallas_call(
        functools.partial(_cgra_sim_kernel, unroll=pes <= UNROLL_PES),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, pes, 2), lambda b, c: (c % ii, 0, 0)),
            pl.BlockSpec((1, pes, NOPS), lambda b, c: (c % ii, 0, 0)),
            pl.BlockSpec((1, 1, pes), lambda b, c: (c % ii, 0, 0)),
            pl.BlockSpec((1, pes, bt), lambda b, c: (c, 0, b)),
            pl.BlockSpec((1, 1, pes), lambda b, c: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, pes, bt), lambda b, c: (c, 0, b)),
        out_shape=jax.ShapeDtypeStruct((num_cycles, pes, batch), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ring, pes8, bt), jnp.float32),
                        pltpu.VMEM((2, pes, bt), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
        name="cgra_sim",
    )(table, route, op_sel, imm, inj, active)
