"""Bulk payload movement on device — the ICI engine's copy path.

The reference's bulk data path is writev/RDMA WRITE of IOBuf blocks
(socket.cpp:1643, rdma/rdma_endpoint.cpp); on TPU the equivalent hot op
is HBM→HBM movement staged through VMEM. ``device_copy`` is a Pallas
kernel with a pipelined grid (the pipeline emitter double-buffers the
HBM→VMEM→HBM DMAs automatically — the guide's double-buffering pattern
without hand-rolled semaphores); it is what the ICI endpoint uses to
"transmit" a payload buffer within a chip, and the unit the ring
streaming path repeats per hop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128

# Grid-block bounds of the copy/checksum kernels.  A block holds at most
# _BLOCK_ROWS_CAP rows and, widened to the f32 the checksum sums in, at
# most _BLOCK_F32_BYTES: in and out blocks double-buffer (4 blocks) next
# to one f32 temporary, so a kernel stays near 10 MiB of v5e's 16 MiB
# scoped VMEM whatever the row width (256 rows alone overflow it for
# f32 (4096, 4096) or bf16 (1024, 8192)).
_BLOCK_ROWS_CAP = 256
_BLOCK_F32_BYTES = 2 << 20

# dtypes the Mosaic kernels compile for (tests/test_chip_compile.py
# compiles each for v5e).  Everything else, float16 among them (Mosaic:
# "Invalid vector type for load"), rides the XLA-copy lane.
KERNEL_DTYPES = frozenset(
    jnp.dtype(t)
    for t in (jnp.float32, jnp.bfloat16, jnp.int32, jnp.int16, jnp.int8,
              jnp.uint32, jnp.uint16, jnp.uint8)
)


def _fit_block_rows(m: int, n: int, dtype) -> int:
    """Grid-block row count for an (m, n) view of ``dtype`` — the ONE
    place the copy/checksum kernels derive their block layout, so the
    whole-frame, chunked and DMA variants decompose a given array into
    the SAME block sequence (the property their checksums' bit-equality
    rests on).  The whole view when it fits the block bounds; else the
    largest power of two under them that divides m.  0 when that block
    breaks the dtype's sublane tiling (8 rows of 32-bit, 16 of 16-bit,
    32 of 8-bit): the view does not tile."""
    cap = max(1, min(_BLOCK_ROWS_CAP, _BLOCK_F32_BYTES // (4 * n)))
    if m <= cap:
        return m
    rows = 1 << (cap.bit_length() - 1)
    while m % rows:
        rows //= 2
    return rows if rows % (32 // jnp.dtype(dtype).itemsize) == 0 else 0


def lanes_view(arr):
    """2D lane-aligned view of ``arr`` for the copy/checksum kernels,
    or None when no tiling fits.  Like _fit_block_rows, this is the ONE
    place the lane decomposition is decided: the whole-frame, fused-
    chunked, and pipelined transmit paths must reshape identically or
    their checksums stop being comparable."""
    if (
        arr.ndim == 2 and arr.shape[1] % _LANE == 0 and arr.shape[0] > 0
        and _fit_block_rows(*arr.shape, arr.dtype)
    ):
        return arr
    total = arr.size
    if total <= 0 or total % _LANE:
        return None
    for lanes in (4096, 2048, 1024, 512, 256, 128):
        if total % lanes == 0 and _fit_block_rows(
            total // lanes, lanes, arr.dtype
        ):
            return arr.reshape(total // lanes, lanes)
    return None


def _lane_sums(blk):
    """Per-lane f32 sums of one block: the checksum's one widening rule,
    shared by every kernel.  Mosaic has no unsigned-to-float cast, so
    unsigned lanes widen through int32 first (exact for uint8/uint16;
    uint32 wraps mod 2**32, which keeps the sum a deterministic
    integrity value)."""
    if jnp.issubdtype(blk.dtype, jnp.unsignedinteger):
        blk = blk.astype(jnp.int32)
    return jnp.sum(blk.astype(jnp.float32), axis=0, keepdims=True)


def _copy_csum_kernel(in_ref, out_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    blk = in_ref[:]
    out_ref[:] = blk
    # running checksum per lane-column, folded on host side; f32 sum is
    # the VPU-friendly stand-in for the reference's crc32c framing check
    acc_ref[:] += _lane_sums(blk)


@functools.partial(jax.jit, static_argnames=("interpret",))
def device_copy_with_checksum(x: jax.Array, interpret: bool = False):
    """Fused transmit-and-verify: copies the payload and produces a
    per-lane checksum in one pass over HBM (one read instead of two).
    ``interpret=True`` runs the SAME kernel through the Pallas
    interpreter — the off-TPU compile gates exercise the real op's
    semantics instead of a lookalike (pallas_guide: interpret mode)."""
    m, n = x.shape
    rows = _fit_block_rows(m, n, x.dtype)
    if not rows:
        raise ValueError(f"{x.dtype} array of shape {x.shape} does not tile")
    grid = (m // rows,)
    # one spec construction for both paths: only memory_space differs
    # (the interpreter has no VMEM)
    ms = {} if interpret else {"memory_space": pltpu.VMEM}
    kw = {"interpret": True} if interpret else {}
    in_specs = [pl.BlockSpec((rows, n), lambda i: (i, 0), **ms)]
    out_specs = (
        pl.BlockSpec((rows, n), lambda i: (i, 0), **ms),
        pl.BlockSpec((1, n), lambda i: (0, 0), **ms),
    )
    out, acc = pl.pallas_call(
        _copy_csum_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        **kw,
    )(x)
    return out, jnp.sum(acc)


def _copy_csum_carry_kernel(in_ref, carry_ref, out_ref, acc_ref):
    """Chunk-accumulating flavor of _copy_csum_kernel: the lane
    accumulator starts from the carried-in value instead of zero, so a
    frame processed as K chunks chained through this kernel performs
    the SAME f32 additions in the SAME order as one whole-frame pass —
    the combined checksum is bit-identical, and the receiver still
    verifies one integrity value per frame."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = carry_ref[:]

    blk = in_ref[:]
    out_ref[:] = blk
    acc_ref[:] += _lane_sums(blk)


def _copy_csum_carry_slot_kernel(in_ref, carry_ref, slot_ref, out_ref, acc_ref):
    """Staging-ring flavor: identical math, plus a donated ``slot``
    input aliased onto the copy output so steady-state chunked sends
    write into a pre-allocated ring buffer instead of allocating
    (parallel/ici.py StagingRing — the RDMA block_pool analog).
    slot_ref is never read; it exists to carry the aliased buffer."""
    del slot_ref
    _copy_csum_carry_kernel(in_ref, carry_ref, out_ref, acc_ref)


def _csum_specs(rows: int, n: int, interpret: bool):
    """Block specs shared by the carry kernels (one construction for
    both paths: only memory_space differs — the interpreter has no
    VMEM)."""
    ms = {} if interpret else {"memory_space": pltpu.VMEM}
    kw = {"interpret": True} if interpret else {}
    blk = pl.BlockSpec((rows, n), lambda i: (i, 0), **ms)
    lane = pl.BlockSpec((1, n), lambda i: (0, 0), **ms)
    return blk, lane, kw


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def device_copy_with_checksum_chunk(
    x: jax.Array, carry: jax.Array, block_rows: int, interpret: bool = False
):
    """One chunk of a chunked transmit: copy ``x`` and fold its lane
    sums onto ``carry`` (shape (1, n) f32).  Returns (copy, new_carry).
    The pipelined ICI send launches one of these per chunk — chunk k's
    kernel runs while the host stages chunk k+1's launch.  Finish a
    frame with ``fold_checksum(new_carry)``."""
    m, n = x.shape
    blk, lane, kw = _csum_specs(block_rows, n, interpret)
    return pl.pallas_call(
        _copy_csum_carry_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ),
        grid=(m // block_rows,),
        in_specs=[blk, lane],
        out_specs=(blk, lane),
        **kw,
    )(x, carry)


@functools.partial(
    jax.jit, static_argnames=("block_rows",), donate_argnums=(2,)
)
def device_copy_with_checksum_chunk_into(
    x: jax.Array, carry: jax.Array, slot: jax.Array, block_rows: int
):
    """``device_copy_with_checksum_chunk`` writing into a donated
    ``slot`` buffer (same shape/dtype as ``x``): the slot's memory is
    aliased onto the copy output, so a StagingRing cycling 2-4 slots
    gives steady-state chunked sends zero per-call device allocation.
    TPU-only (no interpret flavor — donation is a no-op there)."""
    m, n = x.shape
    blk, lane, kw = _csum_specs(block_rows, n, False)
    return pl.pallas_call(
        _copy_csum_carry_slot_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ),
        grid=(m // block_rows,),
        in_specs=[blk, lane, blk],
        out_specs=(blk, lane),
        input_output_aliases={2: 0},
        **kw,
    )(x, carry, slot)


@jax.jit
def fold_checksum(carry: jax.Array) -> jax.Array:
    """Fold a (1, n) lane accumulator to the frame's single checksum
    scalar — the same reduction the whole-frame op ends with."""
    return jnp.sum(carry)


def chunk_plan_for(arr, chunk_bytes: int):
    """(lane_view, block_rows, chunks) that the chunked transmit paths
    will use for ``arr`` — fused, pipelined, and the fused path's
    pre-dispatch chaos walk all consume THIS plan, so chunk counts (and
    therefore chaos traversal indices) agree across modes.  Returns
    (None, 0, None) when the array doesn't tile."""
    v = lanes_view(arr)
    if v is None:
        return None, 0, None
    from incubator_brpc_tpu.utils.segmentation import plan_row_chunks

    m, n = v.shape
    block_rows = _fit_block_rows(m, n, v.dtype)
    chunks = plan_row_chunks(
        m, n * jnp.dtype(v.dtype).itemsize, chunk_bytes, block_rows
    )
    return v, block_rows, chunks


@functools.partial(
    jax.jit, static_argnames=("chunks", "block_rows", "interpret")
)
def _chunked_copy_csum(x, chunks, block_rows: int, interpret: bool):
    """Fused chunked transmit: the K-chunk pipeline as ONE program
    (one host dispatch per hop; the per-chunk Pallas calls inside are
    auto double-buffered by the pipeline emitter, and XLA schedules
    them back-to-back).  ``chunks`` is the (offset, rows) plan straight
    from segmentation.plan_row_chunks — the SAME plan the pipelined
    mode iterates, so the two modes can never segment differently.
    The accumulator chains through the chunks, so the checksum is
    bit-identical to the whole-frame kernel's."""
    n = x.shape[1]
    acc = jnp.zeros((1, n), jnp.float32)
    outs = []
    for off, rows in chunks:
        xc = jax.lax.slice_in_dim(x, off, off + rows)
        oc, acc = device_copy_with_checksum_chunk(
            xc, acc, block_rows, interpret
        )
        outs.append(oc)
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return out, jnp.sum(acc)


def device_copy_with_checksum_chunked(
    x: jax.Array,
    chunk_bytes: int = 8 << 20,
    interpret: bool = False,
):
    """Chunked copy+checksum over a 2D lane-aligned array.

    Splits ``x`` into ~chunk_bytes row chunks aligned to the
    whole-frame kernel's block layout (segmentation.plan_row_chunks),
    chains the lane accumulator through the chunks, and reassembles one
    output array.  The returned checksum equals
    ``device_copy_with_checksum(x)[1]`` BIT-FOR-BIT (same block
    sequence, same addition order) — frame sizes that are not
    chunk-multiples just get a short tail chunk."""
    v, block_rows, chunks = chunk_plan_for(x, chunk_bytes)
    if v is None:
        raise ValueError(f"array of shape {x.shape} does not lane-tile")
    return _chunked_copy_csum(
        v, chunks=tuple(chunks), block_rows=block_rows, interpret=interpret
    )


# ---------------------------------------------------------------------------
# double-buffered Pallas DMA transmit (chunk_mode="pallas")
# ---------------------------------------------------------------------------
#
# The fused/pipelined modes above lean on the pipeline emitter: each
# chunk is its own grid, and the emitter double-buffers HBM↔VMEM behind
# the scenes.  The DMA kernel below is the hand-rolled version the
# pallas guide's double-buffering pattern describes: the WHOLE frame is
# one `pl.pallas_call` whose body drives explicit `make_async_copy`
# DMAs under send/recv (here: in/out) DMA semaphores — stage k+1's
# HBM→VMEM pull starts while stage k's checksum runs and stage k-2's
# VMEM→HBM push drains.  One host dispatch, one Mosaic program, zero
# per-chunk launch gaps: the plumbing the 4x raw-vs-effective gap in
# BENCH_r02..r05 pointed at.
#
# Bit-equality contract: the stage plan comes from segmentation.
# fit_stage_rows over the SAME (lanes_view, _fit_block_rows) layout as
# every other mode, each stage is a whole number of checksum blocks,
# and the accumulator adds per-block column sums in block order — the
# identical f32 additions in the identical order as the whole-frame
# grid kernel.  tests/test_ici_pipeline.py pins this in interpret mode.


def _dma_copy_csum_body(nstages: int, stage_rows: int, block_rows: int,
                        aliased: bool):
    """Kernel body factory (static shape closure): double-buffered
    HBM→VMEM→HBM copy with the chained per-block checksum.  ``aliased``
    takes the donated slot ref that ``input_output_aliases={2: 0}``
    adds as the third input; it aliases ``out_hbm`` and is never read."""

    def kernel(x_hbm, carry_ref, *refs):
        if aliased:
            refs = refs[1:]
        out_hbm, acc_ref, in_buf, out_buf, in_sems, out_sems = refs

        bps = stage_rows // block_rows  # checksum blocks per stage

        def in_dma(k, slot):
            return pltpu.make_async_copy(
                x_hbm.at[pl.ds(k * stage_rows, stage_rows)],
                in_buf.at[slot], in_sems.at[slot],
            )

        def out_dma(k, slot):
            return pltpu.make_async_copy(
                out_buf.at[slot],
                out_hbm.at[pl.ds(k * stage_rows, stage_rows)],
                out_sems.at[slot],
            )

        acc_ref[:] = carry_ref[:]
        in_dma(0, 0).start()  # warm-up: stage 0 in flight before the loop

        def body(k, _):
            slot = jax.lax.rem(k, 2)

            @pl.when(k + 1 < nstages)
            def _():
                in_dma(k + 1, jax.lax.rem(k + 1, 2)).start()

            in_dma(k, slot).wait()

            # slot reuse discipline: stage k writes the SAME out slot
            # stage k-2 used — its push must have drained first
            @pl.when(k >= 2)
            def _():
                out_dma(k - 2, slot).wait()

            stage = in_buf[slot]
            out_buf[slot] = stage
            a = acc_ref[:]
            for b in range(bps):  # static unroll: block-order additions
                a = a + _lane_sums(stage[b * block_rows:(b + 1) * block_rows])
            acc_ref[:] = a
            out_dma(k, slot).start()
            return 0

        jax.lax.fori_loop(0, nstages, body, 0)
        # drain: the last two pushes are still in flight
        if nstages >= 2:
            out_dma(nstages - 2, (nstages - 2) % 2).wait()
        out_dma(nstages - 1, (nstages - 1) % 2).wait()

    return kernel


def _dma_call(x, carry, block_rows: int, stage_rows: int,
              interpret: bool, slot=None):
    """Build + invoke the DMA pallas_call; returns (out, acc)."""
    m, n = x.shape
    nstages = m // stage_rows
    ms = {} if interpret else {"memory_space": pltpu.VMEM}
    lane = pl.BlockSpec((1, n), lambda: (0, 0), **ms)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [any_spec, lane]
    operands = [x, carry]
    kw = {"interpret": True} if interpret else {}
    if slot is not None:
        in_specs.append(any_spec)
        operands.append(slot)
        kw["input_output_aliases"] = {2: 0}
    return pl.pallas_call(
        _dma_copy_csum_body(nstages, stage_rows, block_rows,
                            slot is not None),
        out_shape=(
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ),
        in_specs=in_specs,
        out_specs=(any_spec, lane),
        scratch_shapes=[
            pltpu.VMEM((2, stage_rows, n), x.dtype),   # in double-buffer
            pltpu.VMEM((2, stage_rows, n), x.dtype),   # out double-buffer
            pltpu.SemaphoreType.DMA((2,)),             # pull semaphores
            pltpu.SemaphoreType.DMA((2,)),             # push semaphores
        ],
        **kw,
    )(*operands)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "stage_rows", "interpret")
)
def device_copy_with_checksum_dma(
    x: jax.Array, block_rows: int, stage_rows: int, interpret: bool = False
):
    """Whole-frame transmit as ONE double-buffered DMA kernel: copies
    ``x`` HBM→HBM through explicitly-semaphored VMEM staging slots and
    returns ``(out, csum)`` with the checksum bit-identical to
    :func:`device_copy_with_checksum`.  ``interpret=True`` runs the
    SAME kernel (DMA semantics included) through the Pallas TPU
    interpreter — the CPU tier-1 coverage gate."""
    m, n = x.shape
    carry = jnp.zeros((1, n), jnp.float32)
    out, acc = _dma_call(x, carry, block_rows, stage_rows, interpret)
    return out, jnp.sum(acc)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "stage_rows"),
    donate_argnums=(1,),
)
def device_copy_with_checksum_dma_into(
    x: jax.Array, slot: jax.Array, block_rows: int, stage_rows: int
):
    """:func:`device_copy_with_checksum_dma` writing into a donated
    frame-shaped ``slot`` (StagingRing buffer): the kernel output
    aliases the slot's memory, so a ring hit makes the whole-frame
    transmit allocation-free.  TPU-only (donation is a no-op under the
    interpreter)."""
    m, n = x.shape
    carry = jnp.zeros((1, n), jnp.float32)
    out, acc = _dma_call(
        x, carry, block_rows, stage_rows, False, slot=slot
    )
    return out, jnp.sum(acc)


def pallas_stage_rows(v, block_rows: int) -> int:
    """The DMA stage size for lane view ``v`` — segmentation policy
    (fit_stage_rows) applied to the transfer kernels' block layout.
    0 when the stage is not a whole number of packed rows (2 rows of
    16-bit, 4 of 8-bit): Mosaic refuses such a VMEM slice, and the DMA
    lane declines the frame."""
    from incubator_brpc_tpu.utils.segmentation import fit_stage_rows

    m, n = v.shape
    itemsize = jnp.dtype(v.dtype).itemsize
    rows = fit_stage_rows(m, n * itemsize, block_rows)
    return 0 if rows % max(1, 4 // itemsize) else rows


def device_copy_with_checksum_pallas(
    x: jax.Array, chunk_bytes: int = 8 << 20, interpret: bool = False,
    plan=None,
):
    """Frame-level entry for the Pallas DMA transmit: plans the layout
    (``chunk_plan_for`` — the one plan source, so chaos walks and bench
    step counts agree with the other modes), sizes the VMEM stages, and
    issues ONE fused kernel dispatch.  Returns (out, csum); raises
    ValueError for arrays that don't lane-tile."""
    v, block_rows, chunks = (
        plan if plan is not None else chunk_plan_for(x, chunk_bytes)
    )
    stage_rows = pallas_stage_rows(v, block_rows) if v is not None else 0
    if not stage_rows:
        raise ValueError(f"array of shape {x.shape} does not lane-tile")
    out, csum = device_copy_with_checksum_dma(
        v, block_rows, stage_rows, interpret
    )
    return (out if v is x else out.reshape(x.shape)), csum


def transmit_array_chunked(arr, chunk_bytes: int = 8 << 20, plan=None):
    """Chunked-pipeline flavor of :func:`transmit_array` — the fabric's
    large-frame path.  Frames big enough for ≥2 chunks run the fused
    chunked copy+checksum (one dispatch, chunk-granular device
    pipeline); everything else falls through to transmit_array
    unchanged (including the off-TPU XLA-copy fallback).  ``plan`` is an
    optional precomputed ``chunk_plan_for(arr, chunk_bytes)`` result so
    a caller that already planned (the fabric's pre-dispatch chaos
    walk) doesn't plan twice."""
    from incubator_brpc_tpu.utils.segmentation import MIN_CHUNKS

    if kernel_lane(arr) and int(arr.nbytes) >= MIN_CHUNKS * chunk_bytes:
        v, block_rows, chunks = (
            plan if plan is not None else chunk_plan_for(arr, chunk_bytes)
        )
        if v is not None:
            out, csum = _chunked_copy_csum(
                v, chunks=tuple(chunks), block_rows=block_rows,
                interpret=False,
            )
            return (out if v is arr else out.reshape(arr.shape)), csum
    return transmit_array(arr)


@jax.jit
def _xla_copy(x: jax.Array) -> jax.Array:
    # jit output cannot alias the (undonated) input, so XLA emits a real
    # HBM traversal — the fallback "transmission" for shapes/dtypes the
    # Pallas kernel doesn't tile.
    return jnp.copy(x)


def _on_tpu(arr) -> bool:
    try:
        return all(d.platform == "tpu" for d in arr.devices())
    except Exception:  # noqa: BLE001 — non-jax array-likes
        return False


def kernel_lane(arr) -> bool:
    """True when ``arr`` may take the Pallas transmit kernels: it lives
    on TPU and its dtype is one they compile for (KERNEL_DTYPES).  The
    one gate of every transmit path; what fails it takes the XLA-copy
    lane."""
    return _on_tpu(arr) and arr.dtype in KERNEL_DTYPES


def transmit_array(arr):
    """One ICI "transmission" of an HBM payload: the op the fabric runs
    per device segment on same-chip delivery (the analog of the wire hop
    RDMA WRITE performs; rdma/rdma_endpoint.cpp CutFromIOBufList).

    Runs the fused Pallas copy+checksum when the array tiles onto the
    VPU lanes (``lanes_view``), an XLA copy otherwise (and always
    off-TPU, where the Mosaic kernel can't run, or for a dtype outside
    KERNEL_DTYPES). Returns ``(new_array, checksum_or_None)``; nothing
    here syncs to host — the checksum stays device-resident.
    """
    if kernel_lane(arr):
        v = lanes_view(arr)
        if v is arr:
            return device_copy_with_checksum(arr)
        if v is not None:
            return _transmit_reshaped(arr)
    return _xla_copy(arr), None


@jax.jit
def _transmit_reshaped(x: jax.Array):
    out, csum = device_copy_with_checksum(lanes_view(x))
    return out.reshape(x.shape), csum
