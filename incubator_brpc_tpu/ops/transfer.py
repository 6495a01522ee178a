"""Bulk payload movement on device — the ICI engine's copy path.

The reference's bulk data path is writev/RDMA WRITE of IOBuf blocks
(socket.cpp:1643, rdma/rdma_endpoint.cpp); on TPU the equivalent hot op
is HBM→HBM movement staged through VMEM.  The fabric "transmits" a
same-chip payload through one entry, :func:`transmit_array_chunked`,
which picks by size alone:

- below ``MIN_CHUNKS * chunk_bytes``, the whole-frame copy+checksum
  kernel (``device_copy_with_checksum``, one Pallas grid);
- at or above it, the fused chunk program (``_chunked_copy_csum``: one
  program of per-chunk Pallas calls chaining one lane accumulator);
- whatever does not tile, or a dtype outside ``KERNEL_DTYPES``, or an
  array off the TPU, takes an XLA copy with no checksum.

Each Pallas grid is double-buffered by the pipeline emitter (HBM→VMEM
→HBM DMAs without hand-rolled semaphores), and both kernels decompose a
frame into the same block sequence, so their checksums are bit-equal.
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
    whole-frame kernel and the fused chunk program decompose a given
    array into the SAME block sequence (the property their checksums'
    bit-equality rests on).  The whole view when it fits the block
    bounds; else the
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
    place the lane decomposition is decided: the whole-frame kernel and
    the fused chunk program must reshape identically or their checksums
    stop being comparable."""
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
    blk, lane, kw = _csum_specs(rows, n, interpret)
    out, acc = pl.pallas_call(
        _copy_csum_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ),
        grid=(m // rows,),
        in_specs=[blk],
        out_specs=(blk, lane),
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


def _csum_specs(rows: int, n: int, interpret: bool):
    """Block specs shared by the copy/checksum kernels (one
    construction for both paths: only memory_space differs — the
    interpreter has no VMEM)."""
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
    sums onto ``carry`` (shape (1, n) f32).  Returns (copy, new_carry);
    the fused chunk program chains one of these per chunk."""
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


def chunk_plan_for(arr, chunk_bytes: int):
    """(lane_view, block_rows, chunks) of the fused chunk program for
    ``arr`` — the program and the fabric's pre-dispatch chaos walk both
    consume THIS plan, so chaos traversal indices match the chunks that
    run.  Returns (None, 0, None) when the array doesn't tile."""
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
    from segmentation.plan_row_chunks.  The accumulator chains through
    the chunks, so the checksum is bit-identical to the whole-frame
    kernel's."""
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


def transmit_array_chunked(arr, chunk_bytes: int = 8 << 20, walk=None):
    """The fabric's same-chip transmit, chosen by size: frames of at
    least MIN_CHUNKS chunks run the fused chunk program (one dispatch,
    chunk-granular device pipeline); everything else falls through to
    :func:`transmit_array` (the whole-frame kernel, or the XLA copy).
    ``walk(total_chunks)``, when given, runs before a frame at or above
    that size dispatches, over the plan the program would run — the
    fabric's ici.chunk chaos walk.  Returns ``(new_array, csum_or_None)``."""
    from incubator_brpc_tpu.utils.segmentation import MIN_CHUNKS

    if int(arr.nbytes) >= MIN_CHUNKS * chunk_bytes:
        v, block_rows, chunks = chunk_plan_for(arr, chunk_bytes)
        if walk is not None:
            walk(len(chunks or ()))
        if v is not None and kernel_lane(arr):
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
