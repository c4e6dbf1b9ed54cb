"""CSR segment-semiring SpMV Pallas kernels — the sparse frontier ⊗.

One frontier step of the sparse serving engine (``repro.core.sparse``) is

    out[b, dst_e] ⊕= frontier[b, src_e] ⊗ val_e      for every packed arc e

a gather along the frontier's lane dimension followed by a segment-⊕ scatter
over destinations.  The gather ``frontier[:, src] ⊗ val`` runs in XLA ahead
of the kernel (Mosaic lowers a lane gather only when input, indices and
output share one shape), so each kernel takes the (B, cap) per-arc
contributions and does the scatter, which TPUs have no native instruction
for, as a structured contraction over an *edge chunk*:

* **bool**: the chunk's destination one-hot ``H[e, j] = (dst_e == j)`` turns
  the segment-OR into ``contrib @ H`` — an f32 matmul on the MXU with a
  nonzero-threshold epilogue (the same trick ``boolmm`` uses for ∨.∧).
  A per-chunk **activity bitmap** (does any frontier row carry a live
  contribution in the chunk?) rides in as a scalar-prefetch operand and
  gates the matmul with ``pl.when`` — chunks whose contributions are all
  ⊕-zero (the common case late in a converging fixpoint) skip their MXU
  work entirely.
* **plus-times**: the SAME one-hot contraction as bool — ``contrib @ H`` on
  an f32 one-hot *is* an exact segment-sum by destination (each arc lands in
  exactly one output column), so the plus-times kernel is the bool kernel
  with the nonzero-threshold epilogue dropped: the MXU accumulator is the
  answer.
* **min-plus / max-plus**: no MXU path (min/max is not multiply-accumulate),
  so the segment-min runs on the VPU as a masked broadcast-min over
  (B, chunk, bn) column tiles.  The naive grid visits every (column-tile,
  edge-chunk) pair — O(cap·n) work even when a chunk's destinations touch
  one tile.  :func:`csr_minplus_spmv_tiled` instead walks a host-precomputed
  worklist of the (tile, chunk) pairs with at least one destination hit
  (``core.sparse._tile_plan``), carried in as scalar-prefetch operands whose
  values drive the BlockSpec index maps — O(hits) blocks.  Work items are
  tile-sorted (output blocks revisit contiguously) with a first-visit flag
  for the +inf init; list padding repeats items, sound because min is
  idempotent.

Block shapes follow the TPU tiling: edge chunks and column tiles are
multiples of 128 lanes (or the whole array), destinations ride as a
(cap, 1) column so the one-hot compare broadcasts along lanes.  Ad-hoc
callers with unbucketed edges or domains get padded here — sentinel edges
out of any chunk remainder, ⊕-zero columns out to the ``bn`` tile — instead
of hitting an alignment assert.

The bool and plus-times kernels keep the whole (B, n) output resident in
VMEM, so their width is bounded: :func:`vmem_bytes` is each kernel's VMEM
arithmetic, and every wrapper refuses a width over :data:`VMEM_LIMIT_BYTES`
with a typed :class:`VmemBudgetError` before Mosaic sees it (the same on
every backend, so interpret mode refuses what the chip would).  Tiling the
resident output over n is future work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # TPU lane width: edge chunks and column tiles are multiples
DEFAULT_CHUNK = 128  # edges per grid step: one lane tile
DEFAULT_BN = 128  # min-plus column tile (lane multiple)

#: Mosaic's default scoped-VMEM limit on TPU v5e — the compiler refuses a
#: kernel past it with "Scoped allocation with size ... and limit 16.00M".
#: The kernels set no ``vmem_limit_bytes``, so this is their budget.
VMEM_LIMIT_BYTES = 16 << 20

#: block index 0 as int32: the package enables x64, where a bare ``0`` in an
#: index map lowers as i64 and Mosaic refuses the kernel
_I0 = np.int32(0)


class VmemBudgetError(RuntimeError):
    """A CSR kernel at this frontier width needs more VMEM than the scoped
    limit: Mosaic would refuse to compile it.  Raised before lowering, on
    every backend, so no caller falls back silently."""

    def __init__(self, kind: str, batch: int, width: int, need: int,
                 limit: int = VMEM_LIMIT_BYTES):
        super().__init__(
            f"csr_{kind} kernel: a ({batch}, {width}) frontier needs {need} "
            f"bytes of VMEM, over the {limit}-byte scoped limit; use the "
            "XLA segment path (matmul=None) at this width")
        self.kind, self.batch, self.width = kind, batch, width
        self.need, self.limit = need, limit


def _pow2_floor(x: int) -> int:
    return 1 << (max(int(x), 1).bit_length() - 1)


def _round_up(x: int, m: int) -> int:
    return ((max(int(x), 1) + m - 1) // m) * m


def padded_width(n: int, bn: int = 1) -> int:
    """The frontier width the kernels actually see: ``n`` rounded up to the
    f32 lane multiple AND the column-tile size (``bn`` is a power of two, so
    one rounding to ``max(128, bn)`` covers both).  ``core.sparse`` builds
    its tile-skip plans against this same width."""
    return _round_up(n, max(LANES, bn))


def edge_chunk(chunk: int, cap: int) -> int:
    """The edge-chunk block for ``cap`` packed arcs: a power of two of at
    least one lane width (Mosaic's rank-2 block rule), or the whole (padded)
    edge array when that is smaller.  ``core.sparse`` plans with it too."""
    return min(max(LANES, _pow2_floor(chunk)), _round_up(cap, LANES))


def column_tile(bn: int) -> int:
    """The min-plus output column tile: a power of two of at least 128."""
    return max(LANES, _pow2_floor(bn))


def vmem_bytes(kind: str, batch: int, n: int, *, chunk: int | None = None,
               bn: int = DEFAULT_BN) -> int:
    """Scoped VMEM one call of the ``kind`` kernel needs for a (batch, n)
    frontier: the pipeline's blocks, each double-buffered, which is what
    Mosaic counts against :data:`VMEM_LIMIT_BYTES`.  The (chunk, 1)
    destination column is counted lane-padded; a bool output element as 2
    bytes, as the compiler lays it out.  bool / plus-times keep the whole
    (B, n) output resident, so their need grows with n; min/max-plus hold one
    (B, bn) output tile, so theirs does not.  (The f32 accumulator of the
    one-hot kernels is a scratch operand held in the rest of VMEM, and the
    in-kernel temporaries are processed a vreg at a time; neither binds
    before the blocks do.)
    """
    bp = _round_up(batch, 8)
    ch = max(LANES, _pow2_floor(chunk or DEFAULT_CHUNK))
    if kind in ("bool", "plustimes"):
        out = bp * padded_width(n) * (2 if kind == "bool" else 4)
    elif kind in ("minplus", "maxplus"):
        out = bp * column_tile(bn) * 4
    else:
        raise KeyError(kind)
    return 2 * (ch * LANES * 4 + bp * ch * 4 + out)


def check_vmem(kind: str, batch: int, n: int, *, chunk: int | None = None,
               bn: int = DEFAULT_BN) -> int:
    """:func:`vmem_bytes`, raising :class:`VmemBudgetError` over the limit."""
    need = vmem_bytes(kind, batch, n, chunk=chunk, bn=bn)
    if need > VMEM_LIMIT_BYTES:
        raise VmemBudgetError(kind, batch, n, need)
    return need


def _contributions(frontier, src, val, chunk: int, zero, mul):
    """The XLA half of a step: per-arc contributions ``frontier[:, src] ⊗
    val`` as a (B8, cap') f32 block grid — rows padded to a sublane multiple
    and arcs to whole chunks, both with ⊕-zero."""
    B = frontier.shape[0]
    contrib = mul(frontier[:, src], val).astype(jnp.float32)
    pb, pe = (-B) % 8, (-src.shape[0]) % chunk
    if pb or pe:
        contrib = jnp.pad(contrib, ((0, pb), (0, pe)), constant_values=zero)
    return contrib


def _dst_column(dst, chunk: int):
    """Destinations as a (cap', 1) int32 column (padded arcs point at
    column 0 with a ⊕-zero contribution, so they never change a result)."""
    pe = (-dst.shape[0]) % chunk
    if pe:
        dst = jnp.pad(dst, (0, pe))
    return dst.astype(jnp.int32)[:, None]


def _onehot_kernel(act_ref, dst_ref, c_ref, o_ref, acc_ref, *, threshold):
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(act_ref[c] != 0)  # chunk-skip: no live contribution -> no MXU
    def _body():
        chunk, n = dst_ref.shape[0], acc_ref.shape[1]
        onehot = dst_ref[...] == jax.lax.broadcasted_iota(
            jnp.int32, (chunk, n), 1)
        acc_ref[...] += jnp.dot(c_ref[...], onehot.astype(jnp.float32),
                                preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.HIGHEST)

    @pl.when(c == pl.num_programs(0) - 1)
    def _epilogue():
        acc = acc_ref[...]
        o_ref[...] = (acc > 0.0) if threshold else acc


def _onehot_spmv(kind, frontier, src, dst, val, chunk, interpret, zero, mul,
                 out_dtype):
    """Shared wrapper of the one-hot MXU kernels (bool / plus-times)."""
    B, n = frontier.shape
    check_vmem(kind, B, n, chunk=chunk)
    chunk = edge_chunk(chunk, src.shape[0])
    contrib = _contributions(frontier, src, val, chunk, zero, mul)
    dcol = _dst_column(dst, chunk)
    bp, cap = contrib.shape
    w = padded_width(n)
    nchunks = cap // chunk
    act = jnp.any(contrib.reshape(bp, nchunks, chunk) != 0.0, axis=(0, 2))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nchunks,),
        in_specs=[
            pl.BlockSpec((chunk, 1), lambda c, act: (c, _I0)),
            pl.BlockSpec((bp, chunk), lambda c, act: (_I0, c)),
        ],
        out_specs=pl.BlockSpec((bp, w), lambda c, act: (_I0, _I0)),
        scratch_shapes=[pltpu.VMEM((bp, w), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_onehot_kernel, threshold=kind == "bool"),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bp, w), out_dtype),
        interpret=interpret,
    )(act.astype(jnp.int32), dcol, contrib)
    return out[:B, :n]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def csr_bool_spmv(frontier: jax.Array, src: jax.Array, dst: jax.Array,
                  val: jax.Array, *, chunk: int = DEFAULT_CHUNK,
                  interpret: bool = False) -> jax.Array:
    """(B, n) bool ⊗_bool packed arcs -> (B, n) bool (segment-OR by dst)."""
    return _onehot_spmv("bool", frontier, src, dst, val, chunk, interpret,
                        0.0, jnp.logical_and, jnp.bool_)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def csr_plustimes_spmv(frontier: jax.Array, src: jax.Array, dst: jax.Array,
                       val: jax.Array, *, chunk: int = DEFAULT_CHUNK,
                       interpret: bool = False) -> jax.Array:
    """(B, n) f32 ⊗_+,× packed arcs -> (B, n) f32 (exact segment-sum by dst).

    Sentinel/pad arcs carry ``val = 0`` and contribute nothing; each live arc
    hits exactly one one-hot column, so the MXU accumulation is exact (f32
    keeps integer path counts exact to 2^24)."""
    return _onehot_spmv("plustimes", frontier, src, dst, val, chunk,
                        interpret, 0.0, jnp.multiply, jnp.float32)


def _tropical_kernel(*refs, reduce, zero, tiled):
    """Masked broadcast-⊕ of one edge chunk into one (B, bn) column tile.
    ``tiled``: the tile comes from the prefetched worklist, and a
    first-visit flag (not chunk 0) triggers the ⊕-identity init."""
    if tiled:
        tile_ref, _, first_ref, dst_ref, c_ref, o_ref = refs
        k = pl.program_id(0)
        j, first = tile_ref[k], first_ref[k] == 1
    else:
        dst_ref, c_ref, o_ref = refs
        j, first = pl.program_id(0), pl.program_id(1) == 0

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, zero)

    chunk, bn = dst_ref.shape[0], o_ref.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, bn), 1) + j * bn
    hit = dst_ref[...] == cols  # (chunk, bn) membership of this tile
    cand = reduce(jnp.where(hit[None, :, :], c_ref[...][:, :, None], zero),
                  axis=1)  # (B, bn)
    o_ref[...] = (jnp.minimum if reduce is jnp.min else jnp.maximum)(
        o_ref[...], cand)


def _tropical_spmv(kind, frontier, src, dst, val, chunk, bn, interpret,
                   zero, reduce):
    B, n = frontier.shape
    bn = column_tile(bn)
    check_vmem(kind, B, n, chunk=chunk, bn=bn)
    chunk = edge_chunk(chunk, src.shape[0])
    contrib = _contributions(frontier, src, val, chunk, zero, jnp.add)
    dcol = _dst_column(dst, chunk)
    bp, cap = contrib.shape
    w = padded_width(n, bn)
    # grid: column tiles major, edge chunks minor — the output tile stays
    # resident in VMEM and ⊕-accumulates across the chunk steps
    out = pl.pallas_call(
        functools.partial(_tropical_kernel, reduce=reduce, zero=zero,
                          tiled=False),
        grid=(w // bn, cap // chunk),
        in_specs=[
            pl.BlockSpec((chunk, 1), lambda j, c: (c, _I0)),
            pl.BlockSpec((bp, chunk), lambda j, c: (_I0, c)),
        ],
        out_specs=pl.BlockSpec((bp, bn), lambda j, c: (_I0, j)),
        out_shape=jax.ShapeDtypeStruct((bp, w), jnp.float32),
        interpret=interpret,
    )(dcol, contrib)
    return out[:B, :n]


@functools.partial(jax.jit, static_argnames=("chunk", "bn", "interpret"))
def csr_minplus_spmv(frontier: jax.Array, src: jax.Array, dst: jax.Array,
                     val: jax.Array, *, chunk: int = DEFAULT_CHUNK,
                     bn: int = DEFAULT_BN, interpret: bool = False) -> jax.Array:
    """(B, n) f32 ⊗_min,+ packed arcs -> (B, n) f32 (segment-min by dst)."""
    return _tropical_spmv("minplus", frontier, src, dst, val, chunk, bn,
                          interpret, jnp.inf, jnp.min)


@functools.partial(jax.jit, static_argnames=("chunk", "bn", "interpret"))
def csr_maxplus_spmv(frontier: jax.Array, src: jax.Array, dst: jax.Array,
                     val: jax.Array, *, chunk: int = DEFAULT_CHUNK,
                     bn: int = DEFAULT_BN, interpret: bool = False) -> jax.Array:
    """(B, n) f32 ⊗_max,+ packed arcs -> (B, n) f32 (segment-max by dst) —
    the min-plus broadcast kernel reflected through -inf sentinels."""
    return _tropical_spmv("maxplus", frontier, src, dst, val, chunk, bn,
                          interpret, -jnp.inf, jnp.max)


@functools.partial(jax.jit, static_argnames=("chunk", "bn", "interpret"))
def csr_minplus_spmv_tiled(frontier: jax.Array, src: jax.Array,
                           dst: jax.Array, val: jax.Array,
                           plan_tile: jax.Array, plan_chunk: jax.Array,
                           plan_first: jax.Array, *, chunk: int, bn: int,
                           interpret: bool = False) -> jax.Array:
    """Tile-skipping min-plus SpMV: the grid walks the precomputed worklist
    of (column-tile, edge-chunk) pairs with destination hits instead of the
    dense cross product — O(hits) blocks.  The plan arrays ride in as
    scalar-prefetch operands; their *values* drive the edge-chunk and output
    BlockSpec index maps (``core.sparse._tile_plan`` builds them against
    this wrapper's :func:`padded_width` and :func:`edge_chunk`)."""
    B, n = frontier.shape
    check_vmem("minplus", B, n, chunk=chunk, bn=bn)
    assert chunk == edge_chunk(chunk, src.shape[0]) \
        and bn == column_tile(bn), \
        "tile plan was built for a different packing — rebuild the CSR"
    contrib = _contributions(frontier, src, val, chunk, jnp.inf, jnp.add)
    dcol = _dst_column(dst, chunk)
    bp = contrib.shape[0]
    w = padded_width(n, bn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(plan_tile.shape[0],),
        in_specs=[
            pl.BlockSpec((chunk, 1), lambda k, t, c, fi: (c[k], _I0)),
            pl.BlockSpec((bp, chunk), lambda k, t, c, fi: (_I0, c[k])),
        ],
        out_specs=pl.BlockSpec((bp, bn), lambda k, t, c, fi: (_I0, t[k])),
    )
    out = pl.pallas_call(
        functools.partial(_tropical_kernel, reduce=jnp.min, zero=jnp.inf,
                          tiled=True),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bp, w), jnp.float32),
        interpret=interpret,
    )(plan_tile, plan_chunk, plan_first, dcol, contrib)
    return out[:B, :n]
