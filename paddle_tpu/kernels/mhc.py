"""Manifold-constrained hyper-connections (the ops mhc_maps_read, mhc_maps
and mhc_write, name scopes `mhc.maps` and `mhc.mix`) as three Pallas TPU
kernel pairs over tiles of rows: the maps (with a sublayer's read in the
same pair: `maps_read`; alone: `maps`) and the write.

ops/hyper_connection_ops.py::maps, ::read and ::write are the arithmetic,
in jax.numpy.  There every stage is a pass over fp32 [T, n C] values in
HBM, in the forward, the recomputed forward and the backward, and the
backward of the 2 x `iters` unrolled Sinkhorn normalisations is hundreds of
small fusions: at [1, 4096, 4, 3584] the two scopes took 105.8 ms a step
for 8.6 ms of traffic (PERF.md, PR 50).

Here the kernels read the streams stream-major, [B, n, S, C]: a stream's
[S, C] plane under the last two axes, so that a block of it is whole
(sublane, lane) tiles.  The ops hand [B, S, n, C] over through a
transposition that is one in name (`_by_stream`): the streams live between
these ops alone and the compiler lays the value out stream-major, where as
rows of n C every op paid a copy of the streams each way (a reshape of [S,
n, C] to [S, n C] is no bitcast under the TPU's tiled layouts; PERF.md, PR
51).  A grid step holds a tile of rows x a block of channels of a stream:
- `maps`: the grid walks the blocks of a tile of rows last, the n streams
  of a block of channels one after the other (the rows of Phi follow the
  block: a token's n C values are its n rows of C).  Each block
  adds its squares (fp32, on the VPU) and its products with Phi (on the
  MXU: Phi's three bf16 parts side by side are 72 columns of ONE pass, a
  bf16 stream is exact in the other operand, products and sums are fp32,
  and the three groups of columns add up to the fp32 product with no
  rounding of Phi dropped; fp32 streams go as three parts too) to two
  [rows, 128] accumulators in VMEM.  After the last block the tile's
  [128, rows] transpose puts the tokens on the lanes for the activations,
  the clamp and the Sinkhorn iterations on [n^2, rows] values (sums over a
  map's rows and columns are sublane rolls; exact divides), and H [B, 2n +
  n^2, S] leaves as the jax.numpy form gives it.
- `maps_read`, forward: the same kernel.  Where a tile of rows x all n C
  channels fits VMEM beside the streamed blocks (`resident`: 128 rows of
  the cell's bf16 streams are 3.67 MB) it copies every block to a scratch
  as it passes, and after the tail a second transpose puts H_pre back a
  token a row and x_in = sum_j H_pre[j] X[j] (fp32 sums, one rounding)
  leaves with H: the streams are read once.  Where it does not fit, the
  maps' kernel and then `_read_kernel` over H^T, at `mix_tiles`' tile.
- the backward of both is two kernels; `maps_read`'s are `maps`' with
  x_in's cotangent dOut as one more operand, so that the streams' gradient
  through the maps and through the read is ONE value.  The first makes the
  tile's forward again (beside the squares it adds up dH_pre[j] = sum_c
  dOut X[j], which rides the one transpose in n free lanes and joins H's
  incoming cotangent), keeps the 2 x `iters` + 1 iterates [n^2, rows] in
  VMEM, walks them back and leaves the cotangent of u Phi (as its three
  bf16 parts) and of the sum of squares a token, H_pre and H_post again,
  [B, 128, S] fp32, and the six small parameters' gradients in blocks
  resident over the grid.  The second streams the tile again, channel
  blocks outermost, a block of channels of ALL n streams a grid step (dOut's
  block is read and converted once for the n of them): dX = dm Phi^T + 2 u
  dss (+ H_pre[j] dOut) on the MXU and the VPU, and dPhi^T [2n + n^2, C] a
  stream accumulates in fp32 in blocks resident over the rows.
- `write`: given H it is independent per channel.  H comes
  transposed ([B, S, 2n + n^2], 0.4 MB, by XLA), a map value a token is a
  lane-broadcast column, sums are fp32 with one rounding.  A grid step
  writes ONE stream's block (the innermost grid axis walks the streams
  while the blocks they share stay in VMEM), so channels block freely
  under one output array.  The backward (jax.custom_vjp; the residuals are
  the op's INPUTS) reads X, the cotangents and y, writes dX, dy and dH's
  columns, which accumulate over the channel blocks in a resident block.
  (`read` alone had such a pair until PR 62; its forward kernel is the
  streamed form's, its backward is the maps'.)

`maps_tiles`, `maps_read_tiles` and `mix_tiles` read the tile from the
shape and the VMEM it needs (the budget and the search are
kernels/engine.py's), or say that the shape does not tile; the ops ask
kernels/engine.py whether a site runs these pairs at all
(ops/hyper_connection_ops.py::_site) and run their jax.numpy form where it
does not.
tools/mhc_probe.py times the pairs alone on the chip.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import engine
from .engine import F32, LANES, add_up, compiler_params, roll, sigmoid

__all__ = ["Tiles", "FusedTiles", "maps_tiles", "mix_tiles", "maps_read_tiles",
           "maps", "maps_read", "write"]

_BF16 = jnp.bfloat16
# Both planners take the widest block of a stream's channels first, then
# the most rows that fit beside it (kernels/kda_mix.py::_CHANNELS's lesson:
# a wider block is a longer contiguous run a row for the DMA); the maps may
# take more rows than kda_mix's 256, since a tile of rows streams Phi's
# parts (3.67 MB at the cell's shape) once and its tail works on [n^2,
# rows] values.  On the chip at the cell's shape (tools/mhc_probe.py
# --sweep, ms a sublayer forward / backward, PERF.md PR 51) the maps read
# 0.36 / 0.84 at 128 rows x 896 channels, 0.28 / 0.67 x 1792, 0.23 / 0.62
# x 3584; 0.27 / 0.66, 0.23 / 0.61, 0.20 / 0.59 at 256 rows; 0.22 / 0.61
# and 0.20 / 0.59 at 512 x 896 and x 1792; 0.22 / 0.60 at 1024 x 512;
# `read` 0.23 / 0.52, 0.23 / 0.45, 0.22 / 0.42 at 128 rows and 0.23 / 0.46,
# 0.23 / 0.42 at 256 x 896 and x 1792; `write` 0.65 / 0.95 and 0.54 / 0.81
# at 128 x 896 and x 1792, 0.59 / 0.85 at 256 x 896.
_MAPS_ROWS = (1024, 512, 256, 128)
# `read` and `write` hold a column's fp32 value in registers as
# kernels/kda_mix.py's pairs do: no tile above 256 rows
_MIX_ROWS = (256, 128)
# rows of the sum of squares a pass of the inner loop keeps in registers
_SQUARE_ROWS = 64


class Tiles(NamedTuple):
    """What a site's kernel pair is built from, all read from the shape."""
    rows: int
    channels: int
    fwd_vmem_bytes: int
    bwd_vmem_bytes: int


class FusedTiles(NamedTuple):
    """The same for a `mhc_maps_read` site; `resident` 1 where the forward
    holds a tile of rows x all n C channels in VMEM and reads the streams
    once, 0 where it is the maps' kernel and then `read`'s."""
    rows: int
    channels: int
    fwd_vmem_bytes: int
    bwd_vmem_bytes: int
    resident: int


class Maps(NamedTuple):
    """What `mhc_maps` computes under, beside its operands."""
    streams: int
    epsilon: float
    hc_eps: float
    iters: int
    clamp_min: float
    clamp_max: float


def _values(n):
    """2n + n^2: the map values a token."""
    return 2 * n + n * n


def _spare(n):
    """Where the columns of u Phi's three parts and of the squares end (3
    N + 1 of 128), at a whole sublane tile: the lanes the sums of dOut X
    ride to the tail in, the rows H_pre and H_post leave the backward's
    first kernel in."""
    return 3 * _values(n) + 8


def _parts(dtype):
    """bf16 values that add up to a value of `dtype`."""
    return 1 if jnp.dtype(dtype) == jnp.dtype(_BF16) else 3


def _blocks(width):
    """Blocks of whole 128-lane vectors that divide `width`, widest
    first."""
    units = width // LANES
    return [d * LANES for d in range(units, 0, -1) if units % d == 0]


def maps_working_set(rows, block, n, iters, size, backward, fused=False
                     ) -> int:
    """What a grid step of the maps' forward kernel and of the backward's
    first holds in VMEM: the declared blocks twice (the pipeline's two
    buffers), the scratch and the fp32 temporaries of the tail; `fused`
    (the backward of `mhc_maps_read`): a block of dOut and the n sums of
    dOut X besides."""
    N = _values(n)
    tile, wide = rows * block * size, rows * LANES * 4
    phi = block * LANES * 2
    forward = 2 * (tile + phi + N * rows * 4) + 2 * wide + 14 * N * rows * 4
    if not backward:
        return forward
    return (forward + 2 * (N * rows * 4 + wide)
            + (2 * iters + 1) * n * n * rows * 4
            + (2 * tile + n * wide if fused else 0))


def stream_working_set(rows, block, n, size, fused=False) -> int:
    """The same for the backward's second kernel, whose grid step holds a
    block of channels of ALL n streams, in and out (and of dOut, `fused`),
    beside the n blocks of Phi^T's parts and of dPhi^T."""
    N, parts = _values(n), 3 if size == 4 else 1
    tile, wide = rows * block * size, rows * LANES * 4
    return (2 * ((2 * n + bool(fused)) * tile + 2 * wide
                 + (parts + 1) // 2 * rows * LANES * 2
                 + n * (block * LANES * 2 + N * block * 4)) + 8 * wide)


def stream_channels(rows, channels, n, width, size, fused=False):
    """The block of channels of the backward's second kernel under a tile
    of `rows` x `channels`: the widest no wider than the tile's whose
    working set fits the budget (its grid step holds n streams' blocks
    where the first kernel's holds one), None where none does."""
    for c in _blocks(width):
        if c <= channels and stream_working_set(
                rows, c, n, size, fused) <= engine.PLAN_VMEM_BUDGET:
            return c
    return None


def _maps_backward_set(rows, block, n, width, iters, size, fused=False):
    """The larger working set of the backward's two kernels; over the
    budget where the second finds no block whose n streams are as wide
    together as the first's one (more rows are worth less than that:
    PERF.md PR 51's sweep)."""
    c = stream_channels(rows, block, n, width, size, fused)
    if c is None or n * c < block:
        return engine.PLAN_VMEM_BUDGET + 1
    return max(maps_working_set(rows, block, n, iters, size, True, fused),
               stream_working_set(rows, c, n, size, fused))


def resident_working_set(rows, block, n, width, size) -> int:
    """What a grid step of `mhc_maps_read`'s resident forward holds: the
    maps' forward, the tile of rows x n C channels it keeps and x_in's
    block of rows x C twice, and the read's fp32 temporaries."""
    return (maps_working_set(rows, block, n, 0, size, False)
            + (n + 2) * rows * width * size + 16 * rows * LANES * 4)


def mix_working_set(rows, block, n, size, what) -> int:
    """The same for `read`'s forward kernel (`what` "read") and for
    `write`'s kernels (forward "write", backward "write_bwd")."""
    tile, wide = rows * block * size, rows * LANES * 4
    blocks = {"read": n + 1, "write": n + 2, "write_bwd": n + 4}[what]
    return (2 * (blocks * tile + (2 if what == "write_bwd" else 1) * wide)
            + 16 * wide)


def _maps_shape_tiles(n, width):
    """Whether the maps' kernels take the shape at all: C whole 128-lane
    vectors, the map's rows and the five groups of 2n + n^2 columns whole
    sublane tiles of one 128-lane vector (n = 4)."""
    return not (width % LANES or n % 4 or 5 * _values(n) > LANES)


def _maps_rows(rows):
    """The candidates for a tile's rows, the tokens whole 128-lane
    vectors."""
    return [r for r in (_MAPS_ROWS if rows is None else (rows,))
            if r % LANES == 0]


def maps_tiles(seq, n, width, iters, dtype, rows=None, channels=None
               ) -> Optional[Tiles]:
    """The tiles of a `mhc_maps` site, None where the shape does not tile
    (`_maps_shape_tiles`), S is no whole tiles of rows with the tokens on
    the lanes, or no working set is inside the budget."""
    size = jnp.dtype(dtype).itemsize
    if not _maps_shape_tiles(n, width):
        return None

    def need(r, c, backward=True):
        if not backward:
            return maps_working_set(r, c, n, iters, size, False)
        return _maps_backward_set(r, c, n, width, iters, size)

    found = engine.widest(
        seq, width, LANES, need, _maps_rows(rows),
        _blocks(width) if channels is None else (channels,))
    return found and Tiles(*found, need(*found, False), need(*found))


def mix_tiles(seq, n, width, dtype, what, rows=None, channels=None
              ) -> Optional[Tiles]:
    """The tiles of `read`'s forward kernel or of a `mhc_write` site
    (`what`), None where the shape does not tile: C whole 128-lane
    vectors, S whole tiles of rows, the working set inside the budget.
    `read` has no backward kernel of its own (the fused op's is the
    maps'): its `bwd_vmem_bytes` is 0."""
    size = jnp.dtype(dtype).itemsize
    if width % LANES:
        return None

    def forward(r, c):
        return mix_working_set(r, c, n, size, what)

    def backward(r, c):
        return (0 if what == "read"
                else mix_working_set(r, c, n, size, what + "_bwd"))

    found = engine.widest(
        seq, width, LANES, lambda r, c: max(forward(r, c), backward(r, c)),
        [r for r in (_MIX_ROWS if rows is None else (rows,))
         if r % engine.halo_rows(dtype) == 0],
        _blocks(width) if channels is None else (channels,))
    return found and Tiles(*found, forward(*found), backward(*found))


def maps_read_tiles(seq, n, width, iters, dtype, rows=None, channels=None,
                    resident=None) -> Optional[FusedTiles]:
    """The tiles of a `mhc_maps_read` site, None where the shape does not
    tile as `maps_tiles` asks.  Streamed, the forward is the maps' kernel
    at the widest tile and then `read`'s forward kernel at its own
    (`mix_tiles`).  It holds the tile instead (`resident`: the streams are
    read once) where a tile of rows x all n C channels fits beside the
    streamed blocks of Phi at the block of channels the streamed forward
    takes: fewer rows cost less than a second pass over the streams, a
    narrower block more (PERF.md, PRs 51 and 62).  The backward's two
    kernels take the same tile either way.  `rows`, `channels` and
    `resident` pin what a probe or a test asks for."""
    size = jnp.dtype(dtype).itemsize
    if not _maps_shape_tiles(n, width):
        return None

    def backward(r, c):
        return _maps_backward_set(r, c, n, width, iters, size, True)

    def plan(held, blocks):
        if not held and mix_tiles(seq, n, width, dtype, "read") is None:
            return None

        def forward(r, c):
            if held:
                return resident_working_set(r, c, n, width, size)
            return maps_working_set(r, c, n, iters, size, False)

        found = engine.widest(
            seq, width, LANES, lambda r, c: max(forward(r, c), backward(r, c)),
            _maps_rows(rows), blocks)
        return found and FusedTiles(*found, forward(*found),
                                    backward(*found), held)

    blocks = _blocks(width) if channels is None else (channels,)
    if resident is not None:
        return plan(int(bool(resident)), blocks)
    streamed = plan(0, blocks)
    return plan(1, (streamed.channels,) if streamed else blocks) or streamed


# ---------------------------------------------------------------------------
# what the kernels compute
# ---------------------------------------------------------------------------
def _top(v):
    """fp32 v with all but the 8 leading bits of its significand cleared:
    a bf16 value, exactly, still in fp32.  A mask and not a pair of casts:
    under jit XLA folds f32 -> bf16 -> f32 away (xla_allow_excess_precision)
    and the parts after the first came out 0 (PERF.md, PR 51)."""
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), F32)


def _split(v, parts):
    """`parts` bf16 values that add up to v: all of a bf16 v in one, all 24
    bits of an fp32 v in three."""
    if parts == 1:
        return [v.astype(_BF16)]
    out, rest = [], v.astype(F32)
    for _ in range(parts):
        top = _top(rest)
        out.append(top.astype(_BF16))
        rest = rest - top
    return out


def _phi_columns(phi, groups):
    """[n C, 128] bf16: the parts of Phi [n C, N] named by `groups` side by
    side, zeros after them."""
    p = _split(phi, 3)
    cols = jnp.concatenate([p[g] for g in groups], axis=1)
    return jnp.pad(cols, ((0, 0), (0, LANES - cols.shape[1])))


# the second kernel of the backward multiplies the parts a1, a2, a3 of dm
# by the parts p1, p2, p3 of Phi: a row of groups is one pass of the MXU
# over 5 x N <= 128 contracted columns.  The first row's products are all
# down to 2^-16 of the whole but a3 p1, far under a bf16 dX's rounding; an
# fp32 dX takes the second row too (all but a3 p3, 2^-32).
_PHI_GROUPS = (0, 1, 2, 0, 1)
_DM_GROUPS = ((0, 0, 0, 1, 1), (2, 2, 1))


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=F32)


def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _over_maps_rows(m, n):
    """sum_i M[i, j] at every row i n + j of m [n^2, rows]."""
    return add_up([m] + [roll(m, d * n, 0) for d in range(1, n)])


def _over_maps_columns(m, n, j):
    """sum_j M[i, j] at every row i n + j of m; j [n^2, rows] is a row's
    place in its group of n."""
    return add_up([m] + [jnp.where(j < n - d, roll(m, -d, 0),
                                 roll(m, n - d, 0)) for d in range(1, n)])


def _place(n, rows):
    r = jax.lax.broadcasted_iota(jnp.int32, (n * n, rows), 0)
    return jax.lax.rem(r, n)


def _accumulate(x_ref, phis_ref, acc_ref, ssq_ref, parts, dout_ref=None,
                dot_ref=None):
    """A block's share of u Phi's parts (acc [rows, 128]) and of the
    squares, a lane a column of the block (ssq [rows, 128]); with
    `dout_ref` (dOut's block of the same rows and channels) the block's
    share of sum_c dOut X[j] too, the same way, in `dot_ref` [n, rows, 128]
    at the stream j the block belongs to (the streams innermost)."""
    import jax.experimental.pallas as pl

    fused = dout_ref is not None

    @pl.when(pl.program_id(2) == 0)
    def _nothing_yet():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ssq_ref[...] = jnp.zeros_like(ssq_ref)
        if fused:
            dot_ref[...] = jnp.zeros_like(dot_ref)

    acc_ref[...] += add_up(_dot(p, phis_ref[...])
                         for p in _split(x_ref[...], parts))
    rows, step = x_ref.shape[0], min(_SQUARE_ROWS, x_ref.shape[0])
    if fused:   # outside the loop: the interpreter has no program_id there
        j = jax.lax.rem(pl.program_id(2), dot_ref.shape[0])

    def squares(i, carry):
        at = pl.ds(pl.multiple_of(i * step, step), step)
        s = ssq_ref[at, :]
        if fused:
            d = dot_ref[j, at, :]
        for cols in engine.columns(x_ref.shape[1], LANES):
            v = x_ref[at, cols].astype(F32)
            s = s + v * v
            if fused:
                d = d + v * dout_ref[at, cols].astype(F32)
        ssq_ref[at, :] = s
        if fused:
            dot_ref[j, at, :] = d
        return carry

    jax.lax.fori_loop(0, rows // step, squares, 0)


def _tail(acc_ref, ssq_ref, a_ref, b_ref, geo: Maps, width, keep=None,
          dot_ref=None):
    """The maps of a tile from its accumulators, the tokens on the lanes:
    (H_pre and H_post [2n, rows], H_res [n^2, rows], what the backward
    needs besides).  `keep(i, M)` is handed the iterate before
    normalisation i and the last.  The sums of `dot_ref` [n, rows, 128]
    over their lanes ride the one transpose in n free lanes and come back
    last, [2n, rows]: dH_pre of the read, zeros under them."""
    n, N = geo.streams, _values(geo.streams)
    rows = acc_ref.shape[0]
    lane = _lane(acc_ref.shape)
    m = jnp.where(lane == 3 * N,
                  jnp.sum(ssq_ref[...], axis=-1, keepdims=True),
                  acc_ref[...])
    if dot_ref is not None:
        for s in range(n):
            m = jnp.where(lane == _spare(n) + s, _rowsum(dot_ref[s]), m)
    m = m.T
    raw = m[0:N] + m[N:2 * N] + m[2 * N:3 * N]
    rms = jax.lax.rsqrt(m[3 * N:3 * N + 1] / width + geo.epsilon)
    mm = raw * rms
    z = a_ref[...] * mm + b_ref[...]
    twice = jnp.where(jax.lax.broadcasted_iota(
        jnp.int32, (2 * n, rows), 0) < n, 1.0, 2.0)
    gates = twice * sigmoid(z[0:2 * n])
    res = jnp.exp(jnp.clip(z[2 * n:], geo.clamp_min, geo.clamp_max))
    j = _place(n, rows)

    def normalise(i, res):
        """Columns, then rows; a loop and not 20 copies of its body: the
        step's executable holds every kernel of every sublayer."""
        if keep is not None:
            keep(2 * i, res)
        res = res / (_over_maps_rows(res, n) + geo.hc_eps)
        if keep is not None:
            keep(2 * i + 1, res)
        return res / (_over_maps_columns(res, n, j) + geo.hc_eps)

    res = jax.lax.fori_loop(0, geo.iters, normalise, res)
    if keep is not None:
        keep(2 * geo.iters, res)
    return gates, res, (raw, rms, mm, z, twice, j,
                        m[_spare(n):_spare(n) + 2 * n])


def _maps_kernel(x_ref, phis_ref, a_ref, b_ref, h_ref, *refs, geo, parts,
                 width, resident=False):
    """H of a tile after its last block.  `resident` (`mhc_maps_read`'s
    forward, with x_in's block before the scratch and the tile's [blocks,
    rows, channels] after it): every block is kept as it passes and x_in
    = sum_j H_pre[j] X[j] leaves with H."""
    import jax.experimental.pallas as pl

    o_ref, acc_ref, ssq_ref, tile_ref = refs if resident else (
        None, *refs, None)
    _accumulate(x_ref, phis_ref, acc_ref, ssq_ref, parts)
    if resident:
        tile_ref[pl.program_id(2)] = x_ref[...]

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _the_maps():
        n = geo.streams
        gates, res, _ = _tail(acc_ref, ssq_ref, a_ref, b_ref, geo, width)
        h_ref[0:2 * n] = gates
        h_ref[2 * n:] = res
        if not resident:
            return
        # H_pre a token a column, through the accumulator the tail is done
        # with: the tile's second transpose
        acc_ref[...] = jnp.concatenate(
            [gates, jnp.zeros((LANES - 2 * n, gates.shape[1]), F32)]).T
        h = [_wide(acc_ref[:, j:j + 1]) for j in range(n)]
        block = x_ref.shape[1]
        for c in range(tile_ref.shape[0] // n):   # the streams innermost
            for cols in engine.columns(block, LANES):
                out = slice(c * block + cols.start, c * block + cols.stop)
                o_ref[:, out] = add_up(
                    h[j] * tile_ref[c * n + j, :, cols].astype(F32)
                    for j in range(n)).astype(o_ref.dtype)


def _fold(v):
    """[N, rows] -> [N, 128]: the lane tiles added up."""
    return add_up(v[:, c] for c in engine.columns(v.shape[1], LANES))


def _maps_bwd_tail_kernel(x_ref, phis_ref, a_ref, b_ref, dh_ref, *refs, geo,
                          parts, width, fused=False):
    """The first kernel of the backward: the tile's forward again, then
    back through the iterations, the clamp and the activations.  `fused`
    (`mhc_maps_read`, with dOut's block first among `refs` and the n sums'
    scratch last): the read's dH_pre[j] = sum_c dOut X[j] is made beside
    the squares and joins the incoming dH before the walk back."""
    import jax.experimental.pallas as pl

    dout_ref, g_ref, da_ref, db_ref, acc_ref, ssq_ref, its_ref, dot_ref = (
        refs if fused else (None, *refs, None))
    first = ((pl.program_id(0) == 0) & (pl.program_id(1) == 0)
             & (pl.program_id(2) == 0))

    @pl.when(first)
    def _no_gradient_yet():
        da_ref[...] = jnp.zeros_like(da_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    _accumulate(x_ref, phis_ref, acc_ref, ssq_ref, parts, dout_ref, dot_ref)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _back_through_the_maps():
        n, N = geo.streams, _values(geo.streams)

        def keep(i, m):
            its_ref[i] = m

        gates, _, (raw, rms, mm, z, twice, j, dpre) = _tail(
            acc_ref, ssq_ref, a_ref, b_ref, geo, width, keep, dot_ref)

        def back(t, g):
            """Through normalisation 2 i + 1 (the rows), then 2 i."""
            i = geo.iters - 1 - t
            for step, over in (
                    (2 * i + 1, functools.partial(_over_maps_columns, n=n,
                                                  j=j)),
                    (2 * i, functools.partial(_over_maps_rows, n=n))):
                before, after = its_ref[step], its_ref[step + 1]
                g = (g - over(g * after)) / (over(before) + geo.hc_eps)
            return g

        g = jax.lax.fori_loop(0, geo.iters, back, dh_ref[2 * n:])
        z_res = z[2 * n:]
        inside = (z_res >= geo.clamp_min) & (z_res <= geo.clamp_max)
        dz_res = jnp.where(inside, g * its_ref[0], 0.0)
        # d(t s)/dz = t s (1 - s) = gate (1 - gate / t); dpre: the read's
        # dH_pre, zeros where there is no read
        dz_gates = (dh_ref[0:2 * n] + dpre) * gates * (1.0 - gates / twice)
        dz = jnp.concatenate([dz_gates, dz_res], axis=0)
        da_ref[...] += _fold(dz * mm)
        db_ref[...] += _fold(dz)
        dmm = dz * a_ref[...]
        drms = jnp.sum(dmm * raw, axis=0, keepdims=True)
        dss2 = drms * (-1.0 / width) * (rms * rms * rms)   # 2 x d(ss)
        rest = dmm * rms
        for p in range(3):
            piece = _top(rest)
            g_ref[p * N:(p + 1) * N] = piece
            rest = rest - piece
        g_ref[3 * N:_spare(n)] = jnp.broadcast_to(
            dss2, (_spare(n) - 3 * N,) + dss2.shape[1:])
        # H_pre and H_post again, for the second kernel's H_pre dOut
        g_ref[_spare(n):_spare(n) + 2 * n] = gates
        g_ref[_spare(n) + 2 * n:] = jnp.zeros(
            (g_ref.shape[0] - _spare(n) - 2 * n, g_ref.shape[1]), F32)


def _maps_bwd_stream_kernel(x_ref, g_ref, small_ref, *refs, n, parts,
                            fused=False):
    """The second: dX = dm Phi^T + 2 u dss (+ H_pre[j] dOut, `fused`, with
    dOut's block after dm's among `refs`) and dPhi^T += dm^T u, a column of
    128 lanes at a time, the n streams of it one after the other."""
    import jax.experimental.pallas as pl

    phit_refs, refs = refs[:n], refs[n:]
    lhs_refs, refs = refs[:(parts + 1) // 2], refs[(parts + 1) // 2:]
    dout_ref, dx_ref, *dphit_refs = refs if fused else (None, *refs)
    N = dphit_refs[0].shape[0]

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _no_gradient_yet():
        for ref in dphit_refs:
            ref[...] = jnp.zeros_like(ref)

    # the three parts of dm^T (and the rows of dss after them, which no
    # one reads back): bf16 values in fp32, so the cast drops nothing
    dmt = g_ref[0:_spare(n)].astype(_BF16)
    lhs = [ref[...] for ref in lhs_refs]
    dss2 = _wide(small_ref[:, 0:1])
    at = _spare(n) - 3 * N   # H_pre's columns, after those of dss
    if fused:
        pre = [_wide(small_ref[:, at + j:at + j + 1]) for j in range(n)]
    for cols in engine.columns(x_ref.shape[2], LANES):
        if fused:
            g = dout_ref[:, cols].astype(F32)
        for j in range(n):
            x = x_ref[j, :, cols]
            phit = phit_refs[j][:, cols]
            du = add_up(_dot(l, phit) for l in lhs) + dss2 * x.astype(F32)
            if fused:
                du = du + pre[j] * g
            dx_ref[j, :, cols] = du.astype(dx_ref.dtype)
            dp = add_up(_dot(dmt, p) for p in _split(x, parts))
            dphit_refs[j][:, cols] += dp[0:N] + dp[N:2 * N] + dp[2 * N:3 * N]


def _wide(column):
    """A map value a token [rows, 1] spread over the lanes, once a tile:
    on the chip a loop over blocks of 16 rows that kept the spread values
    in registers lost to this (`write` 0.54 / 0.81 -> 0.73 / 0.94 ms a
    sublayer, tools/mhc_probe.py, PERF.md PR 51)."""
    return jnp.broadcast_to(column, (column.shape[0], LANES))


def _chosen(h_ref, ks, which):
    """The column of H^T that `which` (a grid index) names among `ks`,
    spread over the lanes."""
    return _wide(add_up((which == i).astype(F32) * h_ref[:, k:k + 1]
                      for i, k in enumerate(ks)))


def _rowsum(v):
    return jnp.sum(v, axis=-1, keepdims=True)


def _read_kernel(h_ref, *refs, n):
    xs, o_ref = refs[:n], refs[n]
    h = [_wide(h_ref[:, j:j + 1]) for j in range(n)]
    for cols in engine.columns(o_ref.shape[1], LANES):
        o_ref[:, cols] = add_up(h[j] * xs[j][:, cols].astype(F32)
                              for j in range(n)).astype(o_ref.dtype)


def _no_map_gradient_yet(dh_ref):
    import jax.experimental.pallas as pl

    @pl.when((pl.program_id(2) == 0) & (pl.program_id(3) == 0))
    def _zero():
        dh_ref[...] = jnp.zeros_like(dh_ref)


def _into_columns(dh_ref, columns, parts):
    """dH's `columns` += the sums of `parts` over their lanes."""
    lane = _lane(parts[0].shape)
    dh_ref[...] += add_up(jnp.where(lane == k, _rowsum(p), 0.0)
                        for k, p in zip(columns, parts))


def _write_kernel(h_ref, *refs, n):
    """Stream s: X'[s] = sum_j H_res[s, j] X[j] + H_post[s] y."""
    import jax.experimental.pallas as pl

    xs, y_ref, o_ref = refs[:n], refs[n], refs[n + 1]
    s = pl.program_id(3)
    res = [_chosen(h_ref, [2 * n + i * n + j for i in range(n)], s)
           for j in range(n)]
    post = _chosen(h_ref, range(n, 2 * n), s)
    for cols in engine.columns(o_ref.shape[1], LANES):
        o_ref[:, cols] = (
            add_up(res[j] * xs[j][:, cols].astype(F32) for j in range(n))
            + post * y_ref[:, cols].astype(F32)).astype(o_ref.dtype)


def _write_bwd_kernel(h_ref, x_ref, *refs, n):
    """Stream s: dX[s] = sum_i H_res[i, s] g[i] and dH_res[i, s] += sum_c
    g[i] X[s]; with the first stream dy = sum_i H_post[i] g[i] and
    dH_post[i] += sum_c g[i] y."""
    import jax.experimental.pallas as pl

    gs, y_ref = refs[:n], refs[n]
    dx_ref, dy_ref, dh_ref = refs[n + 1:]
    s = pl.program_id(3)
    _no_map_gradient_yet(dh_ref)

    def both(coefficient, other_ref, out_ref, columns):
        """out = sum_i coefficient[i] g[i]; dH's `columns` += sum_c g[i]
        other."""
        parts = [jnp.zeros(coefficient[0].shape, F32)] * n
        for cols in engine.columns(x_ref.shape[1], LANES):
            other = other_ref[:, cols].astype(F32)
            g = [ref[:, cols].astype(F32) for ref in gs]
            out_ref[:, cols] = add_up(
                coefficient[i] * g[i] for i in range(n)).astype(out_ref.dtype)
            parts = [parts[i] + g[i] * other for i in range(n)]
        _into_columns(dh_ref, columns, parts)

    both([_chosen(h_ref, [2 * n + i * n + j for j in range(n)], s)
          for i in range(n)],
         x_ref, dx_ref, [2 * n + i * n + s for i in range(n)])

    @pl.when(s == 0)
    def _the_sublayers_output():
        both([_wide(h_ref[:, n + i:n + i + 1]) for i in range(n)],
             y_ref, dy_ref, [n + i for i in range(n)])


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------
def _jitted(kernel, **kw):
    """Memoized by the callers and jitted, as kernels/kda_mix.py's calls:
    the sites after the first find the kernel's body traced and lowered."""
    import jax.experimental.pallas as pl

    return jax.jit(pl.pallas_call(kernel, **kw))


def _scratch(*shapes):
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM(s, F32) for s in shapes]


def _stream_block(T, Cb, where):
    """A [T, Cb] block of the streams [B, n, S, C]; `where` gives (sequence,
    stream, tile of rows, block of channels) from the grid's indices."""
    import jax.experimental.pallas as pl

    return pl.BlockSpec((None, None, T, Cb), where)


def _maps_specs(B, S, n, C, N, tiles):
    """(grid, x, phis, small, h, one): the block specs on the grid
    (sequence, tile of rows, block of channels x stream: the streams
    innermost, so that a [B, S, C] value's block `one` stays while the n
    streams' blocks of its channels pass)."""
    import jax.experimental.pallas as pl

    T, Kb = tiles.rows, tiles.channels
    per = C // Kb
    return ((B, S // T, n * per),
            _stream_block(T, Kb, lambda b, i, k: (b, k % n, i, k // n)),
            pl.BlockSpec((Kb, LANES),
                         lambda b, i, k: (k % n * per + k // n, 0)),
            pl.BlockSpec((N, 1), lambda b, i, k: (0, 0)),
            pl.BlockSpec((None, N, T), lambda b, i, k: (b, 0, i)),
            pl.BlockSpec((None, T, Kb), lambda b, i, k: (b, i, k // n)))


@functools.lru_cache(maxsize=64)
def _maps_fwd_call(B, S, C, geo, tiles, dtype, interpret, resident=False):
    """The maps' forward kernel; `resident`: `mhc_maps_read`'s, which keeps
    the tile and writes x_in [B, S, C] too."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, N, T = geo.streams, _values(geo.streams), tiles.rows
    grid, x, phis, small, h, _ = _maps_specs(B, S, n, C, N, tiles)
    maps = jax.ShapeDtypeStruct((B, N, S), F32)
    held = [pltpu.VMEM((grid[2], T, tiles.channels), jnp.dtype(dtype))]
    return _jitted(
        functools.partial(_maps_kernel, geo=geo, parts=_parts(dtype),
                          width=n * C, resident=resident),
        grid=grid, in_specs=[x, phis, small, small],
        out_specs=[h, pl.BlockSpec((None, T, C), lambda b, i, k: (b, i, 0))]
        if resident else h,
        out_shape=[maps, jax.ShapeDtypeStruct((B, S, C), jnp.dtype(dtype))]
        if resident else maps,
        scratch_shapes=_scratch((T, LANES), (T, LANES))
        + (held if resident else []),
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary"), tiles.fwd_vmem_bytes),
        interpret=interpret)


@functools.lru_cache(maxsize=64)
def _maps_bwd_tail_call(B, S, C, geo, tiles, dtype, interpret, fused=False):
    import jax.experimental.pallas as pl

    n, N, T = geo.streams, _values(geo.streams), tiles.rows
    grid, x, phis, small, h, one = _maps_specs(B, S, n, C, N, tiles)
    sums = pl.BlockSpec((N, LANES), lambda b, i, k: (0, 0))
    return _jitted(
        functools.partial(_maps_bwd_tail_kernel, geo=geo,
                          parts=_parts(dtype), width=n * C, fused=fused),
        grid=grid,
        in_specs=[x, phis, small, small, h] + ([one] if fused else []),
        out_specs=[pl.BlockSpec((None, LANES, T), lambda b, i, k: (b, 0, i)),
                   sums, sums],
        out_shape=[jax.ShapeDtypeStruct((B, LANES, S), F32)]
        + [jax.ShapeDtypeStruct((N, LANES), F32)] * 2,
        scratch_shapes=_scratch(
            (T, LANES), (T, LANES), (2 * geo.iters + 1, n * n, T),
            *([(n, T, LANES)] if fused else [])),
        compiler_params=compiler_params(
            ("arbitrary",) * 3, tiles.bwd_vmem_bytes),
        interpret=interpret)


@functools.lru_cache(maxsize=64)
def _maps_bwd_stream_call(B, S, n, C, tiles, dtype, interpret, fused=False):
    """Channel blocks outermost (dPhi^T's blocks stay over the rows), all
    n streams of a block in one grid step (dOut's block is read once for
    the n of them)."""
    import jax.experimental.pallas as pl

    N, T, like = _values(n), tiles.rows, jnp.dtype(dtype)
    Kb = stream_channels(T, tiles.channels, n, C, like.itemsize, fused)
    per = C // Kb
    tile = pl.BlockSpec((None, n, T, Kb), lambda c, b, i: (b, 0, i, c))
    wide = pl.BlockSpec((None, T, LANES), lambda c, b, i: (b, i, 0))

    def phit(j):
        return pl.BlockSpec((LANES, Kb), lambda c, b, i: (0, j * per + c))

    return _jitted(
        functools.partial(_maps_bwd_stream_kernel, n=n,
                          parts=_parts(dtype), fused=fused),
        grid=(per, B, S // T),
        in_specs=[tile,
                  pl.BlockSpec((None, LANES, T), lambda c, b, i: (b, 0, i)),
                  pl.BlockSpec((None, T, _spare(n) - 3 * N + 2 * n),
                               lambda c, b, i: (b, i, 0))]
        + [phit(j) for j in range(n)]
        + [wide] * ((_parts(dtype) + 1) // 2)
        + ([pl.BlockSpec((None, T, Kb), lambda c, b, i: (b, i, c))]
           if fused else []),
        out_specs=[tile] + [pl.BlockSpec((N, Kb), lambda c, b, i: (0, c))] * n,
        out_shape=[jax.ShapeDtypeStruct((B, n, S, C), like)]
        + [jax.ShapeDtypeStruct((N, C), F32)] * n,
        compiler_params=compiler_params(
            ("parallel", "arbitrary", "arbitrary"), tiles.bwd_vmem_bytes),
        interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _maps(x, phi, a, b, geo: Maps, tiles: Tiles, interpret: bool = False):
    """H [B, N, S] of a site `maps_tiles` tiled: x [B, n, S, C], Phi [n C,
    N], a and b [N, 1] (a row's scalar and bias), all but x fp32."""
    B, _, S, C = x.shape
    return _maps_fwd_call(B, S, C, geo, tiles, str(x.dtype), interpret)(
        x, _phi_columns(phi, (0, 1, 2)), a, b)


def _maps_fwd(x, phi, a, b, geo, tiles, interpret):
    return _maps(x, phi, a, b, geo, tiles, interpret), (x, phi, a, b)


def _maps_bwd(geo, tiles, interpret, inputs, dh, dout=None):
    """The gradients of x, Phi, a and b from H's cotangent `dh` and, of a
    `mhc_maps_read` site, x_in's `dout` [B, S, C] besides: the same two
    kernels, one dX."""
    x, phi, a, b = inputs
    (B, n, S, C), N = x.shape, _values(geo.streams)
    fused, dtype = dout is not None, str(x.dtype)
    extra = [dout.astype(x.dtype)] if fused else []
    g, da, db = _maps_bwd_tail_call(B, S, C, geo, tiles, dtype, interpret,
                                    fused)(
        x, _phi_columns(phi, (0, 1, 2)), a, b, dh.astype(F32), *extra)
    by_token = jnp.swapaxes(g, 1, 2)
    pieces = [by_token[..., p * N:(p + 1) * N] for p in range(3)]
    lhs = [jnp.pad(jnp.concatenate([pieces[p] for p in row], axis=-1),
                   ((0, 0), (0, 0), (0, LANES - N * len(row)))).astype(_BF16)
           for row in _DM_GROUPS[:(_parts(x.dtype) + 1) // 2]]
    phit = _phi_columns(phi, _PHI_GROUPS).T
    dx, *dphit = _maps_bwd_stream_call(B, S, n, C, tiles, dtype, interpret,
                                       fused)(
        x, g, by_token[..., 3 * N:_spare(n) + 2 * n], *[phit] * n, *lhs,
        *extra)
    return (dx, jnp.concatenate(dphit, axis=1).T.astype(phi.dtype),
            _rowsum(da).astype(a.dtype), _rowsum(db).astype(b.dtype))


_maps.defvjp(_maps_fwd, _maps_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _maps_read(x, phi, a, b, geo: Maps, tiles: FusedTiles,
               interpret: bool = False):
    """(H [B, N, S], x_in [B, S, C]) of a site `maps_read_tiles` tiled,
    `_maps`' operands: one kernel where the forward holds the tile, the
    maps' and then `read`'s where it does not."""
    (B, n, S, C), dtype = x.shape, str(x.dtype)
    phis = _phi_columns(phi, (0, 1, 2))
    if tiles.resident:
        return tuple(_maps_fwd_call(B, S, C, geo, tiles, dtype, interpret,
                                    True)(x, phis, a, b))
    h = _maps_fwd_call(B, S, C, geo, tiles, dtype, interpret)(x, phis, a, b)
    return h, _read_fwd_call(B, S, C, n, mix_tiles(S, n, C, x.dtype, "read"),
                             dtype, interpret)(_by_token(h), *[x] * n)


def _maps_read_fwd(x, phi, a, b, geo, tiles, interpret):
    return _maps_read(x, phi, a, b, geo, tiles, interpret), (x, phi, a, b)


def _maps_read_bwd(geo, tiles, interpret, inputs, cotangents):
    return _maps_bwd(geo, tiles, interpret, inputs, *cotangents)


_maps_read.defvjp(_maps_read_fwd, _maps_read_bwd)


def _mix_specs(B, S, C, n, N, tiles, streams_axis):
    """(grid, h, one, wide, at(j)): the block specs of H^T, of a [B, S, C]
    value's block, of a token's 128 map gradients and of stream j's block
    (j None: the stream the grid's last axis names) of the streams [B, n,
    S, C], on the grid (sequence, tile of rows, block of channels[,
    stream])."""
    import jax.experimental.pallas as pl

    T, Cb = tiles.rows, tiles.channels

    def at(j):
        if j is None:
            return _stream_block(T, Cb, lambda b, i, c, s: (b, s, i, c))
        return _stream_block(T, Cb, lambda b, i, c, *s: (b, j, i, c))

    grid = (B, S // T, C // Cb) + ((n,) if streams_axis else ())
    return (grid, pl.BlockSpec((None, T, N), lambda b, i, c, *s: (b, i, 0)),
            pl.BlockSpec((None, T, Cb), lambda b, i, c, *s: (b, i, c)),
            pl.BlockSpec((None, T, LANES), lambda b, i, c, *s: (b, i, 0)),
            at)


@functools.lru_cache(maxsize=64)
def _read_fwd_call(B, S, C, n, tiles, dtype, interpret):
    grid, h, one, _, at = _mix_specs(B, S, C, n, _values(n), tiles, False)
    return _jitted(
        functools.partial(_read_kernel, n=n), grid=grid,
        in_specs=[h] + [at(j) for j in range(n)], out_specs=one,
        out_shape=jax.ShapeDtypeStruct((B, S, C), jnp.dtype(dtype)),
        compiler_params=compiler_params(
            ("parallel",) * 3, tiles.fwd_vmem_bytes),
        interpret=interpret)


_ONE_STREAM_A_STEP = ("parallel", "parallel", "arbitrary", "arbitrary")


@functools.lru_cache(maxsize=64)
def _write_fwd_call(B, S, C, n, tiles, dtype, interpret):
    grid, h, one, _, at = _mix_specs(B, S, C, n, _values(n), tiles, True)
    return _jitted(
        functools.partial(_write_kernel, n=n), grid=grid,
        in_specs=[h] + [at(j) for j in range(n)] + [one],
        out_specs=at(None),
        out_shape=jax.ShapeDtypeStruct((B, n, S, C), jnp.dtype(dtype)),
        compiler_params=compiler_params(
            _ONE_STREAM_A_STEP, tiles.fwd_vmem_bytes),
        interpret=interpret)


@functools.lru_cache(maxsize=64)
def _write_bwd_call(B, S, C, n, tiles, dtype, interpret):
    grid, h, one, wide, at = _mix_specs(B, S, C, n, _values(n), tiles, True)
    like = jnp.dtype(dtype)
    return _jitted(
        functools.partial(_write_bwd_kernel, n=n), grid=grid,
        in_specs=[h, at(None)] + [at(i) for i in range(n)] + [one],
        out_specs=[at(None), one, wide],
        out_shape=[jax.ShapeDtypeStruct((B, n, S, C), like),
                   jax.ShapeDtypeStruct((B, S, C), like),
                   jax.ShapeDtypeStruct((B, S, LANES), F32)],
        compiler_params=compiler_params(
            _ONE_STREAM_A_STEP, tiles.bwd_vmem_bytes),
        interpret=interpret)


def _by_token(h):
    return jnp.swapaxes(h, 1, 2)


def _by_value(dht, h):
    """dH [B, N, S] of the kernels' [B, S, 128]."""
    return jnp.swapaxes(dht[..., :h.shape[1]], 1, 2).astype(h.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _write(x, h, y, n: int, tiles: Tiles, interpret: bool = False):
    """X' [B, n, S, C] of a site `mix_tiles` tiled; y [B, S, C] in x's
    dtype."""
    B, _, S, C = x.shape
    return _write_fwd_call(B, S, C, n, tiles, str(x.dtype), interpret)(
        _by_token(h), *[x] * n, y)


def _write_fwd(x, h, y, n, tiles, interpret):
    return _write(x, h, y, n, tiles, interpret), (x, h, y)


def _write_bwd(n, tiles, interpret, inputs, g):
    x, h, y = inputs
    B, _, S, C = x.shape
    g = g.astype(x.dtype)
    dx, dy, dht = _write_bwd_call(B, S, C, n, tiles, str(x.dtype),
                                  interpret)(_by_token(h), x, *[g] * n, y)
    return dx, _by_value(dht, h), dy


_write.defvjp(_write_fwd, _write_bwd)


# ---------------------------------------------------------------------------
# the three pairs, on the ops' own arguments
# ---------------------------------------------------------------------------
def _by_stream(x):
    """[B, S, n, C] <-> [B, n, S, C]: the kernels' view of the streams, a
    stream's [S, C] plane under the last two axes.  A transposition in
    name: the streams live between these ops alone, so the compiler lays
    the [B, S, n, C] value out stream-major and this is a bitcast; as rows
    of n C it cannot be one under the TPU's tiled layouts, and every op
    paid a copy of the streams each way (PERF.md, PR 51)."""
    return jnp.swapaxes(x, 1, 2)


def _maps_operands(x, phi, a_pre, a_post, a_res, b_pre, b_post, b_res, *,
                   epsilon, hc_eps, iters, clamp):
    """`_maps`' and `_maps_read`'s operands of the ops' own."""
    def column(values):
        return jnp.concatenate(values).astype(F32).reshape(-1, 1)

    biases = [b.reshape(-1) for b in (b_pre, b_post, b_res)]
    scalars = [jnp.broadcast_to(a.reshape(1), b.shape)
               for a, b in zip((a_pre, a_post, a_res), biases)]
    geo = Maps(x.shape[2], float(epsilon), float(hc_eps), int(iters),
               float(clamp[0]), float(clamp[1]))
    return (_by_stream(x), phi.astype(F32), column(scalars),
            column(biases), geo)


def maps(x, phi, a_pre, a_post, a_res, b_pre, b_post, b_res, tiles: Tiles,
         interpret: bool = False, **cfg):
    """ops/hyper_connection_ops.py::maps' H by the kernel pair, of a site
    `maps_tiles` tiled (`tiles`); `interpret` runs the pair in the Pallas
    interpreter."""
    return _maps(*_maps_operands(x, phi, a_pre, a_post, a_res, b_pre, b_post,
                                 b_res, **cfg), tiles, interpret)


def maps_read(x, phi, a_pre, a_post, a_res, b_pre, b_post, b_res,
              tiles: FusedTiles, interpret: bool = False, **cfg):
    """(ops/hyper_connection_ops.py::maps' H, ::read's x_in under it) by
    one kernel pair, of a site `maps_read_tiles` tiled."""
    return _maps_read(*_maps_operands(x, phi, a_pre, a_post, a_res, b_pre,
                                      b_post, b_res, **cfg), tiles, interpret)


def write(x, h, y, tiles: Tiles, interpret: bool = False):
    """ops/hyper_connection_ops.py::write's X' by the kernel pair, of a
    site `mix_tiles` tiled; x and y in one dtype."""
    return _by_stream(_write(_by_stream(x), h.astype(F32), y, x.shape[2],
                             tiles, interpret))
