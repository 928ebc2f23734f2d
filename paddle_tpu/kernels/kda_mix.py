"""What streams [S, H D] values around Kimi Delta Attention's scan (the ops
kda_conv_decay and kda_gated_norm, name scope `kda.mix`) as two Pallas TPU
kernel pairs over tiles of rows.

ops/linear_attention_ops.py::conv_decay and ::gated_norm are the
arithmetic, in jax.numpy: before the scan three causal depthwise
convolutions with SiLU over the projections q~, k~, v~ and the decay g =
-exp(A_log) softplus(f + dt_bias); after it a norm a head of the scan's
output times sigmoid(gate + gate_bias).  There every stage is a pass over
fp32 [S, H D] values in HBM, the backward keeps the convolutions' fp32
pre-activations, and the norm's statistic is broadcast to fp32 [S, H D]
once it reads a kernel's output: at [1, 4096, 4096] the scope took 22.5 ms
a step for ~5 ms of traffic (PERF.md, PR 48).

Here a grid step holds a tile of rows x a block of channels of the streams
as the projections leave them ([B, S, H D], nothing regrouped) and works a
column of 128 lanes (a head, after the scan) at a time on fp32 values in
VMEM:
- before the scan: the convolution is depthwise, so channels block
  freely; the tile's `halo` is an aligned block of the rows before it
  (zeros at the first tile); the taps are sublane rolls times a row of
  weights; q', k', v leave in the inputs' dtype, g in fp32;
- after the scan: a head's statistic over its lanes in the tile; no
  [S, H D] broadcast of it exists anywhere.
The backward (jax.custom_vjp; the residuals are the ops' INPUTS) makes the
tile's forward again in VMEM.  The transposed convolution reaches k - 1
rows ahead: a step reads a halo on both sides (inputs and cotangents of
the block after the tile; nothing after the last), so no tile waits for
another.  The parameters' gradients accumulate in fp32 in output blocks
resident over the batch and the rows, a row of C each: the head's rate
and the norm's scale are handed in as such rows, and jax differentiates
the few operations that make them outside.

The same design serves what streams around the other recurrent mixers:
`short_conv`, the op short_conv1d's ONE causal depthwise convolution with a
bias and an activation (silu | identity; Gated DeltaNet's q | k | v,
Mamba's x and Mamba-2's x | B | C), is the pair before the scan for one
stream with a bias and no decay, and the pair after the scan takes the
gate's rule as a static argument: sigmoid or SiLU, of the gate with or
without a bias row (Gated DeltaNet's: silu, none).

`conv_tiles`, `short_conv_tiles` and `norm_tiles` read the tile from the
shape and the VMEM it needs, or say that the shape does not tile; the ops
ask kernels/engine.py whether a site runs these pairs at all
(ops/linear_attention_ops.py) and run their jax.numpy form where it does
not.  tools/kda_mix_probe.py times the pairs alone on the chip.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import engine
from .engine import (F32, LANES, add_up, back, columns, compiler_params, roll,
                     sigmoid)

__all__ = ["Tiles", "conv_tiles", "short_conv_tiles", "norm_tiles",
           "conv_decay", "short_conv", "SHORT_CONV_ACTS", "gated_norm",
           "conv_moved_bytes", "short_conv_moved_bytes", "norm_moved_bytes"]

# The tiles `conv_tiles` / `norm_tiles` try: the widest block of channels
# first, then the most rows that fit beside it.  On the chip at the cell's
# shape (tools/kda_mix_probe.py --sweep, ms a layer forward / backward,
# PERF.md PR 49) the pair before the scan reads 0.83 / 1.17 at 128 rows x
# 128 channels, 0.64 / 0.95 x 256, 0.53 / 0.83 x 512, 0.50 / 0.79 x 1024,
# and 0.66 / 1.01, 0.55 / 0.92, 0.49 / 0.87 at 256 rows x 128, 256, 512
# (x 1024 is past the budget); the pair after it 0.44 / 0.59 at 128 x 128
# down to 0.17 / 0.26 at 256 x 2048: a wider block is a longer contiguous
# run a row for the DMA and fewer grid steps, and counts for more than the
# rows do.  A column's value is [rows + halo, 128] fp32, 34 vregs at 256
# rows, which chains of elementwise operations keep in registers
# (kernels/cca_mix.py's sweep, PERF.md PR 45): no tile above 256 rows.
_ROWS = (256, 128)
_CHANNELS = (2048, 1024, 512, 256, 128)
# the activations `short_conv` has a kernel for
SHORT_CONV_ACTS = ("identity", "silu")
# `short_conv_tiles`' widest block of channels.  At [8192, 8192] bf16, four
# taps (tools/kda_mix_probe.py --shapes qwen3next --sweep, ms a layer
# forward / backward, PERF.md PR 67) the one-stream pair reads 1.50 / 1.95
# at 128 rows x 128 channels, 1.04 / 1.41 x 256, 0.78 / 1.08 x 512, 0.60 /
# 0.92 x 1024, 0.52 / 0.80 x 2048, 0.48 / 0.78 x 4096; 1.05 / 1.43, 0.81 /
# 1.14, 0.63 / 0.95, 0.53 / 0.92, 0.49 / 0.90 at 256 rows x 128 ... 2048;
# 512 rows never win (0.59 / 1.13 x 512).  XLA's passes: 2.24 / 7.40.  A
# block of 4096 fits (one stream's working set is a quarter of the four
# streams') and is 8% faster than 2048, 0.2% of the cell's step; it is not
# taken because a body unrolls a column for every 128 channels and a step
# pays for its kernels' bodies in `setup_s`: at 4096 the cell's warm
# set-up read +7% (bound 10%), `setup_trace_lower_s.train` 23.3 -> 28.1.
_SHORT_WIDEST = 2048


class Tiles(NamedTuple):
    """What a site's kernel pair is built from, all read from the shape."""
    rows: int
    channels: int
    halo: int          # rows of the block before (and after) a tile; 0: none
    fwd_vmem_bytes: int
    bwd_vmem_bytes: int


def conv_working_set(rows, channels, halo, taps, size, backward) -> int:
    """What a grid step of the kernels before the scan holds in VMEM: the
    declared blocks twice (the pipeline's two buffers) and the fp32
    temporaries of the column in flight."""
    tile, edge = rows * channels, halo * channels
    params = (3 * taps + 2) * channels * 4
    if backward:
        blocks = (4 + 3 + 4) * tile * size + tile * 4 + 9 * edge * size
        live = 20 * (rows + 2 * halo) * LANES * 4
        return 2 * (blocks + 2 * params) + live
    blocks = (4 + 3) * tile * size + tile * 4 + 3 * edge * size
    return 2 * (blocks + params) + 12 * (rows + halo) * LANES * 4


def short_conv_working_set(rows, channels, halo, taps, size, backward) -> int:
    """The same for the one-stream pair: X, the cotangent and dX (forward:
    X and Out), a halo block of X on either side and one of the cotangent,
    the filter's rows and the bias row."""
    tile, edge = rows * channels, halo * channels
    params = (taps + 1) * channels * 4
    if backward:
        return (2 * ((3 * tile + 3 * edge) * size + 2 * params)
                + 20 * (rows + 2 * halo) * LANES * 4)
    return (2 * ((2 * tile + edge) * size + params)
            + 12 * (rows + halo) * LANES * 4)


def norm_working_set(rows, channels, head_dim, size, backward) -> int:
    """The same for the kernels after the scan."""
    tile = rows * channels
    blocks = (5 if backward else 3) * tile * size
    params = (4 if backward else 2) * channels * 4
    return 2 * (blocks + params) + 12 * rows * head_dim * 4


def _widest(seq, width, unit, need, rows, channels, candidates=_CHANNELS):
    """engine.widest over `candidates` x `_ROWS`; `rows` / `channels` pin
    either for a test or the probe, never a model."""
    return engine.widest(seq, width, unit, need,
                         _ROWS if rows is None else (rows,),
                         candidates if channels is None else (channels,))


def _halo_tiles(working_set, candidates, seq, width, taps, dtype, rows,
                channels) -> Optional[Tiles]:
    """The tiles of a convolution's site by its `working_set` count over
    the `candidates` blocks of channels."""
    halo, size = engine.halo_rows(dtype), jnp.dtype(dtype).itemsize
    if width % LANES or not 0 <= taps - 1 <= 8:
        return None

    def need(r, c, backward=True):
        return working_set(r, c, halo, taps, size, backward)

    found = _widest(seq, width, LANES, need, rows, channels, candidates)
    if found is None or found[0] % halo:
        return None
    return Tiles(*found, halo, need(*found, False), need(*found))


def conv_tiles(seq, width, taps, dtype, rows=None, channels=None
               ) -> Optional[Tiles]:
    """The tiles of a site before the scan, None where the shape does not
    tile: channels whole 128-lane vectors, the taps' reach within a halo
    block, S whole tiles of rows whose working set fits."""
    return _halo_tiles(conv_working_set, _CHANNELS, seq, width, taps, dtype,
                       rows, channels)


def short_conv_tiles(seq, width, taps, dtype, rows=None, channels=None
                     ) -> Optional[Tiles]:
    """The same rule for a short_conv1d site and its one stream's working
    set; the blocks it tries are the width's own divisors in whole lane
    vectors, widest first (2304 = 9 x 256 channels has no wide block among
    the powers of two)."""
    candidates = tuple(c for c in range(min(width, _SHORT_WIDEST), 0, -LANES)
                       if width % c == 0)
    return _halo_tiles(short_conv_working_set, candidates, seq, width, taps,
                       dtype, rows, channels)


def norm_tiles(seq, width, head_dim, dtype, rows=None, channels=None
               ) -> Optional[Tiles]:
    """The tiles of a site after the scan, None where the shape does not
    tile: heads whole 128-lane vectors, a block whole heads, S whole tiles
    of rows whose working set fits."""
    size = jnp.dtype(dtype).itemsize
    if head_dim % LANES or width % head_dim:
        return None

    def need(r, c, backward=True):
        return norm_working_set(r, c, head_dim, size, backward)

    found = _widest(seq, width, head_dim, need, rows, channels)
    if found is None or found[0] % engine.halo_rows(dtype):
        return None
    return Tiles(*found, 0, need(*found, False), need(*found))


def _passes(forward, backward, recomputed):
    return forward * (2 if recomputed else 1) + backward


def conv_moved_bytes(q, f, recomputed: bool) -> int:
    """What a site's passes before the scan have to move through HBM: the
    forward reads q~, k~, v~ and f and writes q', k', v and g (fp32), a
    second time where the unit around the site is rematerialised; the
    backward reads the four inputs and the four cotangents and writes
    four."""
    x, z, g = (int(q.size) * q.dtype.itemsize, int(f.size) * f.dtype.itemsize,
               int(f.size) * 4)
    return _passes(6 * x + z + g, 9 * x + 2 * z + g, recomputed)


def short_conv_moved_bytes(x, recomputed: bool) -> int:
    """The same for a short_conv1d site: the forward reads X and writes
    Out; the backward reads X and the cotangent and writes dX."""
    size = int(x.size) * x.dtype.itemsize
    return _passes(2 * size, 3 * size, recomputed)


def norm_moved_bytes(o, gate, recomputed: bool) -> int:
    """The same after the scan: the forward reads o and the gate and writes
    one; the backward reads those and the cotangent and writes two."""
    x, z = (int(t.size) * t.dtype.itemsize for t in (o, gate))
    return _passes(2 * x + z, 3 * x + 2 * z, recomputed)


# ---------------------------------------------------------------------------
# what the kernels compute, on [rows, 128 or D] fp32 values of one column
# ---------------------------------------------------------------------------
def _ahead(x, steps):
    """y[e] = x[e + steps]; the last `steps` rows wrap and are never
    read."""
    return roll(x, -steps, 0)


def _total(x):
    return jnp.sum(x, axis=0, keepdims=True)


def _softplus(x):
    """jax.nn.softplus's own form: logaddexp(x, 0)."""
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _taps(w_ref, cols, taps):
    return [w_ref[j:j + 1, cols] for j in range(taps)]


def _convolve(x, w):
    """y[e] = sum_j w[j] x[e - (k - 1 - j)] over the rows of x."""
    k = len(w)
    return add_up(back(x, k - 1 - j) * w[j] for j in range(k))


def _conv_decay_kernel(q_ref, k_ref, v_ref, f_ref, qb_ref, kb_ref, vb_ref,
                       wq_ref, wk_ref, wv_ref, dt_ref, rate_ref,
                       qo_ref, ko_ref, vo_ref, g_ref, *, taps, halo):
    import jax.experimental.pallas as pl

    # 0 at the first tile: nothing lies before position 0
    seen = 1.0 - (pl.program_id(2) == 0).astype(F32)
    streams = ((q_ref, qb_ref, wq_ref, qo_ref), (k_ref, kb_ref, wk_ref, ko_ref),
               (v_ref, vb_ref, wv_ref, vo_ref))
    for cols in columns(q_ref.shape[-1], LANES):
        for x_ref, before_ref, w_ref, o_ref in streams:
            x = jnp.concatenate(
                [before_ref[0, :, cols].astype(F32) * seen,
                 x_ref[0, :, cols].astype(F32)], 0)
            y = _convolve(x, _taps(w_ref, cols, taps))[halo:]
            o_ref[0, :, cols] = (y * sigmoid(y)).astype(o_ref.dtype)
        z = f_ref[0, :, cols].astype(F32) + dt_ref[:, cols]
        g_ref[0, :, cols] = rate_ref[:, cols] * _softplus(z)


def _conv_decay_bwd_kernel(q_ref, k_ref, v_ref, f_ref, qb_ref, kb_ref, vb_ref,
                           qa_ref, ka_ref, va_ref, gq_ref, gk_ref, gv_ref,
                           gg_ref, gqa_ref, gka_ref, gva_ref,
                           wq_ref, wk_ref, wv_ref, dt_ref, rate_ref,
                           dq_ref, dk_ref, dv_ref, df_ref,
                           dwq_ref, dwk_ref, dwv_ref, ddt_ref, drate_ref,
                           *, taps, halo):
    import jax.experimental.pallas as pl

    step = pl.program_id(2)
    seen = 1.0 - (step == 0).astype(F32)
    # 0 at the last tile: no row after it hands a cotangent back
    more = 1.0 - (step == pl.num_programs(2) - 1).astype(F32)
    tile = q_ref.shape[1]
    own = slice(halo, halo + tile)

    @pl.when((step == 0) & (pl.program_id(1) == 0))
    def _no_gradient_yet():
        for ref in (dwq_ref, dwk_ref, dwv_ref, ddt_ref, drate_ref):
            ref[...] = jnp.zeros_like(ref)

    streams = ((q_ref, qb_ref, qa_ref, gq_ref, gqa_ref, wq_ref, dq_ref,
                dwq_ref),
               (k_ref, kb_ref, ka_ref, gk_ref, gka_ref, wk_ref, dk_ref,
                dwk_ref),
               (v_ref, vb_ref, va_ref, gv_ref, gva_ref, wv_ref, dv_ref,
                dwv_ref))
    for cols in columns(q_ref.shape[-1], LANES):
        nothing = jnp.zeros((halo, LANES), F32)
        for (x_ref, before_ref, after_ref, g_ref, ga_ref, w_ref, dx_ref,
             dw_ref) in streams:
            w = _taps(w_ref, cols, taps)
            x = jnp.concatenate(
                [before_ref[0, :, cols].astype(F32) * seen,
                 x_ref[0, :, cols].astype(F32),
                 after_ref[0, :, cols].astype(F32)], 0)
            g = jnp.concatenate(
                [nothing, g_ref[0, :, cols].astype(F32),
                 ga_ref[0, :, cols].astype(F32) * more], 0)
            y = _convolve(x, w)
            s = sigmoid(y)
            dy = g * (s * (1.0 + y * (1.0 - s)))
            # the transpose reaches k - 1 rows ahead, into the block after
            dx = add_up(_ahead(dy, taps - 1 - j) * w[j] for j in range(taps))
            dx_ref[0, :, cols] = dx[own].astype(dx_ref.dtype)
            # a row of y is counted by the tile that owns it
            for j in range(taps):
                dw_ref[j:j + 1, cols] += _total(
                    (dy * back(x, taps - 1 - j))[own])
        z = f_ref[0, :, cols].astype(F32) + dt_ref[:, cols]
        gg = gg_ref[0, :, cols].astype(F32)
        dz = gg * rate_ref[:, cols] * sigmoid(z)
        df_ref[0, :, cols] = dz.astype(df_ref.dtype)
        ddt_ref[:, cols] += _total(dz)
        drate_ref[:, cols] += _total(gg * _softplus(z))


def _short_conv_kernel(x_ref, before_ref, w_ref, *refs, taps, halo, silu):
    """`_conv_decay_kernel` for ONE stream with no decay; `refs`: the bias
    row where the site has one, then Out."""
    import jax.experimental.pallas as pl

    *bias_ref, o_ref = refs
    seen = 1.0 - (pl.program_id(2) == 0).astype(F32)
    for cols in columns(x_ref.shape[-1], LANES):
        x = jnp.concatenate(
            [before_ref[0, :, cols].astype(F32) * seen,
             x_ref[0, :, cols].astype(F32)], 0)
        y = _convolve(x, _taps(w_ref, cols, taps))[halo:]
        for ref in bias_ref:
            y = y + ref[:, cols]
        o_ref[0, :, cols] = (y * sigmoid(y) if silu else y).astype(
            o_ref.dtype)


def _short_conv_bwd_kernel(x_ref, before_ref, after_ref, g_ref, ga_ref, w_ref,
                           *refs, taps, halo, silu):
    """`_conv_decay_bwd_kernel` likewise; `refs`: [bias,] dx, dw [, dbias]."""
    import jax.experimental.pallas as pl

    biased = len(refs) == 4
    bias_ref, dbias_ref = (refs[:1], refs[3:]) if biased else ((), ())
    dx_ref, dw_ref = refs[biased:biased + 2]
    step = pl.program_id(2)
    seen = 1.0 - (step == 0).astype(F32)
    more = 1.0 - (step == pl.num_programs(2) - 1).astype(F32)
    tile = x_ref.shape[1]
    own = slice(halo, halo + tile)

    @pl.when((step == 0) & (pl.program_id(1) == 0))
    def _no_gradient_yet():
        for ref in (dw_ref, *dbias_ref):
            ref[...] = jnp.zeros_like(ref)

    for cols in columns(x_ref.shape[-1], LANES):
        w = _taps(w_ref, cols, taps)
        x = jnp.concatenate(
            [before_ref[0, :, cols].astype(F32) * seen,
             x_ref[0, :, cols].astype(F32),
             after_ref[0, :, cols].astype(F32)], 0)
        dy = jnp.concatenate(
            [jnp.zeros((halo, LANES), F32), g_ref[0, :, cols].astype(F32),
             ga_ref[0, :, cols].astype(F32) * more], 0)
        if silu:
            y = _convolve(x, w)
            for ref in bias_ref:
                y = y + ref[:, cols]
            s = sigmoid(y)
            dy = dy * (s * (1.0 + y * (1.0 - s)))
        dx = add_up(_ahead(dy, taps - 1 - j) * w[j] for j in range(taps))
        dx_ref[0, :, cols] = dx[own].astype(dx_ref.dtype)
        for j in range(taps):
            dw_ref[j:j + 1, cols] += _total((dy * back(x, taps - 1 - j))[own])
        for ref in dbias_ref:
            ref[:, cols] += _total(dy[own])


def _unit(o, eps):
    r = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * r, r


# The gate's rules, on fp32 values: the gate's argument z is Gate (+ the
# bias row where the site has one), its factor s = sigmoid(z) or silu(z) =
# z sigmoid(z), and ds/dz is made from z and sigmoid(z).  The sites differ
# in their refs (no bias row and no dBias without a bias) and in the static
# `rule`; under sigmoid with a bias the operations and their order are
# `kimi-train-kda8k`'s kernels as PR 49 wrote them.
def _gate(rule, gate_ref, bias_ref, cols):
    """(z, sigmoid(z), s)."""
    z = gate_ref[0, :, cols].astype(F32)
    if bias_ref:
        z = z + bias_ref[0][:, cols]
    sg = sigmoid(z)
    return z, sg, z * sg if rule == "silu" else sg


def _gate_slope(rule, z, sg):
    return sg * ((1.0 + z * (1.0 - sg)) if rule == "silu" else (1.0 - sg))


def _gated_norm_kernel(o_ref, gate_ref, *refs, head_dim, eps, rule):
    *bias_ref, scale_ref, out_ref = refs
    for cols in columns(o_ref.shape[-1], head_dim):
        n, _ = _unit(o_ref[0, :, cols].astype(F32), eps)
        _, _, s = _gate(rule, gate_ref, bias_ref, cols)
        out_ref[0, :, cols] = (n * scale_ref[:, cols] * s).astype(
            out_ref.dtype)


def _gated_norm_bwd_kernel(o_ref, gate_ref, g_ref, *refs, head_dim, eps,
                           rule):
    import jax.experimental.pallas as pl

    # in: [bias,] scale; out: do, dgate, [dbias,] dscale
    biased = len(refs) == 6
    bias_ref, dbias_ref = (refs[:1], refs[-2:-1]) if biased else ((), ())
    scale_ref, do_ref, dgate_ref = refs[biased:biased + 3]
    dscale_ref = refs[-1]

    @pl.when((pl.program_id(2) == 0) & (pl.program_id(1) == 0))
    def _no_gradient_yet():
        for ref in (*dbias_ref, dscale_ref):
            ref[...] = jnp.zeros_like(ref)

    for cols in columns(o_ref.shape[-1], head_dim):
        n, r = _unit(o_ref[0, :, cols].astype(F32), eps)
        z, sg, s = _gate(rule, gate_ref, bias_ref, cols)
        g = g_ref[0, :, cols].astype(F32)
        gn = g * n
        dscale_ref[:, cols] += _total(gn * s)
        dgate = gn * scale_ref[:, cols] * _gate_slope(rule, z, sg)
        dgate_ref[0, :, cols] = dgate.astype(dgate_ref.dtype)
        for ref in dbias_ref:
            ref[:, cols] += _total(dgate)
        dn = g * scale_ref[:, cols] * s
        do = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
        do_ref[0, :, cols] = do.astype(do_ref.dtype)


# ---------------------------------------------------------------------------
# the four calls
# ---------------------------------------------------------------------------
def _specs(B, S, C, tiles):
    """(grid, rows, before, after, whole): the block specs of a [B, S, C]
    stream's tile, of the halo blocks on either side of it and of a
    parameter's rows, on the grid (channel block, sequence, tile)."""
    import jax.experimental.pallas as pl

    T, Cb, halo = tiles.rows, tiles.channels, tiles.halo
    rows = pl.BlockSpec((1, T, Cb), lambda c, b, i: (b, i, c))
    before = after = None
    if halo:
        per, last = T // halo, S // halo - 1
        before = pl.BlockSpec(
            (1, halo, Cb), lambda c, b, i: (b, jnp.maximum(i * per - 1, 0), c))
        after = pl.BlockSpec(
            (1, halo, Cb),
            lambda c, b, i: (b, jnp.minimum((i + 1) * per, last), c))

    def whole(height):
        return pl.BlockSpec((height, Cb), lambda c, b, i: (0, c))

    return (C // Cb, B, S // T), rows, before, after, whole


@functools.lru_cache(maxsize=64)
def _conv_fwd_call(B, S, C, taps, tiles, dtype, interpret):
    """Memoized, as kernels/flash_attention.py::_fwd_call: every site of
    one shape shares one kernel payload; and jitted, so that the sites
    after the first (a layer's first forward, its recomputed one, the
    next layer's) find the kernel's body traced and lowered: un-jitted,
    the four bodies were traced and lowered afresh at every site, 7.6 s of
    `setup_s` in `kimi-train-kda8k` (PERF.md, PR 49)."""
    import jax.experimental.pallas as pl

    grid, rows, before, _, whole = _specs(B, S, C, tiles)
    like = jax.ShapeDtypeStruct((B, S, C), jnp.dtype(dtype))
    return jax.jit(pl.pallas_call(
        functools.partial(_conv_decay_kernel, taps=taps, halo=tiles.halo),
        grid=grid,
        in_specs=[rows] * 4 + [before] * 3 + [whole(taps)] * 3
        + [whole(1)] * 2,
        out_specs=[rows] * 4,
        out_shape=[like] * 3 + [jax.ShapeDtypeStruct((B, S, C), F32)],
        compiler_params=compiler_params(
            ("parallel",) * 3, tiles.fwd_vmem_bytes),
        interpret=interpret,
    ))


@functools.lru_cache(maxsize=64)
def _conv_bwd_call(B, S, C, taps, tiles, dtype, interpret):
    import jax.experimental.pallas as pl

    grid, rows, before, after, whole = _specs(B, S, C, tiles)
    like = jax.ShapeDtypeStruct((B, S, C), jnp.dtype(dtype))
    small = [taps] * 3 + [1] * 2
    return jax.jit(pl.pallas_call(
        functools.partial(_conv_decay_bwd_kernel, taps=taps, halo=tiles.halo),
        grid=grid,
        in_specs=[rows] * 4 + [before] * 3 + [after] * 3 + [rows] * 4
        + [after] * 3 + [whole(h) for h in small],
        out_specs=[rows] * 4 + [whole(h) for h in small],
        out_shape=[like] * 4
        + [jax.ShapeDtypeStruct((h, C), F32) for h in small],
        compiler_params=compiler_params(
            ("parallel", "arbitrary", "arbitrary"), tiles.bwd_vmem_bytes),
        interpret=interpret,
    ))


@functools.lru_cache(maxsize=64)
def _short_fwd_call(B, S, C, taps, silu, biased, tiles, dtype, interpret):
    import jax.experimental.pallas as pl

    grid, rows, before, _, whole = _specs(B, S, C, tiles)
    return jax.jit(pl.pallas_call(
        functools.partial(_short_conv_kernel, taps=taps, halo=tiles.halo,
                          silu=silu),
        grid=grid,
        in_specs=[rows, before, whole(taps)] + [whole(1)] * biased,
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((B, S, C), jnp.dtype(dtype)),
        compiler_params=compiler_params(
            ("parallel",) * 3, tiles.fwd_vmem_bytes),
        interpret=interpret,
    ))


@functools.lru_cache(maxsize=64)
def _short_bwd_call(B, S, C, taps, silu, biased, tiles, dtype, interpret):
    import jax.experimental.pallas as pl

    grid, rows, before, after, whole = _specs(B, S, C, tiles)
    return jax.jit(pl.pallas_call(
        functools.partial(_short_conv_bwd_kernel, taps=taps, halo=tiles.halo,
                          silu=silu),
        grid=grid,
        in_specs=[rows, before, after, rows, after, whole(taps)]
        + [whole(1)] * biased,
        out_specs=[rows, whole(taps)] + [whole(1)] * biased,
        out_shape=[jax.ShapeDtypeStruct((B, S, C), jnp.dtype(dtype)),
                   jax.ShapeDtypeStruct((taps, C), F32)]
        + [jax.ShapeDtypeStruct((1, C), F32)] * biased,
        compiler_params=compiler_params(
            ("parallel", "arbitrary", "arbitrary"), tiles.bwd_vmem_bytes),
        interpret=interpret,
    ))


@functools.lru_cache(maxsize=64)
def _norm_fwd_call(B, S, C, head_dim, eps, rule, biased, tiles, dtype,
                   interpret):
    import jax.experimental.pallas as pl

    grid, rows, _, _, whole = _specs(B, S, C, tiles)
    return jax.jit(pl.pallas_call(
        functools.partial(_gated_norm_kernel, head_dim=head_dim, eps=eps,
                          rule=rule),
        grid=grid,
        in_specs=[rows] * 2 + [whole(1)] * (1 + biased),
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((B, S, C), jnp.dtype(dtype)),
        compiler_params=compiler_params(
            ("parallel",) * 3, tiles.fwd_vmem_bytes),
        interpret=interpret,
    ))


@functools.lru_cache(maxsize=64)
def _norm_bwd_call(B, S, C, head_dim, eps, rule, biased, tiles, dtype,
                   interpret):
    import jax.experimental.pallas as pl

    grid, rows, _, _, whole = _specs(B, S, C, tiles)
    like = jax.ShapeDtypeStruct((B, S, C), jnp.dtype(dtype))
    return jax.jit(pl.pallas_call(
        functools.partial(_gated_norm_bwd_kernel, head_dim=head_dim, eps=eps,
                          rule=rule),
        grid=grid,
        in_specs=[rows] * 3 + [whole(1)] * (1 + biased),
        out_specs=[rows] * 2 + [whole(1)] * (1 + biased),
        out_shape=[like] * 2
        + [jax.ShapeDtypeStruct((1, C), F32)] * (1 + biased),
        compiler_params=compiler_params(
            ("parallel", "arbitrary", "arbitrary"), tiles.bwd_vmem_bytes),
        interpret=interpret,
    ))


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _conv_decay(q, k, v, f, wq, wk, wv, dt, rate, tiles: Tiles,
                interpret: bool = False):
    """(q', k', v, g) of a site `conv_tiles` tiled; the filters [k, C], dt
    and rate [1, C] fp32."""
    B, S, C = q.shape
    call = _conv_fwd_call(B, S, C, wq.shape[0], tiles, str(q.dtype),
                          interpret)
    return tuple(call(q, k, v, f, q, k, v, wq, wk, wv, dt, rate))


def _conv_decay_fwd(q, k, v, f, wq, wk, wv, dt, rate, tiles, interpret):
    return (_conv_decay(q, k, v, f, wq, wk, wv, dt, rate, tiles, interpret),
            (q, k, v, f, wq, wk, wv, dt, rate))


def _conv_decay_bwd(tiles, interpret, inputs, cotangents):
    q, k, v, f, wq, wk, wv, dt, rate = inputs
    B, S, C = q.shape
    gq, gk, gv = (g.astype(q.dtype) for g in cotangents[:3])
    gg = cotangents[3].astype(F32)
    call = _conv_bwd_call(B, S, C, wq.shape[0], tiles, str(q.dtype),
                          interpret)
    return tuple(call(q, k, v, f, q, k, v, q, k, v, gq, gk, gv, gg,
                      gq, gk, gv, wq, wk, wv, dt, rate))


_conv_decay.defvjp(_conv_decay_fwd, _conv_decay_bwd)


def _present(*rows):
    """The parameter rows a site has: a bias it has not is no operand."""
    return tuple(row for row in rows if row is not None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _short_conv(x, w, bias, silu: bool, tiles: Tiles,
                interpret: bool = False):
    """The convolution of a site `short_conv_tiles` tiled; the filter [k,
    C] fp32, the bias [1, C] fp32 or None."""
    B, S, C = x.shape
    return _short_fwd_call(B, S, C, w.shape[0], silu, bias is not None,
                           tiles, str(x.dtype), interpret)(
        x, x, w, *_present(bias))


def _short_conv_fwd(x, w, bias, silu, tiles, interpret):
    return _short_conv(x, w, bias, silu, tiles, interpret), (x, w, bias)


def _short_conv_bwd(silu, tiles, interpret, inputs, cotangent):
    x, w, bias = inputs
    B, S, C = x.shape
    g = cotangent.astype(x.dtype)
    dx, dw, *dbias = _short_bwd_call(
        B, S, C, w.shape[0], silu, bias is not None, tiles, str(x.dtype),
        interpret)(x, x, x, g, g, w, *_present(bias))
    return dx, dw, (dbias[0] if dbias else None)


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _gated_norm(o, gate, bias, scale, head_dim: int, eps: float, rule: str,
                tiles: Tiles, interpret: bool = False):
    """The gated norm of a site `norm_tiles` tiled under the gate's `rule`;
    scale [1, C] fp32, bias likewise or None."""
    B, S, C = o.shape
    return _norm_fwd_call(B, S, C, head_dim, eps, rule, bias is not None,
                          tiles, str(o.dtype), interpret)(
        o, gate, *_present(bias, scale))


def _gated_norm_fwd(o, gate, bias, scale, head_dim, eps, rule, tiles,
                    interpret):
    return (_gated_norm(o, gate, bias, scale, head_dim, eps, rule, tiles,
                        interpret), (o, gate, bias, scale))


def _gated_norm_bwd(head_dim, eps, rule, tiles, interpret, inputs, cotangent):
    o, gate, bias, scale = inputs
    B, S, C = o.shape
    do, dgate, *dbias, dscale = _norm_bwd_call(
        B, S, C, head_dim, eps, rule, bias is not None, tiles, str(o.dtype),
        interpret)(o, gate, cotangent.astype(o.dtype), *_present(bias, scale))
    return do, dgate, (dbias[0] if dbias else None), dscale


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def _row(t):
    return t.astype(F32).reshape(1, -1)


def conv_decay(q, k, v, f, wq, wk, wv, dt_bias, a_log, heads, tiles: Tiles,
               interpret: bool = False):
    """ops/linear_attention_ops.py::conv_decay's four outputs by the kernel
    pair, of a site `conv_tiles` tiled (`tiles`); `interpret` runs the pair
    in the Pallas interpreter."""
    rate = jnp.repeat(-jnp.exp(a_log.astype(F32)), q.shape[2] // heads)
    return _conv_decay(
        q, k, v, f, *(w.astype(F32) for w in (wq, wk, wv)), _row(dt_bias),
        _row(rate), tiles, interpret)


def short_conv(x, w, bias, activation, tiles: Tiles,
               interpret: bool = False):
    """ops/linear_attention_ops.py::short_conv's output by the kernel pair,
    of a site `short_conv_tiles` tiled; `activation` silu | identity, `bias`
    [C] or None."""
    return _short_conv(x, w.astype(F32), None if bias is None else _row(bias),
                       bool(SHORT_CONV_ACTS.index(activation)), tiles,
                       interpret)


def gated_norm(o, gate, gate_bias, scale, heads, eps, tiles: Tiles,
               interpret: bool = False, activation: str = "sigmoid"):
    """ops/linear_attention_ops.py::gated_norm's output by the kernel pair,
    of a site `norm_tiles` tiled; `activation` sigmoid | silu, `gate_bias`
    [C] or None."""
    if activation not in ("sigmoid", "silu"):
        raise ValueError(f"no kernel for the gate {activation!r}")
    return _gated_norm(
        o, gate, None if gate_bias is None else _row(gate_bias),
        _row(jnp.tile(scale.astype(F32), heads)), o.shape[2] // heads,
        float(eps), activation, tiles, interpret)
