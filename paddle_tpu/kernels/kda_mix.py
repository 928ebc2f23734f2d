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

`conv_tiles` and `norm_tiles` read the tile from the shape and the VMEM it
needs, or say that the shape does not tile; the ops ask kernels/engine.py
whether a site runs these pairs at all (ops/linear_attention_ops.py) and
run their jax.numpy form where it does not.  tools/kda_mix_probe.py times the
pairs alone on the chip.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import engine
from .engine import (F32, LANES, add_up, back, columns, compiler_params, roll,
                     sigmoid)

__all__ = ["Tiles", "conv_tiles", "norm_tiles", "conv_decay", "gated_norm",
           "conv_moved_bytes", "norm_moved_bytes"]

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


def norm_working_set(rows, channels, head_dim, size, backward) -> int:
    """The same for the kernels after the scan."""
    tile = rows * channels
    blocks = (5 if backward else 3) * tile * size
    params = (4 if backward else 2) * channels * 4
    return 2 * (blocks + params) + 12 * rows * head_dim * 4


def _widest(seq, width, unit, need, rows, channels):
    """engine.widest over `_CHANNELS` x `_ROWS`; `rows` / `channels` pin
    either for a test or the probe, never a model."""
    return engine.widest(seq, width, unit, need,
                         _ROWS if rows is None else (rows,),
                         _CHANNELS if channels is None else (channels,))


def conv_tiles(seq, width, taps, dtype, rows=None, channels=None
               ) -> Optional[Tiles]:
    """The tiles of a site before the scan, None where the shape does not
    tile: channels whole 128-lane vectors, the taps' reach within a halo
    block, S whole tiles of rows whose working set fits."""
    halo, size = engine.halo_rows(dtype), jnp.dtype(dtype).itemsize
    if width % LANES or not 0 <= taps - 1 <= 8:
        return None

    def need(r, c, backward=True):
        return conv_working_set(r, c, halo, taps, size, backward)

    found = _widest(seq, width, LANES, need, rows, channels)
    if found is None or found[0] % halo:
        return None
    return Tiles(*found, halo, need(*found, False), need(*found))


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


def _unit(o, eps):
    r = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * r, r


def _gated_norm_kernel(o_ref, gate_ref, bias_ref, scale_ref, out_ref, *,
                       head_dim, eps):
    for cols in columns(o_ref.shape[-1], head_dim):
        n, _ = _unit(o_ref[0, :, cols].astype(F32), eps)
        s = sigmoid(gate_ref[0, :, cols].astype(F32) + bias_ref[:, cols])
        out_ref[0, :, cols] = (n * scale_ref[:, cols] * s).astype(
            out_ref.dtype)


def _gated_norm_bwd_kernel(o_ref, gate_ref, g_ref, bias_ref, scale_ref,
                           do_ref, dgate_ref, dbias_ref, dscale_ref, *,
                           head_dim, eps):
    import jax.experimental.pallas as pl

    @pl.when((pl.program_id(2) == 0) & (pl.program_id(1) == 0))
    def _no_gradient_yet():
        dbias_ref[...] = jnp.zeros_like(dbias_ref)
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    for cols in columns(o_ref.shape[-1], head_dim):
        n, r = _unit(o_ref[0, :, cols].astype(F32), eps)
        s = sigmoid(gate_ref[0, :, cols].astype(F32) + bias_ref[:, cols])
        g = g_ref[0, :, cols].astype(F32)
        gn = g * n
        dscale_ref[:, cols] += _total(gn * s)
        dgate = gn * scale_ref[:, cols] * (s * (1.0 - s))
        dgate_ref[0, :, cols] = dgate.astype(dgate_ref.dtype)
        dbias_ref[:, cols] += _total(dgate)
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
def _norm_fwd_call(B, S, C, head_dim, eps, tiles, dtype, interpret):
    import jax.experimental.pallas as pl

    grid, rows, _, _, whole = _specs(B, S, C, tiles)
    return jax.jit(pl.pallas_call(
        functools.partial(_gated_norm_kernel, head_dim=head_dim, eps=eps),
        grid=grid,
        in_specs=[rows] * 2 + [whole(1)] * 2,
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((B, S, C), jnp.dtype(dtype)),
        compiler_params=compiler_params(
            ("parallel",) * 3, tiles.fwd_vmem_bytes),
        interpret=interpret,
    ))


@functools.lru_cache(maxsize=64)
def _norm_bwd_call(B, S, C, head_dim, eps, tiles, dtype, interpret):
    import jax.experimental.pallas as pl

    grid, rows, _, _, whole = _specs(B, S, C, tiles)
    like = jax.ShapeDtypeStruct((B, S, C), jnp.dtype(dtype))
    return jax.jit(pl.pallas_call(
        functools.partial(_gated_norm_bwd_kernel, head_dim=head_dim, eps=eps),
        grid=grid,
        in_specs=[rows] * 3 + [whole(1)] * 2,
        out_specs=[rows] * 2 + [whole(1)] * 2,
        out_shape=[like] * 2 + [jax.ShapeDtypeStruct((1, C), F32)] * 2,
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _gated_norm(o, gate, bias, scale, head_dim: int, eps: float,
                tiles: Tiles, interpret: bool = False):
    """The gated norm of a site `norm_tiles` tiled; bias and scale [1, C]
    fp32."""
    B, S, C = o.shape
    return _norm_fwd_call(B, S, C, head_dim, eps, tiles, str(o.dtype),
                          interpret)(o, gate, bias, scale)


def _gated_norm_fwd(o, gate, bias, scale, head_dim, eps, tiles, interpret):
    return (_gated_norm(o, gate, bias, scale, head_dim, eps, tiles,
                        interpret), (o, gate, bias, scale))


def _gated_norm_bwd(head_dim, eps, tiles, interpret, inputs, cotangent):
    o, gate, bias, scale = inputs
    B, S, C = o.shape
    call = _norm_bwd_call(B, S, C, head_dim, eps, tiles, str(o.dtype),
                          interpret)
    return tuple(call(o, gate, cotangent.astype(o.dtype), bias, scale))


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


def gated_norm(o, gate, gate_bias, scale, heads, eps, tiles: Tiles,
               interpret: bool = False):
    """ops/linear_attention_ops.py::gated_norm's output by the kernel pair,
    of a site `norm_tiles` tiled."""
    return _gated_norm(
        o, gate, _row(gate_bias), _row(jnp.tile(scale.astype(F32), heads)),
        o.shape[2] // heads, float(eps), tiles, interpret)
