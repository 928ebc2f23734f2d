"""Compressed convolutional attention's mixing (the op compressed_conv_qkv,
name scope `cca.mix`) as one Pallas TPU kernel pair over tiles of rows.

ops/attention_ops.py::compressed_conv_mix is the arithmetic, in jax.numpy:
two causal convolutions along the sequence over z = [q~ ; k~], the q-k mean
of the values before them, every head to length sqrt(D), the keys times
tau, a rotary turn of the first `rotary_dim` features, the value's second
half of channels from the token before.  There every stage is a pass over
fp32 [H + G, S, D] tensors in HBM, XLA lays [1, S, C] fp32 out S-minor and
copies between layouts, and the half rotary makes 32-lane values: at
[1, 16384, 1280] a forward moves 0.1 GB and took 1.8 ms (PERF.md, PR 43).

Here a grid step holds a tile of rows of q~, k~, v~ as the projections
leave them ([B, S, C], nothing transposed before the call) plus the `halo`
rows before the tile (an aligned block of the previous tile: the two
convolutions reach (k0 - 1) + (k1 - 1) rows back; at the first tile zeros,
so that convolution A's bias is what B sees before position 0, the op's
`before` rule), and does everything in VMEM a head ([rows, D], D a
multiple of 128 lanes) at a time:
- convolution A: k0 sublane rolls times a row of weights;
- convolution B: ONE [rows, D] x [D, k1 D] product on the AMP tier's
  operands (fp32 out) whose k1 column blocks are rolled and summed;
- the q-k mean, the unit length, tau in fp32;
- the rotary turn at the head's full width: two lane rolls by
  `rotary_dim / 2`, a select, and [rows, D] planes of cos (1 past
  `rotary_dim`) and signed sin (0 past it) that XLA makes once outside;
- q^ [B, H, S, D], k^ and v [B, G, S, D] written through the output block
  specs, in q's dtype.
Nothing fp32 and nothing [.., S, ..]-sized but the op's inputs, outputs and
the two planes touches HBM.  The rows before position 0 pass the MXU as
every other row does (A's bias in the operand dtype); the jnp form keeps
that one row's term in fp32.

The backward (jax.custom_vjp; the residuals are the op's INPUTS) runs the
tiles last to first.  A step recomputes its tile's forward in VMEM, carries
dq^, dk^, dv back to dq~, dk~, dv~, and hands the tile before it, in VMEM
scratch, the first rows of what the transposed convolutions reach forward
for: B's and A's cotangents and dv.  The parameters' gradients accumulate
in fp32 in output blocks that stay resident over the whole grid; a row of
A's output (with the rows before position 0 at the first tile) is counted
by the tile that owns it.  Because the residuals are the inputs, a
recomputed layer runs the forward kernel again only for what the flash
backward reads.

`plan` reads the tile from the shape and the VMEM it needs, or says that
the shape does not tile; the op asks kernels/engine.py whether the site
runs this pair at all (ops/attention_ops.py::_compressed_conv_qkv) and
runs compressed_conv_mix where it does not.  tools/cca_mix_probe.py times the
pair alone on the chip.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import amp
from . import engine
from .engine import add_up, back, roll

__all__ = ["Geometry", "plan", "cca_mix", "moved_bytes"]

# The widest tile `plan` takes and the narrowest it falls to.  On the chip
# at the cell's shape (tools/cca_mix_probe.py --sweep, ms a sequence,
# PERF.md PR 45) the forward reads 0.281 / 0.207 / 0.195 / 0.196 at 128 /
# 256 / 512 / 1024 rows and the backward 0.754 / 0.499 / 0.582 / 0.515: a
# grid step's fixed cost and the halo's rows computed twice fall with the
# tile, the values a head holds outgrow the registers with it.  The budget
# (engine.PLAN_VMEM_BUDGET) decides: 256 rows both ways at the cell's ten
# heads.
_TILES = (512, 256, 128)


class Geometry(NamedTuple):
    """What a site's kernels are built from, all read from the shape."""
    heads: int
    kv_heads: int
    head_dim: int
    taps0: int
    taps1: int
    rotary_dim: int
    halo: int          # rows of the block before a tile
    fwd_tile: int
    bwd_tile: int
    operand: str       # the grouped product's operand dtype (the AMP tier's)

    @property
    def groups(self):
        return self.heads + self.kv_heads

    @property
    def tile(self):
        """What `cca.lower` says of a site: the forward's."""
        return self.fwd_tile


def working_set_bytes(tile, halo, H, G, D, k1, dtype, backward) -> int:
    """What a grid step holds in VMEM: the declared blocks and the
    parameters twice (the pipeline's two buffers), the backward's carried
    rows, and the fp32 temporaries of the heads in flight (a group's z and
    about a dozen [rows, D] values of the head being computed)."""
    size = jnp.dtype(dtype).itemsize
    n, rows = H + G, tile + halo
    tiles = (H + 2 * G) * D * tile * size          # q~, k~, v~ or q^, k^, v
    blocks = 2 * tiles + 2 * tile * D * 4          # in, out, cos and sin
    blocks += 3 * halo * (H + G) * D * size
    params = n * D * k1 * D * size + 6 * n * D * 4
    carried = 0
    if backward:
        blocks += tiles
        params += n * D * k1 * D * 4
        carried = (2 * n + G) * halo * D * 4
    temporaries = (H // G + 1 + 12) * rows * D * 4
    return 2 * (blocks + params) + carried + temporaries


def plan(S, H, G, D, k0, k1, rotary_dim, dtype, tile=None
         ) -> Optional[Geometry]:
    """The geometry of a site whose shape tiles, None where it does not: D
    whole 128-lane vectors, H a multiple of G, an even `rotary_dim` of at
    most D, the convolutions' reach within a halo block, and S a multiple
    of a tile whose working set fits.  `tile` pins both tiles for a test
    or the probe, never a model."""
    halo = engine.halo_rows(dtype)
    if (D % engine.LANES or H % G or rotary_dim % 2 or not 0 < rotary_dim <= D
            or (k0 - 1) + (k1 - 1) > min(halo, 8) or k0 < 1 or k1 < 1):
        return None

    def widest(backward):
        # heads do not block: the one candidate of channels is a head
        found = engine.widest(
            S, D, engine.LANES, lambda t, _: working_set_bytes(
                t, halo, H, G, D, k1, dtype, backward), _TILES, (D,))
        return found and found[0]

    if tile is not None:
        fwd = bwd = tile if S % tile == 0 and tile % halo == 0 else None
    else:
        fwd, bwd = widest(False), widest(True)
    if fwd is None or bwd is None:
        return None
    # convolution A's output is fp32 (amp.stats_dtype); what the AMP tier
    # makes of an fp32 operand is what causal_conv1d's product runs on
    a = jnp.zeros((), amp.stats_dtype(jnp.zeros((), dtype)))
    return Geometry(H, G, D, k0, k1, rotary_dim, halo, fwd, bwd,
                    str(amp.mxu_operands(a)[0].dtype))


def moved_bytes(q, k, v, recomputed: bool) -> int:
    """What a site's passes have to move through HBM: the forward reads
    q~, k~, v~ and writes q^, k^, v (as many bytes again), a second time
    where the unit around the site is rematerialised; the backward reads
    the three inputs and the three cotangents and writes three."""
    once = sum(int(t.size) * t.dtype.itemsize for t in (q, k, v))
    return (2 * once) * (2 if recomputed else 1) + 3 * once


# ---------------------------------------------------------------------------
# what both kernels compute, on [rows, D] fp32 values of one head
# ---------------------------------------------------------------------------
def _ahead(x, steps, rows):
    """y[e] = x[e + steps] for the first `rows` rows of x (x is longer by
    the carried rows, so nothing read has wrapped)."""
    return roll(x, -steps, 0)[:rows]


def _partner(x, half, low):
    """x with the two halves of its first 2 * half lanes swapped (what lies
    past them is multiplied by a zero of the sin plane)."""
    return jnp.where(low, roll(x, -half, 1), roll(x, half, 1))


def _unit(u):
    r = jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True))
    return u * r, r


def _convolve(z, aw, ab, taps, bb, k0, k1, operand):
    """(a, c): convolution A over z [rows, D] and B over A, one head."""
    D = z.shape[1]
    a = add_up(back(z, k0 - 1 - j) * aw[j] for j in range(k0)) + ab
    p = jnp.dot(a.astype(operand), taps, preferred_element_type=z.dtype)
    c = add_up(back(p[:, j * D:(j + 1) * D], k1 - 1 - j)
             for j in range(k1)) + bb
    return a, c


def _value_lanes(g, G, D):
    """Which lanes of value head g come from the token before: the second
    half of the G D channels.  (none, all, or from this lane on)."""
    first = G * D // 2 - g * D
    return None if first >= D else max(first, 0)


class _Site:
    """The refs of one grid step, a head at a time."""

    def __init__(self, geo, q_ref, k_ref, qh_ref, kh_ref, aw_ref, ab_ref,
                 taps_ref, bb_ref, first):
        self.geo = geo
        self.rows = (q_ref, qh_ref), (k_ref, kh_ref)
        self.params = aw_ref, ab_ref, taps_ref, bb_ref
        # 0 at the first tile: nothing lies before position 0
        self.seen = 1.0 - first.astype(jnp.float32)

    def cols(self, n):
        D = self.geo.head_dim
        return slice(n * D, (n + 1) * D)

    def z(self, n):
        """Head n of [q~ ; k~], its rows with the halo before them:
        [halo + tile, D] fp32."""
        H = self.geo.heads
        (ref, href), cols = self.rows[n >= H], self.cols(n if n < H else n - H)
        top = href[0, :, cols].astype(jnp.float32) * self.seen
        return jnp.concatenate([top, ref[0, :, cols].astype(jnp.float32)], 0)

    def convolve(self, z, n):
        geo = self.geo
        aw_ref, ab_ref, taps_ref, bb_ref = self.params
        cols = self.cols(n)
        aw = [aw_ref[j:j + 1, cols] for j in range(geo.taps0)]
        return _convolve(z, aw, ab_ref[:, cols], taps_ref[n], bb_ref[:, cols],
                         geo.taps0, geo.taps1, taps_ref.dtype)

    def mean_of_group(self, g, zk):
        """m_k of key head g over its rows: the mean of its query heads'
        m_q = (q~ + k~) / 2."""
        share = self.geo.heads // self.geo.kv_heads
        return add_up((self.z(g * share + s) + zk) / 2
                    for s in range(share)) / share


def _cca_mix_kernel(q_ref, k_ref, v_ref, qh_ref, kh_ref, vh_ref, cos_ref,
                    sin_ref, aw_ref, ab_ref, taps_ref, bb_ref, tau_ref,
                    qo_ref, ko_ref, vo_ref, *, geo):
    import jax.experimental.pallas as pl

    H, G, D, halo = geo.heads, geo.kv_heads, geo.head_dim, geo.halo
    share, half = H // G, geo.rotary_dim // 2
    first = pl.program_id(1) == 0
    site = _Site(geo, q_ref, k_ref, qh_ref, kh_ref, aw_ref, ab_ref, taps_ref,
                 bb_ref, first)
    cos, sin = cos_ref[...], sin_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1)
    low = lane < half

    def turned(y):
        return y * cos + _partner(y, half, low) * sin

    for g in range(G):
        zk = site.z(H + g)
        means = None
        for h in range(g * share, (g + 1) * share):
            zq = site.z(h)
            _, c = site.convolve(zq, h)
            mean = (zq + zk) / 2
            means = mean if means is None else means + mean
            y, _ = _unit((c + mean)[halo:])
            qo_ref[0, h] = turned(y).astype(qo_ref.dtype)
        _, c = site.convolve(zk, H + g)
        y, _ = _unit((c + means / share)[halo:])
        ko_ref[0, g] = turned(y * tau_ref[:, site.cols(g)]).astype(
            ko_ref.dtype)

        cols, before = site.cols(g), _value_lanes(g, G, D)
        if before is None:
            vo_ref[0, g] = v_ref[0, :, cols]
            continue
        top = vh_ref[0, :, cols].astype(jnp.float32) * site.seen
        v = jnp.concatenate([top, v_ref[0, :, cols].astype(jnp.float32)], 0)
        shifted = back(v, 1)
        if before:
            shifted = jnp.where(lane < before, v[halo:], shifted[halo:])
        else:
            shifted = shifted[halo:]
        vo_ref[0, g] = shifted.astype(vo_ref.dtype)


def _cca_mix_bwd_kernel(q_ref, k_ref, qh_ref, kh_ref, gq_ref, gk_ref, gv_ref,
                        cos_ref, sin_ref, aw_ref, ab_ref, taps_ref, bb_ref,
                        tau_ref, dq_ref, dk_ref, dv_ref, daw_ref, dab_ref,
                        dtaps_ref, dbb_ref, dtau_ref, dc_scr, da_scr, gv_scr,
                        *, geo):
    import jax.experimental.pallas as pl

    H, G, D, halo = geo.heads, geo.kv_heads, geo.head_dim, geo.halo
    k0, k1 = geo.taps0, geo.taps1
    share, half, tile = H // G, geo.rotary_dim // 2, geo.bwd_tile
    rows = halo + tile
    step = pl.program_id(1)
    first = step == pl.num_programs(1) - 1        # the sequence's first tile
    site = _Site(geo, q_ref, k_ref, qh_ref, kh_ref, aw_ref, ab_ref, taps_ref,
                 bb_ref, first)
    operand = taps_ref.dtype
    f32 = jnp.float32

    @pl.when(step == 0)
    def _nothing_after_the_last_tile():
        dc_scr[...] = jnp.zeros_like(dc_scr)
        da_scr[...] = jnp.zeros_like(da_scr)
        gv_scr[...] = jnp.zeros_like(gv_scr)

    @pl.when((step == 0) & (pl.program_id(0) == 0))
    def _no_gradient_yet():
        for ref in (daw_ref, dab_ref, dtaps_ref, dbb_ref, dtau_ref):
            ref[...] = jnp.zeros_like(ref)

    cos, sin = cos_ref[...], sin_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1)
    low = lane < half
    # the rows of A's output this tile owns: its own, and at the first tile
    # those before position 0 that B reads (they hold A's bias)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, D), 0)
    owned = row >= halo - (k1 - 1) * first.astype(jnp.int32)
    nothing = jnp.zeros((halo, D), f32)

    def total(x):
        return jnp.sum(x, axis=0, keepdims=True)

    def head(n, z, mean, g_ref, tau_cols):
        """Head n of cotangent g_ref[0, .] back to the cotangent of its
        pre-norm value u [tile, D] and of its z through the convolutions
        [tile, D]; the parameters' gradients are added where they live."""
        cols = site.cols(n)
        a, c = site.convolve(z, n)
        y, r = _unit((c + mean)[halo:])
        g = g_ref.astype(f32)
        dy = g * cos - _partner(g, half, low) * sin      # turned back
        if tau_cols is not None:
            dtau_ref[:, tau_cols] += total(dy * y)
            dy = dy * tau_ref[:, tau_cols]
        du = r * (dy - y * jnp.mean(dy * y, axis=-1, keepdims=True))
        dbb_ref[:, cols] += total(du)

        # B's transpose reaches k1 - 1 rows ahead: into the next tile's
        # first rows, which that tile left in dc_scr
        dc = jnp.concatenate([nothing, du, dc_scr[n]], 0)
        dc_scr[n] = du[:halo]
        dp = jnp.concatenate(
            [_ahead(dc, k1 - 1 - j, rows).astype(operand)
             for j in range(k1)], 1)
        da = jnp.where(owned, jax.lax.dot_general(
            dp, taps_ref[n], (((1,), (1,)), ((), ())),
            preferred_element_type=f32), 0.0)
        dtaps_ref[n] += jax.lax.dot_general(
            jnp.where(owned, a, 0.0).astype(operand), dp,
            (((0,), (0,)), ((), ())), preferred_element_type=f32)
        dab_ref[:, cols] += total(da)
        for j in range(k0):
            daw_ref[j:j + 1, cols] += total(da * back(z, k0 - 1 - j))
        # and A's transpose k0 - 1 rows ahead, into da_scr
        ahead = jnp.concatenate([da, da_scr[n]], 0)
        da_scr[n] = da[halo:2 * halo]
        dz = add_up(_ahead(ahead, k0 - 1 - j, rows) * aw_ref[j:j + 1, cols]
                  for j in range(k0))
        return du, dz[halo:]

    for g in range(G):
        zk = site.z(H + g)
        du_k, dz_k = head(H + g, zk, site.mean_of_group(g, zk), gk_ref[0, g],
                          site.cols(g))
        du_heads = []
        for s in range(share):
            h = g * share + s
            zq = site.z(h)
            du, dz = head(h, zq, (zq + zk) / 2, gq_ref[0, h], None)
            # u_h reads q~_h / 2; u of the key head every q~ / (2 share)
            dq_ref[0, :, site.cols(h)] = (
                dz + du / 2 + du_k / (2 * share)).astype(dq_ref.dtype)
            du_heads.append(du)
        dk_ref[0, :, site.cols(g)] = (
            dz_k + du_k / 2 + add_up(du_heads) / 2).astype(dk_ref.dtype)

        cols, before = site.cols(g), _value_lanes(g, G, D)
        if before is None:
            dv_ref[0, :, cols] = gv_ref[0, g].astype(dv_ref.dtype)
            continue
        gv = gv_ref[0, g].astype(f32)
        ahead = _ahead(jnp.concatenate([gv, gv_scr[g]], 0), 1, tile)
        gv_scr[g] = gv[:halo]
        if before:
            ahead = jnp.where(lane < before, gv, ahead)
        dv_ref[0, :, cols] = ahead.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# the two calls
# ---------------------------------------------------------------------------
def _compiler_params(semantics, geo, dtype, backward):
    tile = geo.bwd_tile if backward else geo.fwd_tile
    return engine.compiler_params(semantics, working_set_bytes(
        tile, geo.halo, geo.heads, geo.kv_heads, geo.head_dim, geo.taps1,
        dtype, backward))


@functools.lru_cache(maxsize=64)
def _fwd_call(B, S, geo, dtype, interpret):
    """Memoized, as kernels/flash_attention.py::_fwd_call: every site of
    one shape shares one kernel payload."""
    import jax.experimental.pallas as pl

    H, G, D, halo, T = (geo.heads, geo.kv_heads, geo.head_dim, geo.halo,
                        geo.fwd_tile)
    n, k0, k1 = geo.groups, geo.taps0, geo.taps1

    def rows(width):
        return pl.BlockSpec((1, T, width), lambda b, i: (b, i, 0))

    def before(width):
        return pl.BlockSpec(
            (1, halo, width),
            lambda b, i: (b, jnp.maximum(i * (T // halo) - 1, 0), 0))

    def whole(*shape):
        return pl.BlockSpec(shape, lambda b, i: (0,) * len(shape))

    def heads_first(m):
        return pl.BlockSpec((1, m, T, D), lambda b, i: (b, 0, i, 0))

    plane = pl.BlockSpec((T, D), lambda b, i: (i, 0))
    return pl.pallas_call(
        functools.partial(_cca_mix_kernel, geo=geo),
        grid=(B, S // T),
        in_specs=[rows(H * D), rows(G * D), rows(G * D),
                  before(H * D), before(G * D), before(G * D), plane, plane,
                  whole(k0, n * D), whole(1, n * D), whole(n, D, k1 * D),
                  whole(1, n * D), whole(1, G * D)],
        out_specs=[heads_first(H), heads_first(G), heads_first(G)],
        out_shape=[jax.ShapeDtypeStruct((B, m, S, D), jnp.dtype(dtype))
                   for m in (H, G, G)],
        compiler_params=_compiler_params(("parallel", "parallel"), geo,
                                         dtype, False),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=64)
def _bwd_call(B, S, geo, dtype, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, G, D, halo, T = (geo.heads, geo.kv_heads, geo.head_dim, geo.halo,
                        geo.bwd_tile)
    n, k0, k1 = geo.groups, geo.taps0, geo.taps1
    last = S // T - 1                   # the grid runs the tiles last to first

    def rows(width):
        return pl.BlockSpec((1, T, width), lambda b, i: (b, last - i, 0))

    def before(width):
        return pl.BlockSpec(
            (1, halo, width),
            lambda b, i: (b, jnp.maximum((last - i) * (T // halo) - 1, 0), 0))

    def whole(*shape):
        return pl.BlockSpec(shape, lambda b, i: (0,) * len(shape))

    def heads_first(m):
        return pl.BlockSpec((1, m, T, D), lambda b, i: (b, 0, last - i, 0))

    plane = pl.BlockSpec((T, D), lambda b, i: (last - i, 0))
    small = [(k0, n * D), (1, n * D), (n, D, k1 * D), (1, n * D), (1, G * D)]
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_cca_mix_bwd_kernel, geo=geo),
        grid=(B, S // T),
        in_specs=[rows(H * D), rows(G * D), before(H * D), before(G * D),
                  heads_first(H), heads_first(G), heads_first(G),
                  plane, plane] + [whole(*shape) for shape in small],
        out_specs=[rows(H * D), rows(G * D), rows(G * D)]
        + [whole(*shape) for shape in small],
        out_shape=[jax.ShapeDtypeStruct((B, S, m * D), jnp.dtype(dtype))
                   for m in (H, G, G)]
        + [jax.ShapeDtypeStruct(shape, f32) for shape in small],
        scratch_shapes=[pltpu.VMEM((n, halo, D), f32),
                        pltpu.VMEM((n, halo, D), f32),
                        pltpu.VMEM((G, halo, D), f32)],
        compiler_params=_compiler_params(("arbitrary", "arbitrary"), geo,
                                         dtype, True),
        interpret=interpret,
    )


def _planes(S, D, freq):
    """The rotary turn's two planes [S, D] fp32 at positions 0 .. S - 1
    (ops/attention_ops.py::_rotate's; `freq`, its rotary_dim / 2 pairs'
    frequencies): cos, 1 past `rotary_dim`; sin with the first half's sign
    turned, 0 past it."""
    angle = jnp.asarray(np.arange(S, dtype=np.float64)[:, None]
                        * np.asarray(freq)[None, :], jnp.float32)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    rest = D - 2 * len(freq)
    return (jnp.concatenate([cos, cos, jnp.ones((S, rest), cos.dtype)], 1),
            jnp.concatenate([-sin, sin, jnp.zeros((S, rest), sin.dtype)], 1))


def _kernel_parameters(a_w, a_b, b_w, b_b, tau, geo):
    """The eight-minus-three small inputs as the kernels read them: fp32
    rows of C, the taps of a head side by side on the AMP tier's operand
    dtype, tau a lane wide."""
    f32, D = jnp.float32, geo.head_dim
    taps = jnp.concatenate(list(b_w.astype(geo.operand)), axis=-1)
    return (a_w.astype(f32), a_b.astype(f32).reshape(1, -1), taps,
            b_b.astype(f32).reshape(1, -1),
            jnp.repeat(tau.astype(f32), D).reshape(1, -1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def cca_mix(q, k, v, a_w, a_b, b_w, b_b, tau, geo: Geometry, freq: tuple,
            interpret: bool = False):
    """compressed_conv_mix's (q^ [B, H, S, D], k^ and v [B, G, S, D]) of a
    site whose shape `plan` tiled; `freq`, the op's rotary frequencies
    (ops/attention_ops.py::_inv_freq's geo.rotary_dim / 2, as a tuple)."""
    B, S, _ = q.shape
    cos, sin = _planes(S, geo.head_dim, freq)
    call = _fwd_call(B, S, geo, str(q.dtype), interpret)
    return tuple(call(
        q, k, v, q, k, v, cos, sin,
        *_kernel_parameters(a_w, a_b, b_w, b_b, tau, geo)))


def _cca_mix_fwd(q, k, v, a_w, a_b, b_w, b_b, tau, geo, freq, interpret):
    return (cca_mix(q, k, v, a_w, a_b, b_w, b_b, tau, geo, freq, interpret),
            (q, k, a_w, a_b, b_w, b_b, tau))


def _cca_mix_bwd(geo, freq, interpret, inputs, cotangents):
    q, k, a_w, a_b, b_w, b_b, tau = inputs
    B, S, _ = q.shape
    D, k1 = geo.head_dim, geo.taps1
    cos, sin = _planes(S, D, freq)
    call = _bwd_call(B, S, geo, str(q.dtype), interpret)
    dq, dk, dv, daw, dab, dtaps, dbb, dtau = call(
        q, k, q, k, *(g.astype(q.dtype) for g in cotangents), cos, sin,
        *_kernel_parameters(a_w, a_b, b_w, b_b, tau, geo))
    dbw = jnp.stack([dtaps[..., j * D:(j + 1) * D] for j in range(k1)])
    return (dq, dk, dv, daw.astype(a_w.dtype),
            dab.reshape(a_b.shape).astype(a_b.dtype), dbw.astype(b_w.dtype),
            dbb.reshape(b_b.shape).astype(b_b.dtype),
            dtau.reshape(-1, D).sum(-1).astype(tau.dtype))


cca_mix.defvjp(_cca_mix_fwd, _cca_mix_bwd)
