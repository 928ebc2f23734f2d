"""A selective state-space scan (Mamba-1: Gu & Dao, arXiv:2312.00752; the op
selective_scan, name scope `ssm.scan`) with a backward of its own.

Every one of E channels keeps N numbers, s_{-1} = 0, and a token does

    s_t[e, n] = exp(dt_t[e] A[e, n]) s_{t-1}[e, n] + dt_t[e] x_t[e] B_t[n]
    y_t[e]    = sum_n s_t[e, n] C_t[n] + D[e] x_t[e]

(x, dt [S, E]; A [E, N] < 0; B, C [S, N]; D [E]).  The decay depends on the
token AND the channel AND the state's index, so there is no matmul in it:
S E N multiply-adds and as many exponentials on the vector unit, all fp32,
one token after the other.  What must never exist is the state of every
token, [S, E, N] (2.7 GB at 8192 x 5120 x 16): both engines carry the state
through time, keep the state every CHUNK of tokens starts from ([S / chunk,
N, E]: 42 MB there), and their backward walks the chunks from the last to
the first, makes a chunk's states again from its start and carries the
state's cotangent the other way:

    ds_t   = dy_t C_t^T + exp(dt_{t+1} A) ds_{t+1}        [E, N]
    dC_t   = sum_e dy_t s_t          dB_t = sum_e ds_t (dt_t x_t)
    dx_t   = D dy_t + dt_t sum_n ds_t B_t
    ddt_t  = x_t sum_n ds_t B_t + sum_n ds_t s_{t-1} exp(dt_t A) A
    dA     = sum_t ds_t s_{t-1} exp(dt_t A) dt_t          dD = sum_t dy_t x_t

Two engines, read from the shape and from what the program is traced for
(`tiles`; kernels/engine.py's door), no flag and no model's name:

- The Pallas kernel pair (`ssm.lower` says `engine` pallas): E whole blocks
  of 1024 channels, S whole chunks, for ONE TPU (or force="interpret").  A
  block of channels is ONE [8, 128] fp32 tile a state index: the 16 states
  of 1024 channels are 16 vector registers, a token's x, dt and y are one
  tile each (the arrays viewed as [S, E / 128, 128], nothing regrouped in
  HBM), B_t[n] and C_t[n] are SCALARS read from SMEM (a chunk's [chunk x N]
  of each), and a token is 16 independent chains of exp, multiply and add
  with no reduction and no broadcast across lanes or sublanes.  The grid is
  (batch, chunks, blocks of channels), the blocks innermost; the states of
  every block live in VMEM scratch across the chunks.  The forward writes y
  and the state every chunk starts from.  The backward runs the chunks last
  to first: a step makes its chunk's states again into scratch, walks the
  tokens back, and writes dx, ddt and, for dB and dC (sums over ALL
  channels of a token: the one reduction the layout has to pay), the sum
  over a tile's 8 sublanes of every state's product, 16 tiles folded into
  two by a butterfly of sublane rolls (`_rows_of_sums`: 10 rolls for 8
  tiles where one tile at a time takes 24), added up over the blocks of
  channels in the output's own block ([S, N, 128]); the last 128 lanes are
  summed outside.  dA and dD add up in scratch.
- The jax.numpy engine (`engine` xla) everywhere else: a lax.scan over
  chunks that carries [E, N], inside it a lax.scan over the chunk's tokens;
  jax.custom_vjp, the backward a reversed scan over the chunks that
  differentiates one chunk at a time from its kept start (jax.vjp of the
  chunk: its residuals are a chunk's, never the sequence's).  It is the
  kernels' reference in tests/ beside the token-by-token scan.

Both tag y and the chunk starts with core.compiler.keep: the backward of a
recomputed layer runs no second forward of the scan.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.compiler import keep
from .engine import F32, LANES, PLAN_VMEM_BUDGET, compiler_params, roll

CHUNK = 64
KEPT = ("y", "starts")
_SUB = 8                      # sublanes of an fp32 tile
_SMEM_TILE = 1024             # words of a one-dimensional SMEM block
BLOCK = _SUB * LANES          # channels a grid step: one tile a state index


def kept_bytes(batch: int, seq: int, channels: int, states: int,
               chunk: int = CHUNK) -> int:
    """What a site holds through its layer's recomputation: y and the
    state every chunk starts from, fp32."""
    return 4 * batch * channels * (seq + -(-seq // chunk) * states)


def moved_bytes(batch: int, seq: int, channels: int, states: int,
                itemsize: int = 4) -> int:
    """What a site's two passes have to move through HBM whatever engine
    runs them, at `itemsize` an element of the [S, E] streams: the forward
    reads x, dt, B, C and writes y; the backward reads those and dy and
    writes dx, ddt, dB, dC (B, C and their gradients fp32).  The chunk
    starts, A, D and their gradients are the engine's choice or small and
    are not counted."""
    wide, narrow = batch * seq * channels * itemsize, 4 * batch * seq * states
    return (3 * wide + 2 * narrow) + (5 * wide + 4 * narrow)


def flops(batch: int, seq: int, channels: int, states: int) -> int:
    """The algorithm's operations a site, forward + backward, an
    exponential counted as one: a token, channel and state takes 7 in the
    forward (dt A, exp, the decay's product, dt x B's two, the state's add,
    s C and y's add) and 14 in the backward (the decay again, the state
    again, ds, dC, du, dB, the three of g, ddt, dA, the carried ds)."""
    return 21 * batch * seq * channels * states


# ---------------------------------------------------------------------------
# the jax.numpy engine
# ---------------------------------------------------------------------------
def _chunk(s, x, dt, b, c, a, d):
    """One chunk from the state s [B, E, N]: x, dt [T, B, E], b, c [T, B,
    N]; (the state after it, y [T, B, E])."""
    def token(s, one):
        x, dt, b, c = one
        s = jnp.exp(dt[..., None] * a) * s \
            + (dt * x)[..., None] * b[:, None, :]
        return s, jnp.sum(s * c[:, None, :], axis=-1) + d * x

    return jax.lax.scan(token, s, (x, dt, b, c))


def _by_chunks(t, chunk):
    """[B, S, W] -> [chunks, chunk, B, W]."""
    B, S, W = t.shape
    return jnp.moveaxis(t.reshape(B, S // chunk, chunk, W), (1, 2), (0, 1))


def _from_chunks(t):
    """[chunks, chunk, B, W] -> [B, S, W]."""
    n, T, B, W = t.shape
    return jnp.moveaxis(t, (0, 1), (1, 2)).reshape(B, n * T, W)


def _forward(x, dt, a, b, c, d, chunk):
    """(y [B, S, E], the state every chunk starts from [chunks, B, E, N])."""
    def one(s, xs):
        after, y = _chunk(s, *xs, a, d)
        return after, (y, s)

    zero = jnp.zeros((x.shape[0],) + a.shape, F32)
    y, starts = jax.lax.scan(
        one, zero, tuple(_by_chunks(t, chunk) for t in (x, dt, b, c)))[1]
    return _from_chunks(y), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, b, c, d, chunk):
    return _forward(x, dt, a, b, c, d, chunk)[0]


def _scan_fwd(x, dt, a, b, c, d, chunk):
    y, starts = keep(*_forward(x, dt, a, b, c, d, chunk))
    return y, (x, dt, a, b, c, d, starts)


def _scan_bwd(chunk, res, dy):
    x, dt, a, b, c, d, starts = res

    def one(carry, xs):
        ds, da, dd = carry
        *ins, start, dy_c = xs
        _, pull = jax.vjp(_chunk, start, *ins, a, d)
        ds, dx, ddt, db, dc, da_c, dd_c = pull((ds, dy_c))
        return (ds, da + da_c, dd + dd_c), (dx, ddt, db, dc)

    zero = (jnp.zeros_like(starts[0]), jnp.zeros_like(a), jnp.zeros_like(d))
    (_, da, dd), cts = jax.lax.scan(
        one, zero, tuple(_by_chunks(t, chunk) for t in (x, dt, b, c))
        + (starts, _by_chunks(dy, chunk)), reverse=True)
    dx, ddt, db, dc = (_from_chunks(t) for t in cts)
    return dx, ddt, da, db, dc, dd


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan_by_chunks(x, dt, a, b, c, d, chunk: int = CHUNK):
    """The jax.numpy engine on fp32 values; a sequence that is not whole
    chunks is filled up with tokens of dt = 0, which leave the state as it
    is, and their rows cut."""
    S = x.shape[1]
    chunk = min(int(chunk), S)
    short = -S % chunk
    if short:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, short), (0, 0)))
                       for t in (x, dt, b, c))
    return _scan(x, dt, a, b, c, d, chunk)[:, :S]


# ---------------------------------------------------------------------------
# the Pallas engine
# ---------------------------------------------------------------------------
class Tiles(NamedTuple):
    """What a site's kernels are built from, all read from the shape."""
    chunk: int               # tokens a grid step
    block: int               # channels a grid step
    fwd_vmem_bytes: int
    bwd_vmem_bytes: int


def working_set_bytes(chunk: int, states: int, blocks: int,
                      backward: bool) -> int:
    """What a grid step holds in VMEM: the declared blocks twice (the
    pipeline's two buffers), the states of every block of channels in
    scratch, and in the backward a chunk's states again."""
    tile = 4 * BLOCK
    stream = chunk * tile                          # a [chunk, 8, 128] block
    blocks_in = 2 * stream + (states + 1) * tile   # x dt; A D
    scratch = blocks * states * tile
    if not backward:
        return 2 * (blocks_in + stream + states * tile) + scratch
    rows = chunk * states * 4 * LANES              # [chunk, N, 128]
    blocks_in += stream + states * tile            # dy; the chunk's start
    out = 2 * stream + 2 * rows + (states + 1) * tile
    scratch += blocks * (states + 1) * tile + chunk * states * tile
    return 2 * (blocks_in + out) + scratch


def tiles(seq: int, channels: int, states: int, chunk: int = CHUNK):
    """The tiles of a site the kernel pair takes, None where it does not:
    whole blocks of 1024 channels (a tile a state index), whole chunks,
    states that fold by the butterfly (a multiple of 8), a chunk's scalars
    whole tiles of SMEM (1024 words: Mosaic refuses a one-dimensional block
    of any other multiple, found on the chip at 32 x 16) unless the chunk
    is the sequence, and a working set inside the budget."""
    chunk = min(int(chunk), int(seq))
    if channels % BLOCK or seq % chunk or states % _SUB or chunk % _SUB:
        return None
    if chunk < seq and (chunk * states) % _SMEM_TILE:
        return None
    need = [working_set_bytes(chunk, states, channels // BLOCK, back)
            for back in (False, True)]
    if max(need) > PLAN_VMEM_BUDGET:
        return None
    return Tiles(chunk, BLOCK, *need)


def _fwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, y_ref, start_ref,
                s_scr, *, chunk, states):
    import jax.experimental.pallas as pl

    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _the_state_starts_at_zero():
        s_scr[j] = jnp.zeros(s_scr.shape[1:], F32)

    start_ref[0, 0] = s_scr[j]
    d = d_ref[...]

    def token(t, s):
        x, dt = x_ref[0, t], dt_ref[0, t]
        u, y, new = dt * x, d * x, []
        for n in range(states):
            sn = jnp.exp(dt * a_ref[n]) * s[n] + u * b_ref[t * states + n]
            y = y + sn * c_ref[t * states + n]
            new.append(sn)
        y_ref[0, t] = y
        return tuple(new)

    s = jax.lax.fori_loop(0, chunk, token,
                          tuple(s_scr[j, n] for n in range(states)))
    for n in range(states):
        s_scr[j, n] = s[n]


_BIT_REVERSED = (0, 4, 2, 6, 1, 5, 3, 7)


def _rows_of_sums(tiles_):
    """Of 8 tiles [8, 128]: ONE tile whose row r is tile r summed over its
    sublanes.  A butterfly: a level folds pairs of tiles into one whose
    rows with the level's bit clear hold the first tile's partial sums and
    the others the second's (row r and row r ^ h added); three levels, 10
    sublane rolls."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, LANES), 0)
    t = [tiles_[i] for i in _BIT_REVERSED]
    for h in (4, 2, 1):
        low = (sub & h) == 0
        nxt = []
        for k in range(0, len(t), 2):
            first, second = t[k], t[k + 1]
            if h == 4:       # r - 4 = r + 4 on 8 rows: one roll serves both
                nxt.append(jnp.where(low, first, second)
                           + roll(jnp.where(low, second, first), h))
            else:
                nxt.append(jnp.where(low, first + roll(first, -h),
                                     second + roll(second, h)))
        t = nxt
    return t[0]


def _rows(products):
    """The products of every state [N tiles] as [N, 128] rows of their
    sublanes' sums."""
    return jnp.concatenate(
        [_rows_of_sums(products[g:g + _SUB])
         for g in range(0, len(products), _SUB)], axis=0)


def _bwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, start_ref, dy_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                ds_scr, da_scr, dd_scr, hist_scr, *, chunk, states):
    import jax.experimental.pallas as pl

    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _nothing_after_the_last_chunk():
        ds_scr[j] = jnp.zeros(ds_scr.shape[1:], F32)
        da_scr[j] = jnp.zeros(da_scr.shape[1:], F32)
        dd_scr[j] = jnp.zeros(dd_scr.shape[1:], F32)

    # the chunk's states again: hist[t] is the state token t starts from
    def ahead(t, s):
        x, dt = x_ref[0, t], dt_ref[0, t]
        u, new = dt * x, []
        for n in range(states):
            hist_scr[t, n] = s[n]
            new.append(jnp.exp(dt * a_ref[n]) * s[n]
                       + u * b_ref[t * states + n])
        return tuple(new)

    jax.lax.fori_loop(0, chunk, ahead,
                      tuple(start_ref[0, 0, n] for n in range(states)))
    d = d_ref[...]

    def back(i, ds):
        t = chunk - 1 - i
        x, dt, dy = x_ref[0, t], dt_ref[0, t], dy_ref[0, t]
        u = dt * x
        du = jnp.zeros_like(x)
        ddt = jnp.zeros_like(x)
        carried, for_b, for_c = [], [], []
        for n in range(states):
            bn, cn = b_ref[t * states + n], c_ref[t * states + n]
            an, before = a_ref[n], hist_scr[t, n]
            decay = jnp.exp(dt * an)
            here = dy * cn + ds[n]
            for_c.append(dy * (decay * before + u * bn))
            for_b.append(here * u)
            du = du + here * bn
            g = here * before * decay
            ddt = ddt + g * an
            da_scr[j, n] = da_scr[j, n] + g * dt
            carried.append(decay * here)
        dx_ref[0, t] = d * dy + du * dt
        ddt_ref[0, t] = ddt + du * x
        dd_scr[j] = dd_scr[j] + dy * x
        rows_b, rows_c = _rows(for_b), _rows(for_c)

        @pl.when(j == 0)
        def _the_first_block_of_channels():
            db_ref[0, t] = rows_b
            dc_ref[0, t] = rows_c

        @pl.when(j != 0)
        def _added_to_the_blocks_before():
            db_ref[0, t] = db_ref[0, t] + rows_b
            dc_ref[0, t] = dc_ref[0, t] + rows_c

        return tuple(carried)

    ds = jax.lax.fori_loop(0, chunk, back,
                           tuple(ds_scr[j, n] for n in range(states)))
    for n in range(states):
        ds_scr[j, n] = ds[n]
    # what has added up so far: the last chunk's visit writes the sums
    da_ref[0] = da_scr[j]
    dd_ref[0] = dd_scr[j]


def _specs(tiles_, states, chunks, last=None):
    """The block specs, of the grid (batch, chunks, blocks of channels):
    of a [B, S, E / 128, 128] stream, of the chunk's [chunk x N] scalars in
    SMEM (B and C flat, [B x S x N]), of A [N, E / 128, 128] and D [E /
    128, 128], of the chunk starts [B, chunks, N, E / 128, 128], and of the
    [B, S, N, 128] rows of dB and dC.  `last`: the grid runs the chunks
    last to first."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def at(c):
        return c if last is None else last - c

    T = tiles_.chunk
    return dict(
        stream=pl.BlockSpec((1, T, _SUB, LANES),
                            lambda b, c, j: (b, at(c), j, 0)),
        scalars=pl.BlockSpec((T * states,),
                             lambda b, c, j: (b * chunks + at(c),),
                             memory_space=pltpu.SMEM),
        a=pl.BlockSpec((states, _SUB, LANES), lambda b, c, j: (0, j, 0)),
        d=pl.BlockSpec((_SUB, LANES), lambda b, c, j: (j, 0)),
        start=pl.BlockSpec((1, 1, states, _SUB, LANES),
                           lambda b, c, j: (b, at(c), 0, j, 0)),
        rows=pl.BlockSpec((1, T, states, LANES),
                          lambda b, c, j: (b, at(c), 0, 0)),
        da=pl.BlockSpec((1, states, _SUB, LANES),
                        lambda b, c, j: (b, 0, j, 0)),
        dd=pl.BlockSpec((1, _SUB, LANES), lambda b, c, j: (b, j, 0)))


_SEMANTICS = ("arbitrary", "arbitrary", "arbitrary")


@functools.lru_cache(maxsize=32)
def _fwd_call(B, S, E, N, tiles_, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, blocks = tiles_.chunk, E // BLOCK
    sp = _specs(tiles_, N, S // T)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=T, states=N),
        grid=(B, S // T, blocks),
        in_specs=[sp["scalars"], sp["scalars"], sp["stream"], sp["stream"],
                  sp["a"], sp["d"]],
        out_specs=[sp["stream"], sp["start"]],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, E // LANES, LANES), F32),
            jax.ShapeDtypeStruct((B, S // T, N, E // LANES, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((blocks, N, _SUB, LANES), F32)],
        compiler_params=compiler_params(_SEMANTICS, tiles_.fwd_vmem_bytes),
        interpret=interpret)


@functools.lru_cache(maxsize=32)
def _bwd_call(B, S, E, N, tiles_, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, blocks = tiles_.chunk, E // BLOCK
    sp = _specs(tiles_, N, S // T, last=S // T - 1)
    stream = jax.ShapeDtypeStruct((B, S, E // LANES, LANES), F32)
    rows = jax.ShapeDtypeStruct((B, S, N, LANES), F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=T, states=N),
        grid=(B, S // T, blocks),
        in_specs=[sp["scalars"], sp["scalars"], sp["stream"], sp["stream"],
                  sp["a"], sp["d"], sp["start"], sp["stream"]],
        out_specs=[sp["stream"], sp["stream"], sp["rows"], sp["rows"],
                   sp["da"], sp["dd"]],
        out_shape=[stream, stream, rows, rows,
                   jax.ShapeDtypeStruct((B, N, E // LANES, LANES), F32),
                   jax.ShapeDtypeStruct((B, E // LANES, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((blocks, N, _SUB, LANES), F32),
                        pltpu.VMEM((blocks, N, _SUB, LANES), F32),
                        pltpu.VMEM((blocks, _SUB, LANES), F32),
                        pltpu.VMEM((T, N, _SUB, LANES), F32)],
        compiler_params=compiler_params(_SEMANTICS, tiles_.bwd_vmem_bytes),
        interpret=interpret)


def _lanes(t):
    """[B, S, E] as [B, S, E / 128, 128]: free."""
    return t.reshape(t.shape[:-1] + (t.shape[-1] // LANES, LANES))


def _operands(x, dt, a, b, c, d):
    E, N = a.shape
    return (b.reshape(-1), c.reshape(-1), _lanes(x), _lanes(dt),
            _lanes(a.T), d.reshape(E // LANES, LANES))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernels(x, dt, a, b, c, d, tiles_, interpret):
    return _kernels_fwd(x, dt, a, b, c, d, tiles_, interpret)[0]


def _kernels_fwd(x, dt, a, b, c, d, tiles_, interpret):
    B, S, E = x.shape
    call = _fwd_call(B, S, E, a.shape[1], tiles_, interpret)
    y, starts = keep(*call(*_operands(x, dt, a, b, c, d)))
    return y.reshape(B, S, E), (x, dt, a, b, c, d, starts)


def _kernels_bwd(tiles_, interpret, res, dy):
    x, dt, a, b, c, d, starts = res
    B, S, E = x.shape
    N = a.shape[1]
    call = _bwd_call(B, S, E, N, tiles_, interpret)
    dx, ddt, db, dc, da, dd = call(*_operands(x, dt, a, b, c, d), starts,
                                   _lanes(dy.astype(F32)))
    return (dx.reshape(B, S, E), ddt.reshape(B, S, E),
            jnp.sum(da, axis=0).reshape(N, E).T, jnp.sum(db, axis=-1),
            jnp.sum(dc, axis=-1), jnp.sum(dd, axis=0).reshape(E))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def selective_scan(x, dt, a, b, c, d, tiles_=None, interpret=False,
                   chunk: int = CHUNK):
    """y [B, S, E] fp32 of x, dt [B, S, E], A [E, N], B, C [B, S, N] and D
    [E] (the module's recurrence; every operand taken to fp32): the kernel
    pair at `tiles_` (what `tiles` gave the site), the jax.numpy engine
    where they are None."""
    x, dt, a, b, c, d = (t.astype(F32) for t in (x, dt, a, b, c, d))
    if tiles_ is None:
        return scan_by_chunks(x, dt, a, b, c, d, chunk)
    return _kernels(x, dt, a, b, c, d, tiles_, bool(interpret))
