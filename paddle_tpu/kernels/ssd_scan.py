"""A state-space-dual scan (Mamba-2: Dao & Gu, "Transformers are SSMs",
arXiv:2405.21060; the op ssd_scan, name scope `ssd.scan`) with a backward of
its own.

Every one of H heads keeps a state of P x N numbers, s_{-1} = 0, and a token
does

    s_t[h] = exp(dt_t[h] A[h]) s_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]
    y_t[h] = s_t[h] C_t[g] + D[h] x_t[h]

(x [S, H, P]; dt [S, H] > 0; A [H] < 0; B, C [S, G, N], head h reads group
g = h // (H / G); D [H]).  ONE decay a head a token, and B_t, C_t the same
for every head of a group: so a CHUNK of Q tokens is matmuls.  With cum the
running sum of dt A inside the chunk (fp32, <= 0 and falling),

    Y      = (L o (C B^T)) (dt x) + exp(cum) (C s_prev) + D x
    L[i,j] = exp(cum_i - cum_j) for i >= j, else 0
    s_next = exp(cum_last) s_prev + sum_j exp(cum_last - cum_j) (dt_j x_j) (x) B_j

where the scores C B^T [Q, Q] are made ONCE a chunk for a group and masked
by each head's own L.  Every exponent is a difference cum_i - cum_j <= 0
(or cum itself): never exp of a positive number.  What must never exist is
the state of every token, [S, H, P, N] (8.6 GB in fp32 at 8192 x 32 x 64 x
128): both engines carry the state through the chunks, keep the state every
chunk starts from, and their backward walks the chunks from the last to the
first carrying the state's cotangent the other way:

    dU     = M^T dY + w o (B ds_next)          M = L o (C B^T), U = dt x,
    dM     = dY U^T                            w = exp(cum_last - cum)
    dC     = sum_h (L_h o dM_h) B + sum_h (exp(cum_h) dY_h) s_prev_h
    dB     = sum_h (L_h o dM_h)^T C + sum_h (w_h U_h) ds_next_h
    dcum_i = sum_j (M o dM)[i,j] - sum_j (M o dM)[j,i] + dY_i . Yinter_i
             - w_i (B ds_next)_i . U_i  (+ at the chunk's last token
             exp(cum_last) <ds_next, s_prev> + sum_j w_j (B ds_next)_j . U_j)
    ds_prev = exp(cum_last) ds_next + C^T (exp(cum) dY)
    dx = dt dU + D dY,  ddt = dU . x + A da,  dA = sum dt da,  dD = sum dY . x

with da the running sum of dcum from the chunk's last token back.  The
running sums (cum forward, da backward) are a chunk's own and are taken in
jax.numpy around the kernels on [S, H] values.

Matmul operands take x's dtype (bf16 on the AMP tier) and add in fp32;
decays, running sums, the state and its cotangent are fp32.

Two engines, read from the shape and from what the program is traced for
(`tiles`; kernels/engine.py's door), no flag and no model's name:

- The Pallas kernel pair (`ssd.lower` says `engine` pallas): heads of 64 (two
  a 128-lane vector), states whole 128-lane vectors, S whole chunks, an even
  number of heads a group, for ONE TPU (or force="interpret").  The inputs
  stay as the layer hands them: x [B, S, H P] is read by lane blocks of
  `block` heads, B and C [B, S, G N] by a group's lane block.  The grid is
  (batch, chunks, blocks of heads), the blocks innermost; the states of
  EVERY head live in VMEM scratch across the chunk walk ([H / 2, N, 128]
  fp32: a pair of heads side by side on the lanes, 1 MB at 32 heads), the
  scores of a chunk in scratch across the blocks of its group.  What is a
  pair's alone is done once for both heads (C s_prev, B^T (w U): B and C
  are shared, so the products are 128 lanes wide); the masked product takes
  each head's own L and both halves are picked by lane.  A head's dt and cum
  are read as a column (a lane of [Q, 128]) and, for L, also as a row ([B,
  H, S] laid out by jax.numpy: 1 MB).  The backward runs the chunks last to
  first with ds in scratch, writes dx, dB, dC (added up over the blocks of a
  group in the output's own block), ddt's direct part, dcum as columns and
  the column sums of M o dM as rows, and a chunk's sum of dY x for dD.
- The jax.numpy engine (`engine` xla) everywhere else: a lax.scan over
  chunks that carries [B, H, P, N], a chunk the equations above in einsums;
  jax.custom_vjp, the backward a reversed scan over the chunks that
  differentiates one chunk at a time from its kept start (jax.vjp of the
  chunk: its residuals are a chunk's, never the sequence's).  It is the
  kernels' reference in tests/ beside the token-by-token recurrence.

Both tag y and the chunk starts with core.compiler.keep: the backward of a
recomputed layer runs no second forward of the scan.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.compiler import keep
from .engine import F32, LANES, PLAN_VMEM_BUDGET, compiler_params

CHUNK = 256
KEPT = ("y", "starts")
HEAD_DIM = LANES // 2         # the kernels' head: two side by side on the lanes
_BLOCKS = (8, 4, 2)           # heads a grid step, the widest that divides
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def kept_bytes(batch: int, seq: int, heads: int, head_dim: int, states: int,
               chunk: int = CHUNK, itemsize: int = 4) -> int:
    """What a site holds through its layer's recomputation: y at `itemsize`
    an element and the fp32 state every chunk starts from."""
    width = heads * head_dim
    return batch * width * (itemsize * seq + 4 * -(-seq // chunk) * states)


def moved_bytes(batch: int, seq: int, heads: int, head_dim: int, states: int,
                groups: int = 1, itemsize: int = 4) -> int:
    """What a site's two passes have to move through HBM whatever engine
    runs them, at `itemsize` an element: the forward reads x, dt, B, C and
    writes y; the backward reads those and dy and writes dx, ddt, dB, dC.
    The chunk starts, A, D, their gradients and the running sums are the
    engine's choice or small and are not counted."""
    rows = batch * seq * itemsize
    wide, shared, narrow = (rows * heads * head_dim,
                            rows * groups * states, rows * heads)
    return (2 * wide + 2 * shared + narrow) + (4 * wide + 4 * shared
                                               + 2 * narrow)


def flops(batch: int, seq: int, heads: int, head_dim: int, states: int,
          groups: int = 1, chunk: int = CHUNK) -> int:
    """The algorithm's matmul operations a site, forward + backward (twice
    the forward), at a chunk of `chunk` tokens and the pairs i >= j only: a
    token's scores 2 N (Q + 1) / 2 a group; a head's masked product 2 P (Q
    + 1) / 2, its read of the state 2 N P and its write 2 N P.  The decay
    masks' exponentials and products are vector-unit work and not counted."""
    pairs = (min(chunk, seq) + 1) / 2
    token = groups * 2 * states * pairs \
        + heads * (2 * head_dim * pairs + 4 * states * head_dim)
    return int(3 * batch * seq * token)


# ---------------------------------------------------------------------------
# the jax.numpy engine
# ---------------------------------------------------------------------------
def _einsum(eq, *operands):
    return jnp.einsum(eq, *operands, preferred_element_type=F32)


def _chunk(s, x, dt, b, c, a, d):
    """One chunk from the state s [B, H, P, N] fp32: x [B, Q, H, P], dt [B,
    Q, H] fp32, b, c [B, Q, G, N]; (the state after it, y [B, Q, H, P]
    fp32)."""
    mm = x.dtype
    B, Q, H, P = x.shape
    G, N = b.shape[2:]
    R = H // G
    cum = jnp.cumsum(dt * a, axis=1)
    u = dt[..., None] * x.astype(F32)
    bm, cm = b.astype(mm), c.astype(mm)
    lower = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    decay = jnp.where(lower, jnp.exp(jnp.minimum(
        cum[:, :, None] - cum[:, None], 0.0)), 0.0)           # [B, i, j, H]
    scores = _einsum("bign,bjgn->bijg", cm, bm)
    masked = (decay.reshape(B, Q, Q, G, R) * scores[..., None]).astype(mm)
    y = _einsum("bijgr,bjgrp->bigrp", masked,
                u.astype(mm).reshape(B, Q, G, R, P))
    y = y + jnp.exp(cum).reshape(B, Q, G, R, 1) * _einsum(
        "bign,bgrpn->bigrp", cm, s.astype(mm).reshape(B, G, R, P, N))
    y = y.reshape(B, Q, H, P) + d[:, None] * x.astype(F32)
    last = cum[:, -1]
    written = (jnp.exp(last[:, None] - cum)[..., None] * u).astype(mm)
    s = jnp.exp(last)[..., None, None] * s + _einsum(
        "bjgrp,bjgn->bgrpn", written.reshape(B, Q, G, R, P),
        bm).reshape(B, H, P, N)
    return s, y


def _by_chunks(t, chunk):
    """[B, S, ...] -> [chunks, B, chunk, ...]."""
    B, S = t.shape[:2]
    return jnp.moveaxis(t.reshape((B, S // chunk, chunk) + t.shape[2:]), 1, 0)


def _from_chunks(t):
    """[chunks, B, chunk, ...] -> [B, S, ...]."""
    n, B, Q = t.shape[:3]
    return jnp.moveaxis(t, 0, 1).reshape((B, n * Q) + t.shape[3:])


def _forward(x, dt, a, b, c, d, chunk):
    """(y [B, S, H, P] in x's dtype, the state every chunk starts from
    [chunks, B, H, P, N] fp32)."""
    def one(s, xs):
        after, y = _chunk(s, *xs, a, d)
        return after, (y.astype(x.dtype), s)

    B, _, H, P = x.shape
    zero = jnp.zeros((B, H, P, b.shape[-1]), F32)
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
        ds, dx, ddt, db, dc, da_c, dd_c = pull((ds, dy_c.astype(F32)))
        return (ds, da + da_c, dd + dd_c), (dx, ddt, db, dc)

    zero = (jnp.zeros_like(starts[0]), jnp.zeros_like(a), jnp.zeros_like(d))
    (_, da, dd), cts = jax.lax.scan(
        one, zero, tuple(_by_chunks(t, chunk) for t in (x, dt, b, c))
        + (starts, _by_chunks(dy, chunk)), reverse=True)
    dx, ddt, db, dc = (_from_chunks(t) for t in cts)
    return dx, ddt, da, db, dc, dd


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan_by_chunks(x, dt, a, b, c, d, chunk: int = CHUNK):
    """The jax.numpy engine; a sequence that is not whole chunks is filled
    up with tokens of dt = 0, which leave the state as it is, and their
    rows cut."""
    S = x.shape[1]
    chunk = min(int(chunk), S)
    short = -S % chunk
    if short:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, short)) + ((0, 0),)
                               * (t.ndim - 2)) for t in (x, dt, b, c))
    return _scan(x, dt, a, b, c, d, chunk)[:, :S]


# ---------------------------------------------------------------------------
# the Pallas engine
# ---------------------------------------------------------------------------
class Tiles(NamedTuple):
    """What a site's kernels are built from, all read from the shape."""
    chunk: int               # tokens a grid step
    block: int               # heads a grid step
    fwd_vmem_bytes: int
    bwd_vmem_bytes: int


def working_set_bytes(chunk: int, block: int, heads: int, states: int,
                      itemsize: int, backward: bool) -> int:
    """What a grid step holds in VMEM: the declared blocks twice (the
    pipeline's two buffers), the states of every pair of heads and a
    chunk's scores in scratch, and the step's live fp32 values ([Q, Q]
    planes of a head's mask and [Q, 128] planes of a pair)."""
    wide = chunk * block * HEAD_DIM * itemsize          # x, y, dy, dx
    shared = chunk * states * 4                         # B, C, dB, dC
    columns = chunk * LANES * 4                         # dt, cum (, ddt, dcum)
    rows = 8 * chunk * 4
    start = (block // 2) * states * LANES * 4
    scratch = (heads // 2) * states * LANES * 4 + chunk * chunk * 4
    square, plane = chunk * chunk * 4, chunk * LANES * 4
    if not backward:
        blocks = 2 * wide + 2 * shared + 2 * columns + rows + start
        return 2 * blocks + scratch + 6 * square + 10 * plane
    blocks = 3 * wide + 4 * shared + 4 * columns + 2 * rows + start \
        + block * HEAD_DIM * 4
    return 2 * blocks + scratch + 10 * square + 20 * plane


def tiles(seq: int, heads: int, head_dim: int, states: int, groups: int = 1,
          chunk: int = CHUNK, itemsize: int = 4):
    """The tiles of a site the kernel pair takes, None where it does not:
    heads of 64 (two a lane vector) and at most 128 of them, states whole
    lane vectors, whole chunks that are whole lane vectors themselves (a
    head's cum is also read as a row) unless the chunk is the sequence, an
    even number of heads a group, and a working set inside the budget."""
    chunk = min(int(chunk), int(seq))
    if head_dim != HEAD_DIM or states % LANES or heads % groups \
            or heads > LANES:           # a head a lane of the column planes
        return None
    if seq % chunk or chunk % 8 or (chunk < seq and chunk % LANES):
        return None
    block = next((n for n in _BLOCKS if (heads // groups) % n == 0), None)
    if block is None:
        return None
    need = [working_set_bytes(chunk, block, heads, states, itemsize, back)
            for back in (False, True)]
    if max(need) > PLAN_VMEM_BUDGET:
        return None
    return Tiles(chunk, block, *need)


def _dot(a, b, dims):
    """a . b over `dims`, operands as they come, fp32 out; fp32 operands at
    the highest precision (the MXU's one pass would round them to bf16)."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=F32,
        precision=jax.lax.Precision.HIGHEST if a.dtype == F32 else None)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _column(columns, lane, head):
    """Head `head`'s column [Q, 1] of a [Q, 128] plane of columns."""
    return jnp.sum(jnp.where(lane == head, columns, 0.0), axis=1,
                   keepdims=True)


def _total(plane):
    """[1, 1]: the sum of a plane."""
    return jnp.sum(jnp.sum(plane, axis=0, keepdims=True), axis=1,
                   keepdims=True)


class _Pair:
    """What the two passes read of a pair of heads in a chunk: the planes
    [Q, 128] whose lanes below HEAD_DIM are the first head's."""

    def __init__(self, k, first_head, x_ref, dt_ref, cum_ref, cumr_ref,
                 d_ref):
        Q = x_ref.shape[1]
        lanes = slice(k * LANES, (k + 1) * LANES)
        self.lanes = lanes
        lane = _iota((Q, LANES), 1)
        self.first = lane < HEAD_DIM
        heads = (first_head + 2 * k, first_head + 2 * k + 1)
        self.cum_cols = [_column(cum_ref[0], lane, h) for h in heads]
        self.cum_rows = [cumr_ref[0, 0, 2 * k + i:2 * k + i + 1, :]
                         for i in (0, 1)]
        self.x = x_ref[0, :, lanes].astype(F32)
        self.dt = self.both(*(_column(dt_ref[0], lane, h) for h in heads))
        self.cum = self.both(*self.cum_cols)
        self.d = d_ref[:, lanes]
        self.u = self.dt * self.x
        self.last = self.cum[Q - 1:Q, :]
        self.to_last = jnp.exp(self.last - self.cum)

    def both(self, first, second):
        return jnp.where(self.first, first, second)

    def side(self, i):
        """The lanes of head i of the pair."""
        return self.first if i == 0 else jnp.logical_not(self.first)

    def decay(self, i, lower):
        """Head i's L [Q, Q]."""
        return jnp.where(lower, jnp.exp(jnp.minimum(
            self.cum_cols[i] - self.cum_rows[i], 0.0)), 0.0)


def _scores(b_ref, c_ref, g_scr, j, blocks_a_group, mm):
    """C B^T of the chunk, made at a group's first block of heads."""
    import jax.experimental.pallas as pl

    @pl.when(j % blocks_a_group == 0)
    def _once_a_group():
        g_scr[...] = _dot(c_ref[0].astype(mm), b_ref[0].astype(mm), _NT)

    return g_scr[...]


def _fwd_kernel(x_ref, dt_ref, cum_ref, cumr_ref, b_ref, c_ref, d_ref,
                y_ref, start_ref, s_scr, g_scr, *, block, blocks_a_group):
    import jax.experimental.pallas as pl

    j = pl.program_id(2)
    Q, mm, pairs = x_ref.shape[1], x_ref.dtype, block // 2

    @pl.when(pl.program_id(1) == 0)
    def _the_state_starts_at_zero():
        for k in range(pairs):
            s_scr[j * pairs + k] = jnp.zeros(s_scr.shape[1:], F32)

    scores = _scores(b_ref, c_ref, g_scr, j, blocks_a_group, mm)
    lower = _iota((Q, Q), 0) >= _iota((Q, Q), 1)
    bm, cm = b_ref[0].astype(mm), c_ref[0].astype(mm)
    for k in range(pairs):
        p = _Pair(k, j * block, x_ref, dt_ref, cum_ref, cumr_ref, d_ref)
        s = s_scr[j * pairs + k]
        start_ref[0, 0, k] = s
        um = p.u.astype(mm)
        within = p.both(*(_dot((p.decay(i, lower) * scores).astype(mm), um,
                               _NN) for i in (0, 1)))
        y = within + jnp.exp(p.cum) * _dot(cm, s.astype(mm), _NN) \
            + p.d * p.x
        y_ref[0, :, p.lanes] = y.astype(y_ref.dtype)
        s_scr[j * pairs + k] = jnp.exp(p.last) * s \
            + _dot(bm, (p.to_last * p.u).astype(mm), _TN)


def _bwd_kernel(x_ref, dt_ref, cum_ref, cumr_ref, b_ref, c_ref, d_ref,
                start_ref, dy_ref, dx_ref, ddt_ref, dcum_ref, dcumr_ref,
                db_ref, dc_ref, dd_ref, ds_scr, g_scr, *, block,
                blocks_a_group):
    import jax.experimental.pallas as pl

    j = pl.program_id(2)
    Q, mm, pairs = x_ref.shape[1], x_ref.dtype, block // 2

    @pl.when(pl.program_id(1) == 0)
    def _nothing_after_the_last_chunk():
        for k in range(pairs):
            ds_scr[j * pairs + k] = jnp.zeros(ds_scr.shape[1:], F32)

    scores = _scores(b_ref, c_ref, g_scr, j, blocks_a_group, mm)
    lower = _iota((Q, Q), 0) >= _iota((Q, Q), 1)
    at_last = _iota((Q, 1), 0) == Q - 1
    lane = _iota((Q, LANES), 1)
    bm, cm = b_ref[0].astype(mm), c_ref[0].astype(mm)
    dscores = jnp.zeros((Q, Q), F32)
    db = jnp.zeros(db_ref.shape[1:], F32)
    dc = jnp.zeros(dc_ref.shape[1:], F32)
    ddt = jnp.zeros((Q, LANES), F32)
    dcum = jnp.zeros((Q, LANES), F32)
    for k in range(pairs):
        p = _Pair(k, j * block, x_ref, dt_ref, cum_ref, cumr_ref, d_ref)
        x, dy = p.x, dy_ref[0, :, p.lanes].astype(F32)
        start, ds = start_ref[0, 0, k], ds_scr[j * pairs + k]
        um, dym = p.u.astype(mm), dy.astype(mm)
        to_here = jnp.exp(p.cum)
        read = to_here * dy                        # d (C s_prev)
        written = p.to_last * p.u                  # what the state takes
        dwritten = _dot(bm, ds.astype(mm), _NN)    # B ds_next
        # dcum's parts that are a lane's own: the read's decay, w's
        own = read * _dot(cm, start.astype(mm), _NN) - dwritten * written
        carried = jnp.exp(p.last) * jnp.sum(ds * start, axis=0,
                                            keepdims=True)
        du = p.to_last * dwritten
        for i in (0, 1):
            side = p.side(i)
            decay = p.decay(i, lower)
            masked = decay * scores
            dmasked = _dot(jnp.where(side, dy, 0.0).astype(mm), um, _NT)
            dscores = dscores + decay * dmasked
            through = masked * dmasked
            du = du + jnp.where(side, _dot(masked.astype(mm), dym, _TN), 0.0)
            at_end = _total(jnp.where(side[:1], carried, 0.0)) \
                + _total(jnp.where(side, dwritten * written, 0.0))
            column = jnp.sum(through, axis=1, keepdims=True) \
                + jnp.sum(jnp.where(side, own, 0.0), axis=1, keepdims=True) \
                + jnp.where(at_last, at_end, 0.0)
            head = j * block + 2 * k + i
            dcum = dcum + jnp.where(lane == head, column, 0.0)
            dcumr_ref[0, 0, 2 * k + i:2 * k + i + 1, :] = jnp.sum(
                through, axis=0, keepdims=True)
        for i in (0, 1):
            head = j * block + 2 * k + i
            ddt = ddt + jnp.where(lane == head, jnp.sum(
                jnp.where(p.side(i), du * x, 0.0), axis=1, keepdims=True),
                0.0)
        dx_ref[0, :, p.lanes] = (p.dt * du + p.d * dy).astype(dx_ref.dtype)
        dd_ref[0, 0, :, p.lanes] = jnp.sum(dy * x, axis=0, keepdims=True)
        dc = dc + _dot(read.astype(mm), start.astype(mm), _NT)
        db = db + _dot(written.astype(mm), ds.astype(mm), _NT)
        ds_scr[j * pairs + k] = jnp.exp(p.last) * ds \
            + _dot(cm, read.astype(mm), _TN)
    dsm = dscores.astype(mm)
    dc = dc + _dot(dsm, bm, _NN)
    db = db + _dot(dsm, cm, _TN)

    @pl.when(j == 0)
    def _the_first_block_of_heads():
        ddt_ref[0] = ddt
        dcum_ref[0] = dcum

    @pl.when(j != 0)
    def _added_to_the_blocks_before():
        ddt_ref[0] = ddt_ref[0] + ddt
        dcum_ref[0] = dcum_ref[0] + dcum

    @pl.when(j % blocks_a_group == 0)
    def _the_first_block_of_a_group():
        db_ref[0] = db
        dc_ref[0] = dc

    @pl.when(j % blocks_a_group != 0)
    def _added_to_the_groups_blocks_before():
        db_ref[0] = db_ref[0] + db
        dc_ref[0] = dc_ref[0] + dc


def _specs(tiles_, states, blocks_a_group, last=None):
    """The block specs, of the grid (batch, chunks, blocks of heads): of a
    [B, S, H P] stream, of a group's [B, S, G N] B or C, of the [B, S, 128]
    planes of columns (dt, cum; a head a lane), of cum as rows [B, H /
    block, block, S], of D spread over its head's lanes [1, H P], of the
    chunk starts [B, chunks, H / 2, N, 128] and of a chunk's sums for dD
    [B, chunks, 1, H P].  `last`: the grid runs the chunks last to first."""
    import jax.experimental.pallas as pl

    def at(c):
        return c if last is None else last - c

    Q, block = tiles_.chunk, tiles_.block
    wide = block * HEAD_DIM
    return dict(
        stream=pl.BlockSpec((1, Q, wide), lambda b, c, j: (b, at(c), j)),
        shared=pl.BlockSpec((1, Q, states),
                            lambda b, c, j: (b, at(c), j // blocks_a_group)),
        columns=pl.BlockSpec((1, Q, LANES), lambda b, c, j: (b, at(c), 0)),
        rows=pl.BlockSpec((1, 1, block, Q), lambda b, c, j: (b, j, 0, at(c))),
        d=pl.BlockSpec((1, wide), lambda b, c, j: (0, j)),
        start=pl.BlockSpec((1, 1, block // 2, states, LANES),
                           lambda b, c, j: (b, at(c), j, 0, 0)),
        dd=pl.BlockSpec((1, 1, 1, wide), lambda b, c, j: (b, at(c), 0, j)))


_SEMANTICS = ("arbitrary", "arbitrary", "arbitrary")


@functools.lru_cache(maxsize=32)
def _fwd_call(B, S, H, N, G, dtype, tiles_, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Q, block = tiles_.chunk, tiles_.block
    per = (H // G) // block
    sp = _specs(tiles_, N, per)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block=block, blocks_a_group=per),
        grid=(B, S // Q, H // block),
        in_specs=[sp["stream"], sp["columns"], sp["columns"], sp["rows"],
                  sp["shared"], sp["shared"], sp["d"]],
        out_specs=[sp["stream"], sp["start"]],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * HEAD_DIM), dtype),
            jax.ShapeDtypeStruct((B, S // Q, H // 2, N, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((H // 2, N, LANES), F32),
                        pltpu.VMEM((Q, Q), F32)],
        compiler_params=compiler_params(_SEMANTICS, tiles_.fwd_vmem_bytes),
        interpret=interpret)


@functools.lru_cache(maxsize=32)
def _bwd_call(B, S, H, N, G, dtype, tiles_, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Q, block = tiles_.chunk, tiles_.block
    per = (H // G) // block
    sp = _specs(tiles_, N, per, last=S // Q - 1)
    columns = jax.ShapeDtypeStruct((B, S, LANES), F32)
    shared = jax.ShapeDtypeStruct((B, S, G * N), F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block=block, blocks_a_group=per),
        grid=(B, S // Q, H // block),
        in_specs=[sp["stream"], sp["columns"], sp["columns"], sp["rows"],
                  sp["shared"], sp["shared"], sp["d"], sp["start"],
                  sp["stream"]],
        out_specs=[sp["stream"], sp["columns"], sp["columns"], sp["rows"],
                   sp["shared"], sp["shared"], sp["dd"]],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * HEAD_DIM), dtype), columns,
            columns, jax.ShapeDtypeStruct((B, H // block, block, S), F32),
            shared, shared,
            jax.ShapeDtypeStruct((B, S // Q, 1, H * HEAD_DIM), F32)],
        scratch_shapes=[pltpu.VMEM((H // 2, N, LANES), F32),
                        pltpu.VMEM((Q, Q), F32)],
        compiler_params=compiler_params(_SEMANTICS, tiles_.bwd_vmem_bytes),
        interpret=interpret)


def _running(t, chunk, reverse=False):
    """The running sum of t [B, S, H] inside every chunk, from the chunk's
    last token back under `reverse`."""
    B, S, H = t.shape
    t = t.reshape(B, S // chunk, chunk, H)
    if reverse:
        t = jnp.flip(jnp.cumsum(jnp.flip(t, 2), axis=2), 2)
    else:
        t = jnp.cumsum(t, axis=2)
    return t.reshape(B, S, H)


def _operands(x, dt, a, b, c, d, tiles_):
    """The kernels' views: x [B, S, H P]; dt and cum as planes of columns
    [B, S, 128] (a head a lane); cum as rows [B, H / block, block, S]; B,
    C [B, S, G N]; D over its head's lanes [1, H P]."""
    B, S, H, P = x.shape
    cum = _running(dt * a, tiles_.chunk)

    def columns(t):
        return jnp.pad(t, ((0, 0), (0, 0), (0, LANES - H)))

    return (x.reshape(B, S, H * P), columns(dt), columns(cum),
            jnp.swapaxes(cum, 1, 2).reshape(B, H // tiles_.block,
                                            tiles_.block, S),
            b.reshape(B, S, -1), c.reshape(B, S, -1),
            jnp.repeat(d, P).reshape(1, H * P))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernels(x, dt, a, b, c, d, tiles_, interpret):
    return _kernels_fwd(x, dt, a, b, c, d, tiles_, interpret)[0]


def _kernels_fwd(x, dt, a, b, c, d, tiles_, interpret):
    B, S, H, _ = x.shape
    G, N = b.shape[2:]
    call = _fwd_call(B, S, H, N, G, x.dtype, tiles_, interpret)
    y, starts = keep(*call(*_operands(x, dt, a, b, c, d, tiles_)))
    return y.reshape(x.shape), (x, dt, a, b, c, d, starts)


def _kernels_bwd(tiles_, interpret, res, dy):
    x, dt, a, b, c, d, starts = res
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    call = _bwd_call(B, S, H, N, G, x.dtype, tiles_, interpret)
    dx, ddt, dcum, dcum_rows, db, dc, dd = call(
        *_operands(x, dt, a, b, c, d, tiles_), starts,
        dy.astype(x.dtype).reshape(B, S, H * P))
    dcum = dcum[..., :H] - jnp.swapaxes(dcum_rows.reshape(B, H, S), 1, 2)
    da = _running(dcum, tiles_.chunk, reverse=True)
    return (dx.reshape(x.shape), (ddt[..., :H] + da * a).astype(dt.dtype),
            jnp.sum(da * dt, axis=(0, 1)).astype(a.dtype),
            db.reshape(b.shape).astype(b.dtype),
            dc.reshape(c.shape).astype(c.dtype),
            jnp.sum(dd.reshape(-1, H, P), axis=(0, 2)).astype(d.dtype))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def ssd_scan(x, dt, a, b, c, d, tiles_=None, interpret=False,
             chunk: int = CHUNK):
    """y [B, S, H, P] in x's dtype of x, dt [B, S, H] (the step itself, > 0),
    A [H], B, C [B, S, G, N] and D [H] (the module's recurrence; dt, A and
    D taken to fp32): the kernel pair at `tiles_` (what `tiles` gave the
    site), the jax.numpy engine where they are None."""
    dt, a, d = (t.astype(F32) for t in (dt, a, d))
    if tiles_ is None:
        return scan_by_chunks(x, dt, a, b, c, d, chunk)
    return _kernels(x, dt, a, b, c, d, tiles_, bool(interpret))
