"""Gated delta-rule linear attention as a scan over chunks of the sequence,
with a backward of its own (the op gated_delta_attention), in two forms
read from the operands' shapes (`form`), no flag: a decay for every key
channel, g [B, S, H D] (Kimi Delta Attention, KDA: arXiv:2510.26692 section
3; name scope `kda.scan`), and ONE decay a head, g [B, S, H], whose q and k
may come at fewer heads than v, value head j reading key head j // (H / Hk)
(Gated DeltaNet: arXiv:2412.06464; name scope `gdn.scan`).  The text below
is the first form's; what a head's one decay changes stands at its end.

A head keeps a state M [D keys, D values], M_0 = 0, and a token does

    M~_t = diag(alpha_t) M_{t-1}            alpha_t = exp(g_t), g_t <= 0, [D]
    M_t  = M~_t + beta_t k_t (v_t - M~_t^T k_t)^T
    o_t  = D^-1/2 M_t^T q_t                 q, k at unit length

(the decay acts before the correction reads the state).  Token by token
that is S dependent steps of rank-1 updates; tests/ and the benchmark's
reference run it so.  Here C = `chunk` tokens go at a time, on matmuls.
Inside a chunk, Gc the running sum of g (fp32, <= 0):

    A[r, j] = beta_r sum_d k_r k_j exp(Gc_r - Gc_j)      j < r, else 0
    T = (I + A)^-1 diag(beta)
    W = T (K exp(Gc)),  U = T V
    P[r, j] = D^-1/2 sum_d q_r k_j exp(Gc_r - Gc_j)      j <= r, else 0

and across chunks, M the state the chunk starts from (fp32):

    U' = U - W M
    O  = D^-1/2 (Q exp(Gc)) M + P U'
    M' = diag(exp(Gc_C)) M + (K exp(Gc_C - Gc))^T U'

Every exponent is a DIFFERENCE Gc_r - Gc_j <= 0 (or Gc itself): exp(-Gc)
alone, which the factored form (K exp(Gc)) (K exp(-Gc))^T needs, overflows
fp32 as soon as a channel decays by e^88 inside a chunk (1.4 a token at
C = 64; the published start of A_log and dt_bias reaches 1.6).  The two
decayed products are therefore built by halving (_decayed_products): the
pairs (r, j) of a chunk whose rows lie in the second half of a block of
2b rows and whose columns lie in its first half take the block's middle
row as the point both decays are measured from, exp(Gc_r - Gc_mid) and
exp(Gc_mid - Gc_j), both <= 1, and are ONE masked matmul; b = C/2, C/4, ...,
1 covers every pair below the diagonal, the diagonal is a sum over d.
(I + A)^-1 is built from the inverses of its diagonal blocks, doubled log2
C times (_unit_lower_inverse), fp32 at the highest matmul precision; every other matmul takes operands in
q's dtype (bf16 on the AMP tier) and adds in fp32; decays, running sums and
the state are fp32.

The work that does not read the state (everything up to W, U, P) is
parallel over chunks, the rest walks them in turn.  Two engines run it, and
`engine` reads which from the shape and from what the program is traced
for, no flag and no model's name:

- The Pallas kernel pair (`kda.lower` says `engine` pallas): heads of whole
  128-lane vectors, a sequence of whole tiles of 128 rows, for a TPU (or
  force="interpret", the CPU tests' door).  The inputs stay as the layer
  hands them, [B, S, H D]: a block spec picks a head by its lane block and
  a GROUP of rows (512: `kernel_tiles`) by its row block, nothing is
  regrouped in HBM.  A grid step (batch x heads parallel, the groups in
  turn) walks its group a TILE of 128 rows at a time, two chunks of 64
  whose A, X and P are the diagonal blocks of one [128, 128] square, so
  that every product of the parallel part fills the MXU; the running sum
  (log2 C rolls), the halvings (one plane exp(-|Gc - Gc_mid|) a level
  serves both sides), the inverse, W, U, P, the correction and the output
  live on VMEM values, the state [D, D] fp32 in VMEM scratch across the
  groups.  The forward writes `out` and the state every group starts from.
  The backward (jax.custom_vjp; residuals the op's inputs in their own
  layout and those states) runs the groups last to first with dM in
  scratch: a step makes its tiles' parallel part and chunk states again
  (W, U', Q exp(Gc), P, K exp(Gc_C - Gc), X, the k-k product and a state a
  chunk, all in scratch), then walks the tiles back: _chunk_bwd's
  products stacked, and the cotangents pulled back to q, k, v, g, beta
  written out (_tile_pull: the inverse's is -X^T dX X^T, the running
  sum's a reversed running sum, and the decayed products' dGc needs no
  derivative of the point a halving measures from).
- The jax.numpy engine (`engine` xla) everywhere else: tiny heads, a chunk
  that is the whole sequence, a working set over the budget, a CPU.  Both
  parts go a GROUP of chunks at a time (an outer lax.scan), so that what
  the parallel part holds at once is a group's and not the sequence's
  (`plan`), on q, k, v, g regrouped to [groups, group, B, H, C, D].
  jax.custom_vjp: the backward walks the groups last to first, computes a
  group's parallel part again under jax.vjp, runs the group's chunks last
  to first carrying dM (written out: _chunk_bwd), and pulls the group's
  cotangents back.  It is the kernels' reference in tests/.

Either way autodiff never sees the scan, so no state a token is ever
stored.  Of the states the forward keeps the one every GROUP starts from
(16 of 2 MB a layer at the cell's shape, where the 128 chunk states are 268
MB); the backward makes a group's chunk states again from it before it
walks the group's chunks back.  The forward tags its output and those
states with core.compiler.keep: the backward of a recomputed layer runs no
second forward of this op.  tools/kda_scan_probe.py times and checks the
two engines alone on the chip.

ONE decay a head (alpha_t a scalar, Gc a column): the chunk algebra above
with a scalar in diag's place, the same walk over chunks and groups, the
same inverse, kept states and backward, through the same functions.  What
is that form's alone: exp(Gc_r - Gc_j) is ONE [C, C] lower-triangular
square a head (every exponent still a difference <= 0), applied AFTER the
plain products k k^T and q k^T (_masked_products; in the kernels
_raw_products times x["decay"]), so the halvings (_decayed_products,
_halving, _middles, _block_starts) have no work to do and are the channel
form's alone; Gc's cotangent is the masked square's row sums less its
column sums.  Planes that are [., D] in the channel form are [., 1] here
and broadcast (exp(Gc), exp(Gc_C - Gc), the chunk's last decay).  Fewer key
heads: the jax.numpy engine regroups v, g and beta to (Hk, r) heads and q,
k to (Hk, 1), which broadcast inside _local and whose cotangents come back
summed; the kernels' block specs send value head h to the lane block h //
r of q and k [B, S, Hk D] (nothing is repeated in HBM; a grid step is still
one value head, so a key head's products are made once a value head), g
and its cotangent travel by tiles as beta does, and dq, dk leave the
backward kernel a value head each and are summed over a key head's
(_kernels_bwd).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.compiler import keep
from .engine import (F32, PLAN_VMEM_BUDGET, compiler_params, roll,
                     wants_kernels)

CHUNK = 64
KEPT = ("out", "states")
# what one fp32 [group, B, H, C, D] value may take: the parallel part holds
# a few dozen such values at once
_GROUP_BYTES = 8 << 20
_HIGHEST = jax.lax.Precision.HIGHEST


def plan(batch: int, seq: int, heads: int, dim: int, chunk: int = CHUNK):
    """{chunk, chunks, group}: tokens a chunk (S where S is shorter; a
    power of two that divides S), chunks a sequence, chunks a group (the
    largest divisor of `chunks` whose fp32 [group, B, H, C, D] fits
    _GROUP_BYTES, at least 1)."""
    chunk = min(int(chunk), int(seq))
    if chunk & (chunk - 1) or seq % chunk:
        raise ValueError(
            f"gated_delta_attention: a chunk of {chunk} tokens has to be a "
            f"power of two that divides the sequence's {seq}")
    chunks = seq // chunk
    room = max(1, _GROUP_BYTES // (4 * batch * heads * chunk * dim))
    group = max(n for n in range(1, chunks + 1)
                if chunks % n == 0 and n <= room)
    return {"chunk": chunk, "chunks": chunks, "group": group}


def state_bytes(batch: int, heads: int, dim: int) -> int:
    """One chunk boundary's states, fp32."""
    return 4 * batch * heads * dim * dim


def kept_bytes(batch: int, seq: int, heads: int, dim: int, groups: int,
               itemsize: int) -> int:
    """What a site holds through its layer's recomputation: the output in
    q's dtype and the state every group of chunks starts from."""
    return (itemsize * batch * seq * heads * dim
            + groups * state_bytes(batch, heads, dim))


def flops(batch: int, seq: int, heads: int, dim: int, chunk: int,
          key_heads: int = None) -> int:
    """The algorithm's FLOPs a site, forward + backward (the backward
    twice the forward; a recomputed pass is no work of the algorithm): a
    chunk of a head takes the two decayed products at their triangles (2
    C^2 D; once a KEY head where `key_heads` < `heads`: the value heads of
    a key head share q k^T and k k^T before their decays), the triangular
    inverse (C^3 / 3), T applied to [K | V] (2 C^2 D), the state read twice
    and written once (6 C D^2) and P U' (C^2 D)."""
    c, d = chunk, dim
    a_chunk = 3 * c * c * d + c ** 3 // 3 + 6 * c * d * d
    shared = 2 * c * c * d * (key_heads or heads)
    return 3 * batch * (seq // chunk) * (heads * a_chunk + shared)


def moved_bytes(batch: int, seq: int, heads: int, dim: int,
                itemsize: int, key_heads: int = None,
                head_decay: bool = False) -> int:
    """What a site's two passes have to move through HBM whatever engine
    runs them: the forward reads q, k (at `key_heads` heads where there are
    fewer), v (itemsize), g (fp32: a channel's, or under `head_decay` a
    head's) and beta and writes out; the backward reads those and out's
    cotangent and writes the five gradients.  The chunk states are the
    engine's choice and are not counted."""
    row = batch * seq * heads
    wide, keys = row * dim, batch * seq * (key_heads or heads) * dim
    gate = 4 * row * (1 if head_decay else dim)
    fwd = itemsize * (2 * keys + wide) + gate + 4 * row + itemsize * wide
    bwd = fwd + itemsize * (2 * keys + wide) + gate + 4 * row
    return fwd + bwd


# ---------------------------------------------------------------------------
# the part of a chunk that does not read the state
# ---------------------------------------------------------------------------
def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=F32)


def _block_starts(gc, b):
    """(own, nxt) [..., C, D]: Gc at the first row of the block of b rows a
    row lies in, and at the first row of the block after it (the chunk's
    last row for the last block, which no pair reads)."""
    lead, (c, d) = gc.shape[:-2], gc.shape[-2:]
    first = gc.reshape(lead + (c // b, b, d))[..., 0, :]
    after = jnp.concatenate([first[..., 1:, :], gc[..., -1:, :]], axis=-2)

    def rows(t):
        return jnp.broadcast_to(t[..., None, :],
                                lead + (c // b, b, d)).reshape(gc.shape)

    return rows(first), rows(after)


def _decayed_products(qn, kn, gc, mm):
    """[..., 2, C, C] fp32: for x = k (0) and x = q (1), sum_d x_r k_j
    exp(Gc_r - Gc_j) where j <= r, 0 above the diagonal (the module's
    docstring: by halving, no exponent above 0)."""
    c = gc.shape[-2]
    x = jnp.stack([kn, qn], axis=-3)
    out = jnp.sum(x * kn[..., None, :, :], axis=-1)[..., None] \
        * jnp.eye(c, dtype=F32)
    b = c // 2
    while b:
        own, nxt = _block_starts(gc, b)
        left = x * jnp.exp(jnp.minimum(gc - own, 0.0))[..., None, :, :]
        right = kn * jnp.exp(jnp.minimum(nxt - gc, 0.0))
        out = out + jnp.where(_halves(c, b), _mm(
            "...xrd,...jd->...xrj", left.astype(mm), right.astype(mm)), 0.0)
        b //= 2
    return out


def _masked_products(qn, kn, gc, mm):
    """_decayed_products where a HEAD has one decay, gc [..., C]: exp(Gc_r
    - Gc_j) is one [C, C] mask under the diagonal, applied after k k^T and
    q k^T (which the value heads of a key head share: qn, kn broadcast
    against gc), so no halving has work to do."""
    c = gc.shape[-1]
    x = jnp.stack([kn, qn], axis=-3).astype(mm)
    decay = jnp.exp(jnp.minimum(gc[..., :, None] - gc[..., None, :], 0.0))
    raw = _mm("...xrd,...jd->...xrj", x, kn.astype(mm))
    return jnp.where(jnp.tril(jnp.ones((c, c), bool)),
                     raw * decay[..., None, :, :], 0.0)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _halves(c, b, rows=1):
    """[rows c, c] bool: the pairs (r, j) with r in the second half and j
    in the first half of one block of 2b rows (`rows` such squares on top
    of each other).  Two-dimensional iotas: the kernels build it too."""
    row, col = _iota((rows * c, c), 0) % c, _iota((rows * c, c), 1)
    return (row // b == col // b + 1) & (row // (2 * b) == col // (2 * b))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(a, block=None):
    """(I + a)^-1 of a strictly lower triangular a [..., C, C], fp32, by
    doubling the blocks: X holds the inverses of the diagonal blocks of b
    rows (I at b = 1, so the first level is I - a's pairs itself); a block
    of 2b rows [[L1, 0], [A21, L2]] has the inverse [[X1, 0], [-X2 A21 X1,
    X2]], and X a X, two whole [C, C] matmuls, holds every X2 A21 X1 at
    once.  Every value made is an entry of the inverse: the finite series
    I - a + a^2 - ... in powers of a is shorter and loses every digit
    where a's entries are near 1 (a^32 has entries of 1e10 that cancel).
    `block` (C where None): the doubling stops at diagonal blocks of that
    many rows, for an `a` that is itself block diagonal (the kernels' tile
    of two chunks).  Its cotangent is -X^T dX X^T, two products, not the
    levels differentiated."""
    c = a.shape[-1]
    x = jnp.eye(c, dtype=a.dtype) - jnp.where(_halves(c, 1), a, 0.0)
    b = 2
    while b < (block or c):
        xax = jnp.matmul(jnp.matmul(x, a, precision=_HIGHEST), x,
                         precision=_HIGHEST)
        x = x - jnp.where(_halves(c, b), xax, 0.0)
        b *= 2
    return x


def _inverse_cotangent(x, dx):
    return -jnp.einsum(
        "...ik,...lk->...il",
        jnp.einsum("...ji,...jk->...ik", x, dx, precision=_HIGHEST,
                   preferred_element_type=F32),
        x, precision=_HIGHEST, preferred_element_type=F32)


_unit_lower_inverse.defvjp(
    lambda a, block: (lambda x: (x, x))(_unit_lower_inverse(a, block)),
    lambda block, x, dx: (_inverse_cotangent(x, dx),))


def _unit(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _local(q, k, v, g, beta, eps):
    """Of chunks [..., C, D] (beta [..., C]): (W, U, Q exp(Gc) D^-1/2, P,
    K exp(Gc_C - Gc)) in q's dtype and exp(Gc_C) [..., D] fp32.  With one
    decay a head, g [..., C]: Gc is a column [..., C, 1] that every plane
    below broadcasts, exp(Gc_C) [..., 1], and q, k may come at [..., 1, C,
    D] for v's [..., r, C, D] (r value heads a key head)."""
    mm, (c, d) = q.dtype, q.shape[-2:]
    qn, kn = _unit(q.astype(F32), eps), _unit(k.astype(F32), eps)
    if g.ndim < v.ndim:
        gc = jnp.cumsum(g, axis=-1)[..., None]
        products = _masked_products(qn, kn, gc[..., 0], mm)
    else:
        gc = jnp.cumsum(g, axis=-2)
        products = _decayed_products(qn, kn, gc, mm)
    kk, qk = jnp.moveaxis(products, -3, 0)
    below = jnp.tril(jnp.ones((c, c), bool), -1)
    t = _unit_lower_inverse(jnp.where(below, kk, 0.0) * beta[..., :, None]) \
        * beta[..., None, :]
    last = gc[..., -1:, :]
    tc, scale = t.astype(mm), d ** -0.5
    w = _mm("...rj,...jd->...rd", tc, (kn * jnp.exp(gc)).astype(mm))
    u = _mm("...rj,...jd->...rd", tc, v)
    return (w.astype(mm), u.astype(mm),
            (qn * jnp.exp(gc) * scale).astype(mm), (qk * scale).astype(mm),
            (kn * jnp.exp(last - gc)).astype(mm), jnp.exp(last[..., 0, :]))


# ---------------------------------------------------------------------------
# the part that does: one chunk, forward and backward
# ---------------------------------------------------------------------------
def _corrected(w, u, mc):
    return u.astype(F32) - _mm("...cd,...dv->...cv", w, mc)


def _after(m, u2, kd, gamma):
    return gamma[..., :, None] * m + _mm("...cd,...cv->...dv", kd, u2)


def _chunk_fwd(m, xs):
    w, u, qg, p, kd, gamma = xs
    mm = w.dtype
    mc = m.astype(mm)
    u2 = _corrected(w, u, mc).astype(mm)
    o = _mm("...cd,...dv->...cv", qg, mc) + _mm("...rj,...jv->...rv", p, u2)
    return _after(m, u2, kd, gamma), o.astype(mm)


def _chunk_state(m, xs):
    """(the state a chunk leaves, the state it starts from): _chunk_fwd
    without the output."""
    w, u, kd, gamma = xs
    u2 = _corrected(w, u, m.astype(w.dtype)).astype(w.dtype)
    return _after(m, u2, kd, gamma), m


def _chunk_bwd(dm, xs):
    """dm: the cotangent of the state the chunk leaves; returns that of
    the state it starts from and the cotangents of _local's six."""
    w, u, qg, p, kd, gamma, m, do = xs
    mm = w.dtype
    mc, dmc = m.astype(mm), dm.astype(mm)
    u2 = _corrected(w, u, mc).astype(mm)
    du2 = (_mm("...rj,...rv->...jv", p, do)
           + _mm("...cd,...dv->...cv", kd, dmc)).astype(mm)
    before = (_mm("...cd,...cv->...dv", qg, do) + gamma[..., :, None] * dm
              - _mm("...cd,...cv->...dv", w, du2))
    dgamma = jnp.sum(m * dm, axis=-1)
    if gamma.shape[-1] == 1:                 # one decay a head
        dgamma = jnp.sum(dgamma, axis=-1, keepdims=True)
    return before, (
        (-_mm("...cv,...dv->...cd", du2, mc)).astype(mm), du2,
        _mm("...cv,...dv->...cd", do, mc).astype(mm),
        _mm("...rv,...jv->...rj", do, u2).astype(mm),
        _mm("...cv,...dv->...cd", u2, dmc).astype(mm), dgamma)


# ---------------------------------------------------------------------------
# groups of chunks: arrays [groups, group, B, H, C, D]
# ---------------------------------------------------------------------------
def _forward(q, k, v, g, beta, eps):
    """(out [groups, group, B, H, C, D], the state every group starts
    from [groups, B, H, D, D]); H is (Hk, r) where q and k come at fewer
    heads than v."""
    def group(m, xs):
        after, out = jax.lax.scan(_chunk_fwd, m, _local(*xs, eps))
        return after, (out, m)

    zero = jnp.zeros(v.shape[2:-2] + (v.shape[-1],) * 2, F32)
    return jax.lax.scan(group, zero, (q, k, v, g, beta))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(q, k, v, g, beta, eps):
    return _forward(q, k, v, g, beta, eps)[0]


def _scan_fwd(q, k, v, g, beta, eps):
    out, starts = keep(*_forward(q, k, v, g, beta, eps))
    return out, (q, k, v, g, beta, starts)


def _scan_bwd(eps, res, do):
    q, k, v, g, beta, starts = res

    def group(dm, xs):
        *ins, start, d_out = xs
        outs, pull = jax.vjp(functools.partial(_local, eps=eps), *ins)
        w, u, _, _, kd, gamma = outs
        states = jax.lax.scan(_chunk_state, start, (w, u, kd, gamma))[1]
        dm, cts = jax.lax.scan(_chunk_bwd, dm, outs + (states, d_out),
                               reverse=True)
        return dm, pull(cts)

    return jax.lax.scan(group, jnp.zeros_like(starts[0]),
                        (q, k, v, g, beta, starts, do), reverse=True)[1]


_scan.defvjp(_scan_fwd, _scan_bwd)


# ---------------------------------------------------------------------------
# the Pallas engine: a head's group of chunks a grid step, everything on
# VMEM values
# ---------------------------------------------------------------------------
# Rows the chunk-parallel part takes at once: one MXU tile.  Two chunks of
# 64 side by side: their A, X and P are the diagonal blocks of [128, 128]
# matrices (the masks keep the chunks apart), so every product of that part
# fills the array where a chunk alone fills a quarter of it.
_TILE = 128
# The widest group of rows `kernel_tiles` gives a grid step (today's group,
# 8 chunks of 64) and the tiles of it a loop body holds (all four: the
# compiler then fills one tile's waits, the inverse's ten fp32 products one
# behind the other, with another tile's work).  On the chip at the cell's
# shape (tools/kda_scan_probe.py --sweep, ms a layer, PERF.md PR 48) the
# forward reads 3.86 / 3.72 / 3.61 at 128 / 256 / 512 rows a tile a body
# and 3.37, 3.28 at two, 3.15 at four of 512; the backward 5.80 / 5.89 /
# 5.79, then 5.31, 5.35 and 5.11.
_MAX_ROWS = 512
_UNROLL = 4
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


class Tiles(NamedTuple):
    """What a site's kernels are built from, all read from the shape."""
    rows: int          # rows a grid step: a group of chunks of one head
    chunk: int
    unroll: int        # tiles a loop body of the kernels holds
    fwd_vmem_bytes: int      # bytes of the forward's working set
    bwd_vmem_bytes: int


def working_set_bytes(rows, chunk, dim, itemsize, backward) -> int:
    """What a grid step holds in VMEM: the declared blocks twice (the
    pipeline's two buffers), the scratch (the backward's: the state every
    chunk starts from and seven values a row of what the walk back reads),
    and the fp32 values of the tile in flight (about four dozen [tile, D]
    planes, a dozen [tile, tile] squares, a few states)."""
    wide, gate, state = rows * dim * itemsize, rows * dim * 4, dim * dim * 4
    blocks = 4 * wide + gate + 4 * rows + state      # q k v out, g, beta, M
    scratch = state
    if backward:
        blocks += 3 * wide + gate + 4 * rows         # dout; dq dk dv dg dbeta
        scratch += (rows // chunk + 1) * state + 4 * wide \
            + rows * _TILE * (itemsize + 8)
    live = 48 * _TILE * dim * 4 + 12 * _TILE * _TILE * 4 + 6 * state
    return 2 * blocks + scratch + live


def kernel_tiles(batch, seq, heads, dim, chunk, dtype, rows=None,
                 unroll=None):
    """(the tiles of a site the kernel pair takes, "") or (None, why not).
    It takes heads of whole 128-lane vectors (a block spec then picks a
    head of [B, S, H D] by its lane block), chunks that tile _TILE rows at
    the operand dtype's sublane tile, more than one chunk, and a sequence
    of whole groups of rows whose working set fits; `rows` and `unroll`
    pin the group and the tiles a loop body holds for a test or the
    probe, never a model."""
    size = jnp.dtype(dtype).itemsize
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
        return None, f"operands of {jnp.dtype(dtype).name}"
    if dim % 128:
        return None, f"heads of {dim}: not whole 128-lane vectors"
    if chunk >= seq:
        return None, "the sequence is one chunk"
    if _TILE % chunk or chunk % (32 // size):
        return None, f"chunks of {chunk} do not tile {_TILE} rows"
    if seq % _TILE:
        return None, f"a sequence of {seq} is not whole tiles of {_TILE} rows"

    def fits(r):
        return working_set_bytes(r, chunk, dim, size, True) \
            <= PLAN_VMEM_BUDGET

    if rows is None:
        rows = next((r for r in (_MAX_ROWS, _MAX_ROWS // 2, _TILE)
                     if seq % r == 0 and fits(r)), None)
        if rows is None:
            return None, f"heads of {dim} do not fit the VMEM budget"
    elif seq % rows or rows % _TILE or not fits(rows):
        return None, f"groups of {rows} rows do not tile or do not fit"
    return Tiles(rows, chunk, unroll or _UNROLL,
                 working_set_bytes(rows, chunk, dim, size, False),
                 working_set_bytes(rows, chunk, dim, size, True)), ""


def engine(batch, seq, heads, dim, chunk, dtype, force="auto", rows=None,
           unroll=None):
    """The tiles where the site runs the kernel pair, None where it runs
    the jax.numpy engine: read from the shape and from what the program is
    traced for (`force`: kernels/engine.py's door).  The op reads no mesh
    (ROADMAP D25): on several devices the pair is XLA's to partition."""
    if not wants_kernels(force):
        return None
    tiles, why = kernel_tiles(batch, seq, heads, dim, chunk, dtype, rows,
                              unroll)
    if tiles is None and rows is not None:
        raise ValueError(f"gated_delta_attention: no kernels at {rows} rows "
                         f"a grid step: {why}")
    return tiles


def _dot(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=F32)


def _across(vector, eye, axis):
    """A [1, n] row as the [n, 1] column (axis 1) or the column as the row
    (axis 0): the diagonal of its broadcast, summed."""
    return jnp.sum(jnp.where(eye, vector, 0.0), axis=axis, keepdims=True)


def _running_sum(x, pos, chunk, reverse=False):
    """The running sum down the rows of every chunk (up them under
    `reverse`: its transpose), in log2 `chunk` steps of a roll."""
    s = 1
    while s < chunk:
        if reverse:
            x = x + jnp.where(pos < chunk - s, roll(x, -s), 0.0)
        else:
            x = x + jnp.where(pos >= s, roll(x, s), 0.0)
        s *= 2
    return x


def _middles(gc, pos, b):
    """[T, D]: Gc at the middle row of the block of 2b rows a row lies in,
    the point both decays of a halving are measured from."""
    T, D = gc.shape
    if 2 * b >= 8:                       # whole sublane tiles: a broadcast
        mid = gc.reshape(T // (2 * b), 2 * b, D)[:, b:b + 1, :]
        return jnp.broadcast_to(mid, (T // (2 * b), 2 * b, D)).reshape(T, D)
    at = pos % (2 * b)
    out = gc
    for p in range(2 * b):
        if p != b:
            out = jnp.where(at == p, roll(gc, p - b), out)
    return out


class _Masks:
    """The iotas of a tile, made once a grid step."""

    def __init__(self, T, C, D, head_decay=False):
        row, col = _iota((T, T), 0), _iota((T, T), 1)
        self.T, self.C, self.D, self.head_decay = T, C, D, head_decay
        self.pos = _iota((T, D), 0) % C           # a row's place in its chunk
        self.eye = row == col
        self.eye2 = _iota((2 * T, T), 0) % T == _iota((2 * T, T), 1)
        self.eye_d = _iota((D, D), 0) == _iota((D, D), 1)
        self.below = (col < row) & (row // C == col // C)
        self.halves = {}
        b = 0 if head_decay else C // 2
        while b:
            self.halves[b] = _halves(T, b, rows=2)
            b //= 2

    @property
    def lower(self):
        """The diagonal and what lies under it, a chunk at a time."""
        return self.below | self.eye


def _tile_values(q, k, v, g, beta_row, masks, eps):
    """The planes of a tile that cost no matmul: unit q and k with their
    inverse lengths, Gc, beta down the rows, and the decays."""
    q, k = q.astype(F32), k.astype(F32)
    rq = jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + eps)
    rk = jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + eps)
    T, C, D = masks.T, masks.C, masks.D
    head = {}
    if masks.head_decay:
        # g a [1, T] row, one decay a head: Gc a [T, 1] column that every
        # plane below broadcasts, and exp(Gc_r - Gc_j) ONE [T, T] square
        lower = masks.lower
        gc = jnp.sum(jnp.where(lower, g, 0.0), axis=1, keepdims=True)
        head["decay"] = jnp.where(lower, jnp.exp(jnp.minimum(
            gc - _across(gc, masks.eye, 0), 0.0)), 0.0)
    else:
        gc = _running_sum(g, masks.pos, C)
    last = [gc[c * C + C - 1:(c + 1) * C, :] for c in range(T // C)]
    last_rows = jnp.concatenate(
        [jnp.broadcast_to(t, (C, gc.shape[1])) for t in last], axis=0)
    beta = _across(beta_row, masks.eye, 1)
    qn, kn, eg = q * rq, k * rk, jnp.exp(gc)
    return dict(
        **head, qn=qn, kn=kn, rq=rq, rk=rk, gc=gc, eg=eg, beta=beta, v=v,
        ed=jnp.exp(last_rows - gc), gamma=[jnp.exp(t) for t in last],
        kb=beta * (kn * eg), vb=beta * v.astype(F32),
        qg=qn * eg * D ** -0.5)


def _halving(x, masks, mm):
    """Of every level b: (b, exp(-|Gc - Gc_mid|) [T, D], k and q times it
    in the operand dtype stacked [2T, D])."""
    b = masks.C // 2
    while b:
        e = jnp.exp(-jnp.abs(x["gc"] - _middles(x["gc"], masks.pos, b)))
        yield b, e, jnp.concatenate(
            [(x["kn"] * e).astype(mm), (x["qn"] * e).astype(mm)], axis=0)
        b //= 2


def _raw_products(x, mm):
    """[2T, T] fp32: k k^T over q k^T before any decay (one decay a head)."""
    return _dot(jnp.concatenate([x["kn"], x["qn"]], axis=0).astype(mm),
                x["kn"].astype(mm), _NT)


def _times_decay(gamma, m, masks):
    """exp(Gc_C) m: gamma a [1, D] row of the key channels' decays, or a
    head's one [1, 1]."""
    return (gamma if masks.head_decay
            else _across(gamma, masks.eye_d, 1)) * m


def _tile_local(x, masks, mm):
    """_local of a tile's chunks: (W, U, Q exp(Gc) D^-1/2, P, K exp(Gc_C -
    Gc)) in the operand dtype, and X and the decayed k-k product fp32 for
    the backward.  A row below the middle of its block has exp(Gc - Gc_mid)
    and a row above it exp(Gc_mid - Gc): ONE plane exp(-|Gc - Gc_mid|) a
    level serves both sides of the masked product."""
    T, D = masks.T, masks.D
    kn, qn = x["kn"], x["qn"]
    if masks.head_decay:
        prod = _raw_products(x, mm) * jnp.concatenate([x["decay"]] * 2,
                                                      axis=0)
    else:
        both = jnp.concatenate([kn, qn], axis=0)
        diag = jnp.sum(both * jnp.concatenate([kn, kn], axis=0), axis=-1,
                       keepdims=True)
        prod = jnp.where(masks.eye2, diag, 0.0)
        for b, _, sides in _halving(x, masks, mm):
            prod = prod + jnp.where(masks.halves[b],
                                    _dot(sides, sides[:T], _NT), 0.0)
    kk = jnp.where(masks.below, prod[:T], 0.0)
    inv = _unit_lower_inverse(kk * x["beta"], masks.C)
    wu = _dot(inv.astype(mm), jnp.concatenate(
        [x["kb"].astype(mm), x["vb"].astype(mm)], axis=1), _NN)
    return dict(w=wu[:, :D].astype(mm), u=wu[:, D:].astype(mm),
                qg=x["qg"].astype(mm), p=(prod[T:] * D ** -0.5).astype(mm),
                kd=(kn * x["ed"]).astype(mm), gamma=x["gamma"], inv=inv,
                kk=kk)


def _tile_scan(m, loc, masks, mm):
    """The tile's chunks in turn from the state m: (the state after them,
    the output [T, D] fp32, the state every chunk starts from, U' [T, D])."""
    C = masks.C
    starts, u2s, read = [], [], []
    for c, gamma in enumerate(loc["gamma"]):
        rows = slice(c * C, (c + 1) * C)
        mc = m.astype(mm)
        wq = _dot(jnp.concatenate([loc["w"][rows], loc["qg"][rows]], axis=0),
                  mc, _NN)
        u2 = (loc["u"][rows].astype(F32) - wq[:C]).astype(mm)
        starts.append(m)
        read.append(wq[C:])
        u2s.append(u2)
        m = _times_decay(gamma, m, masks) + _dot(loc["kd"][rows], u2, _TN)
    u2 = jnp.concatenate(u2s, axis=0)
    return (m, jnp.concatenate(read, axis=0) + _dot(loc["p"], u2, _NN),
            starts, u2)


def _walk(trips, body, unroll):
    """body(t) for t in 0 .. trips - 1, `unroll` trips a loop body."""
    unroll = max(1, min(unroll, trips))
    if unroll == trips:
        for t in range(trips):
            body(t)
        return

    def some(j, carry):
        for u in range(unroll):
            body(j * unroll + u)
        return carry

    jax.lax.fori_loop(0, trips // unroll, some, 0)


def _rows_of(t, T):
    import jax.experimental.pallas as pl

    return slice(t * T, (t + 1) * T) if isinstance(t, int) \
        else pl.ds(pl.multiple_of(t * T, T), T)


def _row_of(t):
    import jax.experimental.pallas as pl

    return slice(t, t + 1) if isinstance(t, int) else pl.ds(t, 1)


def _decays_of(g_ref, t, rows, masks):
    """Tile t's log-decays: [T, D] of a [B, S, H D] value (its `rows`), or
    the [1, T] row of a head's (laid out as beta is)."""
    return g_ref[0, 0, 0, _row_of(t), :] if masks.head_decay \
        else g_ref[0, rows, :]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, out_ref, start_ref,
                m_scr, *, tiles, eps):
    import jax.experimental.pallas as pl

    T, D, mm = _TILE, q_ref.shape[-1], q_ref.dtype
    head_decay = g_ref.shape != q_ref.shape
    masks = _Masks(T, tiles.chunk, D, head_decay)

    @pl.when(pl.program_id(2) == 0)
    def _the_state_starts_at_zero():
        m_scr[...] = jnp.zeros_like(m_scr)

    start_ref[0, 0, 0] = m_scr[...]

    def tile(t):
        rows = _rows_of(t, T)
        x = _tile_values(q_ref[0, rows, :], k_ref[0, rows, :],
                         v_ref[0, rows, :], _decays_of(g_ref, t, rows, masks),
                         beta_ref[0, 0, 0, _row_of(t), :], masks, eps)
        m, out, _, _ = _tile_scan(m_scr[...], _tile_local(x, masks, mm),
                                  masks, mm)
        m_scr[...] = m
        out_ref[0, rows, :] = out.astype(out_ref.dtype)

    _walk(tiles.rows // T, tile, tiles.unroll)


def _tile_pull(x, inv, kk, d, masks, mm):
    """The cotangents d = (dW, dU, dQG, dP, dKD [T, .] and d exp(Gc_C) a
    chunk) of _tile_local's values pulled back to (dq, dk, dv, dg [T, D]
    fp32, dbeta [T, 1]), written out.  The decayed products read Gc
    through differences alone, so the point a halving measures from takes
    no cotangent: dGc is x dx as a row less k dk as a column, a channel
    at a time."""
    T, C, D = masks.T, masks.C, masks.D
    dw, du, dqg, dp, dkd, dgamma = d
    qn, kn, eg, ed, beta = x["qn"], x["kn"], x["eg"], x["ed"], x["beta"]
    # W, U = X [beta K exp(Gc) | beta V]
    right = jnp.concatenate([x["kb"].astype(mm), x["vb"].astype(mm)], axis=1)
    dwu = jnp.concatenate([dw.astype(mm), du.astype(mm)], axis=1)
    dright = _dot(inv.astype(mm), dwu, _TN)
    dkb, dvb = dright[:, :D], dright[:, D:]
    da = jnp.where(masks.below, _inverse_cotangent(
        inv, _dot(dwu, right, _NT)), 0.0)
    dbeta = jnp.sum(da * kk, axis=-1, keepdims=True) \
        + jnp.sum(dkb * (kn * eg) + dvb * x["v"].astype(F32), axis=-1,
                  keepdims=True)
    # the two decayed products, level by level
    dprod = jnp.concatenate([da * beta, dp.astype(F32) * D ** -0.5], axis=0)
    if masks.head_decay:
        # prod = raw * decay: the square's cotangent goes to Gc as a row
        # sum less a column sum, the raw products' to q and k as matmuls
        decay, raw = x["decay"], _raw_products(x, mm)
        draw = (dprod * jnp.concatenate([decay] * 2, axis=0)).astype(mm)
        as_row = _dot(draw, kn.astype(mm), _NN)
        dk_col = _dot(draw, jnp.concatenate([kn, qn], axis=0).astype(mm),
                      _TN)
        ddiff = (dprod[:T] * raw[:T] + dprod[T:] * raw[T:]) * decay
        dgc = jnp.sum(ddiff, axis=1, keepdims=True) - _across(
            jnp.sum(ddiff, axis=0, keepdims=True), masks.eye, 1)
        dkn, dqn, pos = as_row[:T] + dk_col, as_row[T:], masks.pos[:, :1]

        def lanes(t):
            return jnp.sum(t, axis=-1, keepdims=True)
    else:
        on_diagonal = jnp.sum(jnp.where(masks.eye, dprod[T:], 0.0), axis=-1,
                              keepdims=True)
        as_row, dk_col = jnp.zeros((2 * T, D), F32), jnp.zeros((T, D), F32)
        for b, e, sides in _halving(x, masks, mm):
            kept = jnp.where(masks.halves[b], dprod, 0.0).astype(mm)
            as_row = as_row + _dot(kept, sides[:T], _NN) \
                * jnp.concatenate([e, e], axis=0)
            dk_col = dk_col + _dot(kept, sides, _TN) * e
        dk_row, dq_row = as_row[:T], as_row[T:]
        dgc = kn * dk_row + qn * dq_row - kn * dk_col
        dkn = dk_row + dk_col + on_diagonal * qn
        dqn, pos = dq_row + on_diagonal * kn, masks.pos

        def lanes(t):
            return t
    # K exp(Gc) beta, Q exp(Gc) D^-1/2, K exp(Gc_C - Gc), exp(Gc_C)
    dkn = dkn + dkb * (beta * eg) + dkd * ed
    dqn = dqn + dqg * (eg * D ** -0.5)
    through_last = lanes(dkd * (kn * ed))
    dgc = dgc + lanes(dkb * x["kb"]) + lanes(dqg * x["qg"]) - through_last
    dlast = jnp.concatenate([jnp.broadcast_to(
        jnp.sum(through_last[c * C:(c + 1) * C], axis=0, keepdims=True)
        + dgamma[c] * x["gamma"][c], (C, dgc.shape[1]))
        for c in range(T // C)], axis=0)
    dgc = dgc + jnp.where(pos == C - 1, dlast, 0.0)

    def unit_back(n, dn, r):
        return r * (dn - n * jnp.sum(dn * n, axis=-1, keepdims=True))

    dq, dk = unit_back(qn, dqn, x["rq"]), unit_back(kn, dkn, x["rk"])
    dv = dvb * beta
    # the running sum's transpose: up the rows of every chunk (a head's
    # decay: summed under the mask, and out as the [1, T] row g came as)
    dg = jnp.sum(jnp.where(masks.lower, dgc, 0.0), axis=0, keepdims=True) \
        if masks.head_decay \
        else _running_sum(dgc, masks.pos, C, reverse=True)
    return dq, dk, dv, dg, dbeta


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dm_scr, m_scr,
                starts_scr, w_scr, u2_scr, qg_scr, kd_scr, p_scr, inv_scr,
                kk_scr, *, tiles, eps):
    import jax.experimental.pallas as pl

    T, C, D, mm = _TILE, tiles.chunk, q_ref.shape[-1], q_ref.dtype
    trips, per = tiles.rows // T, T // C
    head_decay = g_ref.shape != q_ref.shape
    masks = _Masks(T, C, D, head_decay)
    lower = masks.lower

    @pl.when(pl.program_id(2) == 0)
    def _nothing_after_the_last_group():
        dm_scr[...] = jnp.zeros_like(dm_scr)

    def values(t):
        rows = _rows_of(t, T)
        return _tile_values(q_ref[0, rows, :], k_ref[0, rows, :],
                            v_ref[0, rows, :], _decays_of(g_ref, t, rows, masks),
                            beta_ref[0, 0, 0, _row_of(t), :], masks, eps)

    # the group's chunk states again from its start state, and what the
    # walk back reads of every tile
    m_scr[...] = start_ref[0, 0, 0]

    def ahead(t):
        rows = _rows_of(t, T)
        loc = _tile_local(values(t), masks, mm)
        m, _, starts, u2 = _tile_scan(m_scr[...], loc, masks, mm)
        m_scr[...] = m
        for c, start in enumerate(starts):
            starts_scr[t * per + c] = start
        for ref, value in ((w_scr, loc["w"]), (u2_scr, u2),
                           (qg_scr, loc["qg"]), (kd_scr, loc["kd"]),
                           (p_scr, loc["p"]), (inv_scr, loc["inv"]),
                           (kk_scr, loc["kk"])):
            ref[rows, :] = value

    _walk(trips, ahead, tiles.unroll)

    def back(j):
        t = trips - 1 - j
        rows = _rows_of(t, T)
        x = values(t)
        do = do_ref[0, rows, :]
        w, u2, qg, kd, p = (ref[rows, :] for ref in (
            w_scr, u2_scr, qg_scr, kd_scr, p_scr))
        du_own = _dot(p, do, _TN)
        dp = jnp.where(lower, _dot(do, u2, _NT), 0.0)
        dm = dm_scr[...]
        dw, du, dqg, dkd, dgamma = ([None] * per for _ in range(5))
        for c in reversed(range(per)):          # _chunk_bwd, stacked
            cut = slice(c * C, (c + 1) * C)
            m = starts_scr[t * per + c]
            mc, dmc = m.astype(mm), dm.astype(mm)
            du[c] = (du_own[cut] + _dot(kd[cut], dmc, _NN)).astype(mm)
            back_m = _dot(jnp.concatenate([du[c], do[cut]], axis=0), mc, _NT)
            dw[c], dqg[c] = -back_m[:C], back_m[C:]
            dkd[c] = _dot(u2[cut], dmc, _NT)
            dgamma[c] = jnp.sum(m * dm, axis=-1, keepdims=True)
            dm = _times_decay(x["gamma"][c], dm, masks) + _dot(
                jnp.concatenate([qg[cut], w[cut]], axis=0),
                jnp.concatenate([do[cut], -du[c]], axis=0), _TN)
        dm_scr[...] = dm
        dgamma = [jnp.sum(t_, axis=0, keepdims=True) if head_decay
                  else _across(t_, masks.eye_d, 0) for t_ in dgamma]
        stacked = [jnp.concatenate(parts, axis=0)
                   for parts in (dw, du, dqg, dkd)]
        dq, dk, dv, dg, dbeta = _tile_pull(
            x, inv_scr[rows, :], kk_scr[rows, :],
            (stacked[0], stacked[1], stacked[2], dp, stacked[3], dgamma),
            masks, mm)
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)
        if head_decay:
            dg_ref[0, 0, 0, _row_of(t), :] = dg
        else:
            dg_ref[0, rows, :] = dg
        dbeta_ref[0, 0, 0, _row_of(t), :] = _across(dbeta, masks.eye, 0)

    _walk(trips, back, tiles.unroll)


def _compiler_params(need):
    return compiler_params(("parallel", "parallel", "arbitrary"),
                                   need)


def _specs(tiles, D, last=None, ratio=1):
    """The block specs of a head's group of rows: of [B, S, H D] values,
    of beta and dbeta (and a head's one decay) [B, H, groups, tiles, T], of
    the states [B, H, groups, D, D], and of q and k [B, S, Hk D] at `ratio`
    value heads a key head: value head h reads the lane block h // ratio,
    nothing is repeated in HBM.  `last`: the grid runs the groups last to
    first."""
    import jax.experimental.pallas as pl

    def group(s):
        return s if last is None else last - s

    R, T = tiles.rows, _TILE
    wide = pl.BlockSpec((1, R, D), lambda b, h, s: (b, group(s), h))
    return (wide,
            pl.BlockSpec((1, 1, 1, R // T, T),
                         lambda b, h, s: (b, h, group(s), 0, 0)),
            pl.BlockSpec((1, 1, 1, D, D),
                         lambda b, h, s: (b, h, group(s), 0, 0)),
            wide if ratio == 1 else pl.BlockSpec(
                (1, R, D), lambda b, h, s: (b, group(s), h // ratio)))


@functools.lru_cache(maxsize=64)
def _fwd_call(B, S, H, D, tiles, dtype, eps, interpret, ratio=1,
              head_decay=False):
    """Memoized, as kernels/flash_attention.py::_fwd_call: every site of
    one shape shares one kernel payload."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R = tiles.rows
    wide, gate, state, key = _specs(tiles, D, ratio=ratio)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tiles=tiles, eps=eps),
        grid=(B, H, S // R),
        in_specs=[key, key, wide, gate if head_decay else wide, gate],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * D), jnp.dtype(dtype)),
                   jax.ShapeDtypeStruct((B, H, S // R, D, D), F32)],
        scratch_shapes=[pltpu.VMEM((D, D), F32)],
        compiler_params=_compiler_params(tiles.fwd_vmem_bytes),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=64)
def _bwd_call(B, S, H, D, tiles, dtype, eps, interpret, ratio=1,
              head_decay=False):
    """dq and dk come out a VALUE head each, [B, S, H D]: _kernels_bwd sums
    a key head's."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, T = tiles.rows, _TILE
    wide, gate, state, key = _specs(tiles, D, last=S // R - 1, ratio=ratio)
    dtype = jnp.dtype(dtype)
    by_tiles = jax.ShapeDtypeStruct((B, H, S // R, R // T, T), F32)
    decay = gate if head_decay else wide
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tiles=tiles, eps=eps),
        grid=(B, H, S // R),
        in_specs=[key, key, wide, decay, gate, state, wide],
        out_specs=[wide, wide, wide, decay, gate],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * D), dtype)] * 3
        + [by_tiles if head_decay
           else jax.ShapeDtypeStruct((B, S, H * D), F32), by_tiles],
        scratch_shapes=[pltpu.VMEM((D, D), F32), pltpu.VMEM((D, D), F32),
                        pltpu.VMEM((R // tiles.chunk, D, D), F32)]
        + [pltpu.VMEM((R, D), dtype)] * 4
        + [pltpu.VMEM((R, T), dtype), pltpu.VMEM((R, T), F32),
           pltpu.VMEM((R, T), F32)],
        compiler_params=_compiler_params(tiles.bwd_vmem_bytes),
        interpret=interpret,
    )


def _beta_by_tiles(beta, tiles):
    """beta [B, S, H] as [B, H, groups, tiles a group, T] fp32: a tile's
    betas one row of 128 lanes."""
    B, S, H = beta.shape
    return jnp.moveaxis(beta.astype(F32), 2, 1).reshape(
        B, H, S // tiles.rows, tiles.rows // _TILE, _TILE)


def _from_tiles(t, like):
    """_beta_by_tiles back: [B, S, H] in `like`'s dtype."""
    B, S, H = like.shape
    return jnp.moveaxis(t.reshape(B, H, S), 1, 2).astype(like.dtype)


def _calls_form(q, v, g, heads):
    """(value heads a key head, whether a head has one decay): the two
    calls' last arguments."""
    key_heads, head_decay = form(q, v, g, heads)
    return heads // key_heads, head_decay


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _kernels(q, k, v, g, beta, heads, tiles, eps, interpret):
    return _kernels_fwd(q, k, v, g, beta, heads, tiles, eps, interpret)[0]


def _kernels_fwd(q, k, v, g, beta, heads, tiles, eps, interpret):
    B, S, width = v.shape
    ratio, head_decay = _calls_form(q, v, g, heads)
    call = _fwd_call(B, S, heads, width // heads, tiles, str(q.dtype), eps,
                     interpret, ratio, head_decay)
    out, starts = keep(*call(
        q, k, v, _beta_by_tiles(g, tiles) if head_decay else g,
        _beta_by_tiles(beta, tiles)))
    return out, (q, k, v, g, beta, starts)


def _kernels_bwd(heads, tiles, eps, interpret, res, do):
    q, k, v, g, beta, starts = res
    B, S, width = v.shape
    D = width // heads
    ratio, head_decay = _calls_form(q, v, g, heads)
    call = _bwd_call(B, S, heads, D, tiles, str(q.dtype), eps, interpret,
                     ratio, head_decay)
    dq, dk, dv, dg, dbeta = call(
        q, k, v, _beta_by_tiles(g, tiles) if head_decay else g,
        _beta_by_tiles(beta, tiles), starts, do.astype(q.dtype))

    def a_key_heads(t):          # its value heads' cotangents, summed fp32
        if ratio == 1:
            return t
        return jnp.sum(t.reshape(B, S, heads // ratio, ratio, D), axis=3,
                       dtype=F32).astype(t.dtype).reshape(q.shape)

    return (a_key_heads(dq), a_key_heads(dk), dv,
            _from_tiles(dg, g) if head_decay else dg,
            _from_tiles(dbeta, beta))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def form(q, v, g, heads: int):
    """(key heads, whether a head has ONE decay) of a site, read from its
    operands: q (and k) [B, S, Hk D] against v [B, S, H D], and g [B, S, H]
    (a head's) against [B, S, H D] (a key channel's).  A channel's decay
    goes with as many key heads as value heads, and nothing else."""
    D = v.shape[-1] // heads
    key_heads = q.shape[-1] // D
    head_decay = g.shape[-1] == heads != v.shape[-1]
    if q.shape[-1] % D or heads % key_heads or (
            not head_decay and (g.shape != v.shape or key_heads != heads)):
        raise ValueError(
            f"gated_delta_attention: q {q.shape}, v {v.shape}, g {g.shape} "
            f"at {heads} heads: g is [B, S, H] with q, k at a divisor of H "
            "heads, or [B, S, H D] with q, k at H")
    return key_heads, head_decay


def gated_delta_attention(q, k, v, g, beta, heads: int, chunk: int = CHUNK,
                          eps: float = 1e-6, force: str = "auto",
                          rows=None, unroll=None):
    """Out [B, S, H D] in q's dtype of q, k, v (one dtype: the matmuls'
    operands), the log-decay g (<= 0; taken to fp32) and beta [B, S, H]:
    each head's q and k to unit length (fp32, `eps` under the root), then
    the module's recurrence, `chunk` tokens at a time.  Two forms, read
    from the shapes (`form`): g [B, S, H D], a decay for every key channel,
    with q, k, v [B, S, H D]; or g [B, S, H], ONE decay a head, with q, k
    [B, S, Hk D] at Hk <= H key heads, value head j reading key head j //
    (H / Hk).  The engine is read from the shape (`engine`): the kernel
    pair on the values as they come, or the jax.numpy scans on regrouped
    copies; `force`, `rows` and `unroll` are the tests' and the probe's."""
    B, S, width = v.shape
    form(q, v, g, heads)
    chunk = plan(B, S, heads, width // heads, chunk)["chunk"]
    tiles = engine(B, S, heads, width // heads, chunk, q.dtype, force, rows,
                   unroll)
    if tiles is None:
        return _scan_by_groups(q, k, v, g, beta, heads, chunk, float(eps))
    return _kernels(q, k, v, g.astype(F32), beta, heads, tiles, float(eps),
                    force == "interpret")


def _scan_by_groups(q, k, v, g, beta, heads: int, chunk: int, eps: float):
    """The jax.numpy engine: q, k, v, g regrouped to [groups, group, B,
    H, C, D] (copies in HBM, and the custom backward's residuals) for the
    two scans of _scan.  With one decay a head the heads are two axes, (Hk,
    r) of v, g and beta against (Hk, 1) of q and k: a key head's q and k
    broadcast to its r value heads inside _local, and their cotangents come
    back summed."""
    B, S, width = v.shape
    D = width // heads
    tiles = plan(B, S, heads, D, chunk)
    C, n = tiles["chunk"], tiles["group"]
    key_heads, head_decay = form(q, v, g, heads)
    H = (key_heads, heads // key_heads) if head_decay else (heads,)
    at = 3 + len(H)

    def grouped(t, heads, last):   # [B, S, H (D)] -> [groups, n, B, H, C (, D)]
        t = t.reshape((B, S // (n * C), n, C) + heads + last)
        return jnp.moveaxis(t, (1, 2, 3), (0, 1, at))

    keys = (key_heads, 1) if head_decay else H
    out = _scan(grouped(q, keys, (D,)), grouped(k, keys, (D,)),
                grouped(v, H, (D,)),
                grouped(g.astype(F32), H, () if head_decay else (D,)),
                grouped(beta.astype(F32), H, ()), float(eps))
    return jnp.moveaxis(out, (0, 1, at), (1, 2, 3)).reshape(B, S, width)
