"""Gated delta-rule linear attention with a decay for every key channel
(Kimi Delta Attention, KDA: arXiv:2510.26692 section 3; the op
gated_delta_attention, name scope `kda.scan`) as a scan over chunks of the
sequence, with a backward of its own.

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
parallel over chunks, the rest a lax.scan over them.  Both go a GROUP of
chunks at a time (an outer scan), so that what the parallel part holds at
once is a group's and not the sequence's (`plan`).  jax.custom_vjp: the
backward walks the groups last to first, computes a group's parallel part again under
jax.vjp, runs the group's chunks last to first carrying dM (written out:
_chunk_bwd), and pulls the group's cotangents back to q, k, v, g, beta.
Autodiff never sees the scan, so no state a token is ever stored.  Of the
states the forward keeps the one every GROUP starts from (16 of 2 MB a
layer at the cell's shape, where the 128 chunk states are 268 MB); the
backward makes a group's chunk states again from it (_chunk_state: the
state's update alone, a third of the scan's matmuls) before it walks the
group's chunks back.  The forward tags its output and those states with
core.compiler.keep: the backward of a recomputed layer runs no second
forward of this op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.compiler import keep

CHUNK = 64
KEPT = ("out", "states")
# what one fp32 [group, B, H, C, D] value may take: the parallel part holds
# a few dozen such values at once
_GROUP_BYTES = 8 << 20
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


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


def flops(batch: int, seq: int, heads: int, dim: int, chunk: int) -> int:
    """The algorithm's FLOPs a site, forward + backward (the backward
    twice the forward; a recomputed pass is no work of the algorithm): a
    chunk of a head takes the two decayed products at their triangles (2
    C^2 D), the triangular inverse (C^3 / 3), T applied to [K | V] (2 C^2
    D), the state read twice and written once (6 C D^2) and P U' (C^2 D)."""
    c, d = chunk, dim
    a_chunk = 5 * c * c * d + c ** 3 // 3 + 6 * c * d * d
    return 3 * batch * heads * (seq // chunk) * a_chunk


def moved_bytes(batch: int, seq: int, heads: int, dim: int,
                itemsize: int) -> int:
    """What a site's two passes have to move through HBM whatever engine
    runs them: the forward reads q, k, v (itemsize), g (fp32) and beta and
    writes out; the backward reads those and out's cotangent and writes
    the five gradients.  The chunk states are the engine's choice and are
    not counted."""
    row = batch * seq * heads
    wide, gate = row * dim, 4 * row * dim
    fwd = 3 * itemsize * wide + gate + 4 * row + itemsize * wide
    bwd = fwd + 3 * itemsize * wide + gate + 4 * row
    return fwd + bwd


# ---------------------------------------------------------------------------
# the part of a chunk that does not read the state
# ---------------------------------------------------------------------------
def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=_F32)


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
        * jnp.eye(c, dtype=_F32)
    b = c // 2
    while b:
        own, nxt = _block_starts(gc, b)
        left = x * jnp.exp(jnp.minimum(gc - own, 0.0))[..., None, :, :]
        right = kn * jnp.exp(jnp.minimum(nxt - gc, 0.0))
        out = out + jnp.where(_halves(c, b), _mm(
            "...xrd,...jd->...xrj", left.astype(mm), right.astype(mm)), 0.0)
        b //= 2
    return out


def _halves(c, b):
    """[C, C] bool: the pairs (r, j) with r in the second half and j in
    the first half of one block of 2b rows."""
    row = jnp.arange(c)
    return (row[:, None] // b == row[None, :] // b + 1) \
        & (row[:, None] // (2 * b) == row[None, :] // (2 * b))


def _unit_lower_inverse(a):
    """(I + a)^-1 of a strictly lower triangular a [..., C, C], fp32, by
    doubling the blocks: X holds the inverses of the diagonal blocks of b
    rows (I at b = 1); a block of 2b rows [[L1, 0], [A21, L2]] has the
    inverse [[X1, 0], [-X2 A21 X1, X2]], and X a X, two whole [C, C]
    matmuls, holds every X2 A21 X1 at once.  Every value made is an entry
    of the inverse: the finite series I - a + a^2 - ... in powers of a is
    shorter and loses every digit where a's entries are near 1 (a^32 has
    entries of 1e10 that cancel)."""
    c = a.shape[-1]
    x = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)
    b = 1
    while b < c:
        xax = jnp.matmul(jnp.matmul(x, a, precision=_HIGHEST), x,
                         precision=_HIGHEST)
        x = x - jnp.where(_halves(c, b), xax, 0.0)
        b *= 2
    return x


def _unit(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _local(q, k, v, g, beta, eps):
    """Of chunks [..., C, D] (beta [..., C]): (W, U, Q exp(Gc) D^-1/2, P,
    K exp(Gc_C - Gc)) in q's dtype and exp(Gc_C) [..., D] fp32."""
    mm, (c, d) = q.dtype, q.shape[-2:]
    qn, kn = _unit(q.astype(_F32), eps), _unit(k.astype(_F32), eps)
    gc = jnp.cumsum(g, axis=-2)
    kk, qk = jnp.moveaxis(_decayed_products(qn, kn, gc, mm), -3, 0)
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
    return u.astype(_F32) - _mm("...cd,...dv->...cv", w, mc)


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
    return before, (
        (-_mm("...cv,...dv->...cd", du2, mc)).astype(mm), du2,
        _mm("...cv,...dv->...cd", do, mc).astype(mm),
        _mm("...rv,...jv->...rj", do, u2).astype(mm),
        _mm("...cv,...dv->...cd", u2, dmc).astype(mm),
        jnp.sum(m * dm, axis=-1))


# ---------------------------------------------------------------------------
# groups of chunks: arrays [groups, group, B, H, C, D]
# ---------------------------------------------------------------------------
def _forward(q, k, v, g, beta, eps):
    """(out [groups, group, B, H, C, D], the state every group starts
    from [groups, B, H, D, D])."""
    def group(m, xs):
        after, out = jax.lax.scan(_chunk_fwd, m, _local(*xs, eps))
        return after, (out, m)

    zero = jnp.zeros(q.shape[2:4] + (q.shape[-1],) * 2, _F32)
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


def gated_delta_attention(q, k, v, g, beta, heads: int, chunk: int = CHUNK,
                          eps: float = 1e-6):
    """Out [B, S, H D] in q's dtype of q, k, v [B, S, H D] (one dtype: the
    matmuls' operands), the log-decay g [B, S, H D] (<= 0; taken to fp32)
    and beta [B, S, H]: each head's q and k to unit length (fp32, `eps`
    under the root), then the module's recurrence, `chunk` tokens at a
    time."""
    B, S, width = q.shape
    D = width // heads
    tiles = plan(B, S, heads, D, chunk)
    C, n = tiles["chunk"], tiles["group"]

    def grouped(t, last):        # [B, S, H (D)] -> [groups, n, B, H, C (, D)]
        t = t.reshape((B, S // (n * C), n, C, heads) + last)
        return jnp.moveaxis(t, (1, 2, 4), (0, 1, 3))

    out = _scan(*(grouped(t, (D,)) for t in (q, k, v)),
                grouped(g.astype(_F32), (D,)),
                grouped(beta.astype(_F32), ()), float(eps))
    return jnp.moveaxis(out, (0, 1, 3), (1, 2, 4)).reshape(B, S, width)
