"""EVA attention in its chunked form (Zheng et al., "Efficient Attention via
Control Variates", ICLR 2023, as EvaByte runs it): a query attends exactly
to the keys of its own window and, in the SAME softmax, to one learned
summary of every chunk of every window before it.

For head h, q, k, v [S, D] rotated already, mu_h and phi_h in R^D, windows
of `window` positions and chunks of `chunk` (a divisor of the window):

  chunk j (positions c j .. c j + c - 1):
      k^_j = sum_s softmax_s(mu_h . k_s) k_s
      v^_j = sum_s softmax_s(phi_h . k_s) v_s      (both over the c positions)
  query t, in window W = t // window:
      exact keys   {s : s // window = W, s <= t}     scores q_t . k_s  scale
      summaries    {j : j < (window / c) W}          scores q_t . k^_j scale
      o_t = one softmax over both sets, times [v_s ; v^_j]

One engine, `attend`: two calls of flash_attention that hand out their
rows' logsumexp, merged exactly (merge_attention): the windows folded into
the batch-head axis (a free reshape of [B, H, S, D], S = windows x window)
for the causal part, and the same queries over the summaries with
`k_lengths` = (window / c) x the window's index for the prefix (0 keys for
the first window: weight 0).  Chosen over one kernel pair with a two-part
key axis because both parts are shapes the two flash kernels already run
and plan (a window is 32 x 2048 x 128 causal at EvaByte's cut, the kernels'
first tuned shape), the logsumexp's cotangent costs the backward nothing
(dS = P (dP - D + dlse): it is taken off D before the kernel), what each
call keeps through its layer's recomputation is flash's (out and lse), and
the merge is one fused elementwise pass over [S, D] a head.  On a TPU no
[S, S] and no [S, S / c] score array exists outside VMEM.  The summaries
are repeated once a window ([windows, S / c, D] a head: 1 / c of K), and
only the chunks some query sees are pooled at all (the last window's are
not).  Off the TPU and on a mesh the two calls are flash_attention's own
jax.numpy fallback (`force` "jax": a window's dense masked scores), merged
the same way: no second implementation of the arithmetic.

`pool` is jax.numpy everywhere: it reads K and V once and writes 1 / c of
them; XLA fuses it (multiplies and reductions on the VPU, no matmul).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import (flash_attention, kept, kept_bytes,
                              merge_attention)

__all__ = ["pool", "attend", "eva_attention", "geometry", "pairs",
           "flash_sites", "kept_by_flash"]


def geometry(seq: int, window: int, chunk: int) -> dict:
    """How a sequence of `seq` positions is cut: `window` (the sequence
    itself where it is shorter), `windows` (the last may be short),
    `per_window` chunks a window, `pooled` positions that are pooled at all
    (every window but the last) and their `chunks`."""
    window = min(int(window), seq)
    chunk = int(chunk)
    if window % chunk and seq > window:
        raise ValueError(f"eva_attention: chunks of {chunk} do not cut a "
                         f"window of {window}")
    windows = -(-seq // window)
    pooled = (windows - 1) * window
    return dict(window=window, windows=windows,
                per_window=window // chunk if windows > 1 else 0,
                pooled=pooled, chunks=pooled // chunk)


def pairs(seq: int, window: int, chunk: int) -> tuple:
    """(query-key pairs inside the windows, query-summary pairs) that one
    head's mask lets through, static."""
    geo = geometry(seq, window, chunk)
    t = np.arange(seq, dtype=np.int64)
    return (int((t % geo["window"] + 1).sum()),
            int((t // geo["window"] * geo["per_window"]).sum()))


def pool(k, v, mu, phi, chunk: int):
    """(k^, v^) [B, H, S / chunk, D] of k, v [B, H, S, D] and mu, phi
    [H, D]: the chunk's keys weighted by softmax(mu . k), its values by
    softmax(phi . k), both over the chunk's positions; fp32 inside, k's and
    v's dtype out."""
    B, H, S, D = k.shape
    n = S // chunk
    kc = k.reshape(B, H, n, chunk, D).astype(jnp.float32)
    vc = v.reshape(B, H, n, chunk, v.shape[-1]).astype(jnp.float32)

    def weights(w):                                   # [B, H, n, chunk, 1]
        scores = jnp.sum(kc * w.astype(jnp.float32)[None, :, None, None, :],
                         axis=-1, keepdims=True)
        return jax.nn.softmax(scores, axis=3)

    k_hat = jnp.sum(weights(mu) * kc, axis=3)
    v_hat = jnp.sum(weights(phi) * vc, axis=3)
    return k_hat.astype(k.dtype), v_hat.astype(v.dtype)


def _windows(t, geo):
    """[B, H, S, D] -> [B, H, windows, window, D], zero rows after the
    last position where the last window is short."""
    B, H, S, D = t.shape
    short = geo["windows"] * geo["window"] - S
    if short:
        t = jnp.pad(t, ((0, 0), (0, 0), (0, short), (0, 0)))
    return t.reshape(B, H, geo["windows"], geo["window"], D)


def flash_sites(q, geo) -> list:
    """[(queries, keys, causal)], the shapes of attend's flash calls
    for q [B, H, S, D]: a window a batch row of one head over its own keys,
    and (where there is more than one window) the same queries over the
    pooled chunks."""
    B, H, _, D = q.shape
    rows = B * H * geo["windows"]
    own = jax.ShapeDtypeStruct((rows, 1, geo["window"], D), q.dtype)
    if geo["windows"] == 1:
        return [(own, own, True)]
    return [(own, own, True),
            (own, jax.ShapeDtypeStruct((rows, 1, geo["chunks"], D), q.dtype),
             False)]


def kept_by_flash(q, geo) -> tuple:
    """(names, bytes) of what attend's calls keep through the
    recomputation of the unit around them (flash_attention's `kept`)."""
    names, held = (), 0
    for queries, keys, causal in flash_sites(q, geo):
        site = kept(queries, keys, keys, causal)
        names += site
        held += kept_bytes(queries, keys) if site else 0
    return names, held


def attend(q, k, v, k_hat, v_hat, geo, force="auto"):
    """The attention alone (module docstring): [B, H, S, Dv]."""
    B, H, S, D = q.shape
    scale = D ** -0.5
    if geo["windows"] == 1:
        return flash_attention(q, k, v, causal=True, scale=scale, force=force)
    rows = B * H * geo["windows"]

    def fold(t):               # a window a batch row of one head: free
        t = _windows(t, geo)
        return t.reshape(rows, 1, geo["window"], t.shape[-1])

    def before(t):             # every window's copy of the summaries
        n = t.shape[2]
        t = jnp.broadcast_to(t[:, :, None],
                             (B, H, geo["windows"]) + t.shape[2:])
        return t.reshape(rows, 1, n, t.shape[-1])

    qf = fold(q)
    own = flash_attention(qf, fold(k), fold(v), causal=True, scale=scale,
                          force=force, return_lse=True)
    seen = jnp.tile(jnp.arange(geo["windows"], dtype=jnp.int32)
                    * geo["per_window"], B * H)
    summaries = flash_attention(qf, before(k_hat), before(v_hat), scale=scale,
                                k_lengths=seen, force=force, return_lse=True)
    out = merge_attention([own, summaries])
    return out.reshape(B, H, -1, out.shape[-1])[:, :, :S]


def eva_attention(q, k, v, mu, phi, window: int, chunk: int, force="auto"):
    """EVA attention of q, k, v [B, H, S, D] (rotated) under a head's mu,
    phi [H, D], scores times D^-1/2: [B, H, S, D].  `force` is
    flash_attention's: "auto" (the Pallas kernels for a TPU, jax.numpy
    elsewhere), "pallas", "interpret" (the kernels through the Pallas
    interpreter: the CPU tests' door) or "jax".  The pooling runs under
    the name scope `eva.pool`, the attention under `eva.attend`."""
    geo = geometry(q.shape[2], window, chunk)
    k_hat = v_hat = None
    if geo["windows"] > 1:
        with jax.named_scope("eva.pool"):
            k_hat, v_hat = pool(k[:, :, :geo["pooled"]],
                                v[:, :, :geo["pooled"]], mu, phi, chunk)
    with jax.named_scope("eva.attend"):
        return attend(q, k, v, k_hat, v_hat, geo, force)
