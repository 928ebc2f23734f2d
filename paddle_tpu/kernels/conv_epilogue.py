"""Fused conv + BN-stats + normalize/residual/activation (Pallas, TPU).

Reference counterpart: conv2d_fusion — cuDNN's fused
conv+bias+activation op (/root/reference/paddle/fluid/operators/
conv_fusion_op.cu.cc:1).  This is the TPU-native answer to a
minimal-traffic analysis: with XLA owning convs, BN's
batch statistics force extra full passes over every conv output, which
bounds XLA-conv ResNet-50 near MFU ~0.20 on v5e.  Fusing the stats
accumulation INTO the conv pass and the normalize/residual/relu into
one epilogue pass cuts the per-conv activation traffic from ~4-5
passes to 3 (conv-write, epilogue-read, y-write):

  kernel 1  conv_stats:   out = conv(x, w) written ONCE, with
            per-channel sum / sum-of-squares accumulated in VMEM
            scratch across the batch grid — the separate BN-stats pass
            over `out` disappears.
  (host)    mean/var/inv from the two [F] vectors — O(F) work.
  kernel 2  bn_epilogue:  y = act((out - mean) * inv * gamma + beta
            + z) — normalize, residual add, and activation in one
            read-modify-write pass.

Layout is NHWC (the TPU-preferred layout FLAGS_conv_layout=auto picks
on chip); the lane dimension carries channels, so the per-tap matmuls
([Ho*Wo, C] x [C, F]) drive the MXU directly and the stats reductions
are lane-wise VPU sums.  Weights are [K, K, C, F].

Status: model-integrated.  FLAGS_fuse_conv_epilogue (core/fusion.py)
pattern-matches conv2d -> batch_norm [-> add] [-> relu] chains at
compile time and routes them through the conv_bn_add_act op, whose
pallas implementation is this kernel pair; make_conv_bn_act's backward
is the ANALYTIC vjp through the two-kernel decomposition (kernel 1's
conv output, already in HBM, is the BN-backward residual — the earlier
recompute-the-chain backward re-ran the conv and is what the round-5
one-op chip A/B lost on; it remains as the bwd="reference" A/B arm).
The chip-less v5e cost model (core/aot_tpu.py) prices the fused kernel
chain at ~0.63x the unfused XLA chain's bytes on ResNet-50 block shapes
(asserted in tests/test_aot_cost.py); the flag still defaults OFF until
a chip A/B banks the end-to-end win — at the PROGRAM level the custom
calls pin row-major layouts while XLA prefers {3,0,2,1} for conv
tensors, and those boundary relayout copies are the open cost
(ROADMAP open items).

Blocking: the grid runs over (batch, row tiles).  The stride-1
whole-image path DMAs the raw image and builds the padding halo in VMEM
scratch (no host-side jnp.pad materialization).  Shapes whose image
exceeds the ~12 MB VMEM tile budget take halo-free row tiling: output
rows split into the smallest divisor tiling that fits, with the
overlapping phase-plane row windows pre-sliced host-side (halo rows
only) so every kernel block stays contiguous — big non-ResNet images
(VGG 224x224x64) now compile instead of bailing.  pallas_viable()
reports whether a shape has a plan; the op lowering falls back to the
reference composition when it does not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["conv_bn_act", "conv_bn_act_reference", "make_conv_bn_act",
           "pallas_viable"]


def _phase_decompose(xp, stride, K, Ho, Wo):
    """[N, Hp, Wp, C] padded input -> [N, s*s, Hd, Wd, C] stride-phase
    planes: plane (ph, pw) holds xp[:, ph::s, pw::s, :], zero-padded to
    the uniform (Hd, Wd).  Done OUTSIDE the pallas kernel (XLA lowers
    strided slices fine; Mosaic does not), so every in-kernel tap read
    is a contiguous window.  For s=1 this is just an expand_dims."""
    s = stride
    N, Hp, Wp, C = xp.shape
    if s == 1:
        return xp[:, None]
    Hd, Wd = _plane_dims(Hp, Wp, s, K, Ho, Wo)
    planes = []
    for ph in range(s):
        for pw in range(s):
            p = xp[:, ph::s, pw::s, :]
            planes.append(jnp.pad(p, (
                (0, 0), (0, Hd - p.shape[1]), (0, Wd - p.shape[2]),
                (0, 0))))
    return jnp.stack(planes, axis=1)


def conv_bn_act_reference(x, w, gamma, beta, z=None, *, stride=1,
                          padding="SAME", eps=1e-5, act="relu", groups=1):
    """Pure-jax reference: XLA conv + batch-norm + residual + act.
    x: [N, H, W, C] NHWC; w: [K, K, C//groups, F].
    Returns (y, mean, var)."""
    pad = ([(padding, padding)] * 2 if isinstance(padding, int)
           else padding)
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )
    of = out.astype(jnp.float32)
    mean = jnp.mean(of, axis=(0, 1, 2))
    var = jnp.var(of, axis=(0, 1, 2))
    inv = jax.lax.rsqrt(var + eps)
    y = (of - mean) * inv * gamma.astype(jnp.float32) + beta.astype(
        jnp.float32)
    if z is not None:
        y = y + z.astype(jnp.float32)
    if act == "relu":
        y = jax.nn.relu(y)
    elif act:
        raise ValueError(f"unsupported act {act!r}")
    return y.astype(x.dtype), mean, var


def _accum_taps(xplane_at, w_ref, K, stride, Ht, Wo, C):
    """Sum of per-tap matmuls over a (phase-decomposed) image region:
    xplane_at(phase) -> [Hd_t, Wd, C] plane; tap (kh, kw) reads the
    CONTIGUOUS window [kh//s : kh//s + Ht] of phase (kh%s, kw%s) (Mosaic
    cannot lower strided vector slices — chip-only failure caught by the
    TPU lowering gate, hence the host-side stride-phase decomposition)."""
    s = stride
    acc = None
    for kh in range(K):
        for kw in range(K):
            xs = jax.lax.slice(
                xplane_at((kh % s) * s + (kw % s)),
                (kh // s, kw // s, 0),
                (kh // s + Ht, kw // s + Wo, C),
            )                         # [Ht, Wo, C], stride-1 slice
            xm = xs.reshape(Ht * Wo, C)
            tap = jnp.dot(xm, w_ref[kh, kw],
                          preferred_element_type=jnp.float32)
            acc = tap if acc is None else acc + tap
    return acc


def _stats_update(pl, out_ref, sum_ref, sumsq_ref, acc, first, Ht):
    """Write the conv tile and accumulate per-channel sum/sumsq in the
    [1, F] stats refs across the sequential grid (every step maps to the
    same stats block; `first` resets them on the first step)."""
    out_ref[0] = acc.reshape(Ht, -1, out_ref.shape[-1]).astype(out_ref.dtype)

    @pl.when(first)
    def _init():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        sumsq_ref[:] = jnp.zeros_like(sumsq_ref)

    sum_ref[:] += jnp.sum(acc, axis=0, keepdims=True)
    sumsq_ref[:] += jnp.sum(acc * acc, axis=0, keepdims=True)


def _conv_stats_kernel(x_ref, w_ref, out_ref, sum_ref, sumsq_ref,
                       *, K, stride, Ht, Wo):
    """Grid (N, T): one (row tile of a) phase-decomposed padded image per
    step; x block [1, 1, s*s, Hd_t, Wd, C] (host-prepared, see
    _phase_decompose / _row_tiles)."""
    import jax.experimental.pallas as pl

    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)
    C = x_ref.shape[-1]
    acc = _accum_taps(lambda p: x_ref[0, 0, p], w_ref, K, stride, Ht, Wo, C)
    _stats_update(pl, out_ref, sum_ref, sumsq_ref, acc, first, Ht)


def _conv_stats_kernel_inpad(x_ref, w_ref, out_ref, sum_ref, sumsq_ref,
                             *, K, Ho, Wo, pads):
    """Stride-1 whole-image variant that pads INSIDE the kernel: the
    x block is the raw [1, H, W, C] image and the halo is built as a
    VMEM value (jnp.pad), so the host-side jnp.pad materialization (a
    full extra read+write of x per conv in HBM) disappears from the
    lowered module.  fp32 only: Mosaic's sub-32-bit multi-row shifts are
    unimplemented, so bf16 inputs take the host-padded path (the
    chip-less full-compile gate, not interpret tests, caught both)."""
    import jax.experimental.pallas as pl

    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)
    C = x_ref.shape[3]
    xp = jnp.pad(x_ref[0], (pads[0], pads[1], (0, 0)))
    acc = _accum_taps(lambda p: xp, w_ref, K, 1, Ho, Wo, C)
    _stats_update(pl, out_ref, sum_ref, sumsq_ref, acc, first, Ho)


def _bn_epilogue_kernel(out_ref, mean_ref, inv_ref, gamma_ref, beta_ref,
                        z_ref, y_ref, *, act, has_z):
    """Grid (N, T): y = act((out - mean) * inv * gamma + beta [+ z]) in
    one read-modify-write pass over a row tile of the conv output."""
    out = out_ref[0].astype(jnp.float32)          # [Ht, Wo, F]
    y = (out - mean_ref[0]) * inv_ref[0] * gamma_ref[0] + beta_ref[0]
    if has_z:
        y = y + z_ref[0].astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    y_ref[0] = y.astype(y_ref.dtype)


# Per-step VMEM budget for tile planning: 3/4 of the authoritative v5e
# VMEM constant (analysis/pallas.py — the same envelope the linter's
# vmem-overflow detector prices every pallas_call against); the margin
# covers pallas double-buffering and Mosaic temporaries.  A tile plan
# that fits this budget can never trip the linter's 16 MiB gate.
from ..analysis.pallas import V5E_VMEM_BYTES as _V5E_VMEM_BYTES

_VMEM_BUDGET = (3 * _V5E_VMEM_BYTES) // 4


def _geometry(H, W, K, stride, padding):
    """(Ho, Wo, pads) for the kernel's padding vocabulary."""
    if padding == "SAME":
        Ho = -(-H // stride)
        Wo = -(-W // stride)
        pad_h = max((Ho - 1) * stride + K - H, 0)
        pad_w = max((Wo - 1) * stride + K - W, 0)
        pads = ((pad_h // 2, pad_h - pad_h // 2),
                (pad_w // 2, pad_w - pad_w // 2))
    elif padding == "VALID":
        Ho = (H - K) // stride + 1
        Wo = (W - K) // stride + 1
        pads = ((0, 0), (0, 0))
    elif isinstance(padding, int):
        # fluid-style explicit symmetric padding (conv2d's `padding` attr)
        Ho = (H + 2 * padding - K) // stride + 1
        Wo = (W + 2 * padding - K) // stride + 1
        pads = ((padding, padding), (padding, padding))
    else:
        raise ValueError(
            f"padding must be SAME, VALID or an int, got {padding!r}")
    return Ho, Wo, pads


def _plane_dims(Hp, Wp, s, K, Ho, Wo):
    """Uniform stride-phase plane dims — the ONE copy of this geometry,
    used both by _phase_decompose (building the planes) and _plan
    (budgeting tiles against them).  Every tap (kh, kw) reads
    [kh//s : kh//s + Ho] of its phase, so the plane must cover the
    deepest such window."""
    if s == 1:
        return Hp, Wp
    Hd = max(max(-(-(Hp - ph) // s) for ph in range(s)), (K - 1) // s + Ho)
    Wd = max(max(-(-(Wp - pw) // s) for pw in range(s)), (K - 1) // s + Wo)
    return Hd, Wd


def _row_tiles(Ho, fits):
    """Smallest divisor split of the output rows whose tile satisfies
    `fits(Ht)`; None when even single-row tiles do not fit."""
    for T in range(1, Ho + 1):
        if Ho % T == 0 and fits(Ho // T):
            return T, Ho // T
    return None


def _plan(N, H, W, C, F, K, stride, padding, itemsize):
    """Tile plan for the kernel pair: (conv_T, conv_Ht, epi_T, epi_Ht),
    or None when some tile cannot fit VMEM.  Halo-free row tiling: the
    host pre-slices overlapping phase-plane row windows, so every kernel
    block is contiguous — the follow-on the round-5 docstring deferred,
    now load-bearing for bigger-than-VMEM (non-ResNet) images."""
    Ho, Wo, pads = _geometry(H, W, K, stride, padding)
    Hp = H + pads[0][0] + pads[0][1]
    Wp = W + pads[1][0] + pads[1][1]
    Hd, Wd = _plane_dims(Hp, Wp, stride, K, Ho, Wo)
    halo = (K - 1) // stride
    wbytes = K * K * C * F * itemsize

    def conv_fits(Ht):
        xblk = stride * stride * (Ht + halo) * Wd * C * itemsize
        oblk = Ht * Wo * F * itemsize
        return 2 * xblk + wbytes + 2 * oblk < _VMEM_BUDGET

    def epi_fits(Ht):
        return 2 * 3 * Ht * Wo * F * itemsize < _VMEM_BUDGET

    conv = _row_tiles(Ho, conv_fits)
    epi = _row_tiles(Ho, epi_fits)
    if conv is None or epi is None:
        return None
    return conv + epi


def pallas_viable(N, H, W, C, F, K, stride=1, padding="SAME",
                  dtype="float32", groups=1):
    """True when the pallas kernel pair supports this conv shape — used
    by the op lowering (and the fusion pass) to fall back to the
    reference composition instead of failing at compile time.

    Beyond the VMEM tile plan, this encodes the MEASURED Mosaic support
    envelope from the chip-less full-compile sweep (core/aot_tpu.py;
    this jaxlib's Mosaic, v5e target): K=1 convs compile at any dtype
    and stride as long as the output tile is at least one (8,)-sublane
    row; K>1 needs the fp32 in-VMEM padding path with a sublane-aligned
    output width (unaligned tap windows hit 'non-native tiling', and
    sub-32-bit pads hit unimplemented multi-row shifts).  Everything
    else falls back — explicitly, not at compile time."""
    if groups != 1:
        return False
    try:
        itemsize = jnp.dtype(dtype).itemsize
        Ho, Wo, _ = _geometry(H, W, K, stride, padding)
        if _plan(N, H, W, C, F, K, stride, padding, itemsize) is None:
            return False
    except ValueError:
        return False
    if K == 1:
        return min(Ho, Wo) >= 8
    return stride == 1 and itemsize == 4 and Wo % 8 == 0 and Ho >= 8


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "eps", "act", "interpret",
                     "return_conv"),
)
def conv_bn_act(x, w, gamma, beta, z=None, *, stride=1, padding="SAME",
                eps=1e-5, act="relu", interpret=False, return_conv=False):
    """Fused conv2d + batch-norm(batch stats) + residual + activation.

    x: [N, H, W, C] NHWC; w: [K, K, C, F]; gamma/beta: [F];
    z: optional [N, Ho, Wo, F] residual.  Returns (y, mean, var) with
    mean/var the fp32 batch statistics (callers update moving stats).
    return_conv=True additionally returns the raw conv output — it is
    already materialized in HBM (kernel 1's output feeding kernel 2), so
    the trainable wrapper stashes it as the batch-norm backward residual
    for free instead of recomputing the conv in backward.
    """
    import jax.experimental.pallas as pl

    if act not in ("relu", "", None):
        raise ValueError(f"unsupported act {act!r} (relu or none)")
    N, H, W, C = x.shape
    K, K2, C2, F = w.shape
    if K != K2 or C != C2:
        raise ValueError(f"weight shape {w.shape} incompatible with x {x.shape}")
    Ho, Wo, pads = _geometry(H, W, K, stride, padding)
    itemsize = jnp.dtype(x.dtype).itemsize
    plan = _plan(N, H, W, C, F, K, stride, padding, itemsize)
    if plan is None:
        raise ValueError(
            f"conv_bn_act: shape N={N} H={H} W={W} C={C} F={F} K={K} "
            f"stride={stride} exceeds the VMEM tile budget even at "
            "single-row tiles; use conv_bn_act_reference")
    Tc, Htc, Te, Hte = plan
    needs_pad = any(p for pp in pads for p in pp)
    s = stride

    if s == 1 and Tc == 1 and needs_pad and itemsize == 4:
        # stride-1 whole-image path pads in VMEM: no host-side jnp.pad
        # materialization (a full extra read+write of x in HBM per conv)
        out, ssum, ssq = pl.pallas_call(
            functools.partial(_conv_stats_kernel_inpad, K=K, Ho=Ho, Wo=Wo,
                              pads=pads),
            grid=(N, 1),
            in_specs=[
                pl.BlockSpec((1, H, W, C), lambda n, t: (n, 0, 0, 0)),
                pl.BlockSpec((K, K, C, F), lambda n, t: (0, 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, Ho, Wo, F), lambda n, t: (n, 0, 0, 0)),
                pl.BlockSpec((1, F), lambda n, t: (0, 0)),
                pl.BlockSpec((1, F), lambda n, t: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((N, Ho, Wo, F), x.dtype),
                jax.ShapeDtypeStruct((1, F), jnp.float32),
                jax.ShapeDtypeStruct((1, F), jnp.float32),
            ],
            interpret=interpret,
        )(x, w)
    else:
        xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0))) \
            if needs_pad else x
        xd = _phase_decompose(xp, s, K, Ho, Wo)
        Hd, Wd = xd.shape[2], xd.shape[3]
        if Tc == 1:
            xt = xd[:, None]              # free reshape, no halo copies
            Hdt = Hd
        else:
            # halo-free tiling: overlapping row windows are materialized
            # host-side (halo rows only), so each kernel block stays a
            # contiguous window of its tile
            Hdt = Htc + (K - 1) // s
            xt = jnp.stack(
                [jax.lax.slice_in_dim(xd, t * Htc, t * Htc + Hdt, axis=2)
                 for t in range(Tc)], axis=1)
        out, ssum, ssq = pl.pallas_call(
            functools.partial(_conv_stats_kernel, K=K, stride=s,
                              Ht=Htc, Wo=Wo),
            grid=(N, Tc),
            in_specs=[
                pl.BlockSpec((1, 1, s * s, Hdt, Wd, C),
                             lambda n, t: (n, t, 0, 0, 0, 0)),
                pl.BlockSpec((K, K, C, F), lambda n, t: (0, 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, Htc, Wo, F), lambda n, t: (n, t, 0, 0)),
                pl.BlockSpec((1, F), lambda n, t: (0, 0)),
                pl.BlockSpec((1, F), lambda n, t: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((N, Ho, Wo, F), x.dtype),
                jax.ShapeDtypeStruct((1, F), jnp.float32),
                jax.ShapeDtypeStruct((1, F), jnp.float32),
            ],
            interpret=interpret,
        )(xt, w)

    count = N * Ho * Wo
    mean = ssum[0] / count
    var = jnp.maximum(ssq[0] / count - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)

    has_z = z is not None
    zz = z if has_z else jnp.zeros((N, 1, 1, F), x.dtype)
    y = pl.pallas_call(
        functools.partial(_bn_epilogue_kernel, act=act, has_z=has_z),
        grid=(N, Te),
        in_specs=[
            pl.BlockSpec((1, Hte, Wo, F), lambda n, t: (n, t, 0, 0)),
            pl.BlockSpec((1, F), lambda n, t: (0, 0)),
            pl.BlockSpec((1, F), lambda n, t: (0, 0)),
            pl.BlockSpec((1, F), lambda n, t: (0, 0)),
            pl.BlockSpec((1, F), lambda n, t: (0, 0)),
            pl.BlockSpec(
                (1, Hte, Wo, F) if has_z else (1, 1, 1, F),
                lambda n, t: (n, t, 0, 0) if has_z else (n, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hte, Wo, F), lambda n, t: (n, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Ho, Wo, F), x.dtype),
        interpret=interpret,
    )(out, mean[None, :], inv[None, :], gamma[None, :].astype(jnp.float32),
      beta[None, :].astype(jnp.float32), zz)

    if return_conv:
        return y, mean, var, out
    return y, mean, var


def _conv_only(x, w, stride, padding):
    """The exact conv the kernel pair computes (shared with the backward's
    jax.vjp so dx/dw are XLA's own conv gradients)."""
    pad = ([(padding, padding)] * 2 if isinstance(padding, int)
           else padding)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def make_conv_bn_act(*, has_residual=True, stride=1, padding="SAME",
                     eps=1e-5, act="relu", interpret=False,
                     bwd="analytic"):
    """Trainable wrapper: pallas kernels forward, analytic backward.

    Returns f(x, w, gamma, beta[, z]) -> (y, mean, var) with a
    jax.custom_vjp.  Forward runs the fused pallas pair (3 activation
    passes).  Backward (bwd="analytic", the default) is the vjp through
    the two-kernel decomposition: kernel 1's conv output is ALREADY
    materialized in HBM (it feeds kernel 2), so it is stashed as the
    batch-norm backward residual and the backward runs the closed-form
    BN/act gradient plus XLA's own conv gradients — the same residual
    set and traffic class as the unfused chain's backward.  The earlier
    recompute design (bwd="reference": re-derive the whole chain under
    jax.vjp) re-ran the conv in backward, which the v5e cost model
    prices at ~1.5x the unfused step's bytes — that is the shape of the
    round-5 chip A/B loss (1463 vs 2246 img/s), so recompute is kept
    only as an explicit A/B arm.  Gradient parity with jax.grad of the
    XLA chain is the test contract (tests/test_conv_epilogue.py)."""
    cfg = dict(stride=stride, padding=padding, eps=eps, act=act)

    def ref(x, w, gamma, beta, z):
        return conv_bn_act_reference(x, w, gamma, beta, z, **cfg)

    def fwd_run(x, w, gamma, beta, z):
        y, mean, var, out = conv_bn_act(
            x, w, gamma, beta, z, interpret=interpret, return_conv=True,
            **cfg)
        return (y, mean, var), (x, w, out, gamma, beta, y, mean, var)

    def analytic_bwd(res, cots):
        x, w, out, gamma, beta, y, mean, var = res
        dy, dmean, dvar = cots
        f32 = jnp.float32
        count = out.shape[0] * out.shape[1] * out.shape[2]
        inv = jax.lax.rsqrt(var + eps)
        g = dy.astype(f32)
        if act == "relu":
            # y > 0 <=> pre-act > 0, and relu'(0) = 0 matches jax.nn.relu
            g = jnp.where(jnp.asarray(y, f32) > 0.0, g, 0.0)
        of = out.astype(f32)
        xhat = (of - mean) * inv
        dgamma = jnp.sum(g * xhat, axis=(0, 1, 2))
        dbeta = jnp.sum(g, axis=(0, 1, 2))
        dxhat = g * gamma.astype(f32)
        m1 = jnp.mean(dxhat, axis=(0, 1, 2))
        m2 = jnp.mean(dxhat * xhat, axis=(0, 1, 2))
        dout = inv * (dxhat - m1 - xhat * m2)
        # cotangents on the mean/var outputs (the parity tests drive
        # them; the moving-stat update path is stop-gradient in models)
        if dmean is not None:
            dout = dout + dmean.astype(f32) / count
        if dvar is not None:
            dout = dout + dvar.astype(f32) * 2.0 * (of - mean) / count
        _, conv_vjp = jax.vjp(
            lambda xx, ww: _conv_only(xx, ww, stride, padding), x, w)
        dx, dw = conv_vjp(dout.astype(out.dtype))
        grads = (dx, dw, dgamma.astype(gamma.dtype),
                 dbeta.astype(beta.dtype))
        if has_residual:
            grads += (g.astype(y.dtype),)
        return grads

    if has_residual:
        @jax.custom_vjp
        def f(x, w, gamma, beta, z):
            return conv_bn_act(x, w, gamma, beta, z, interpret=interpret,
                               **cfg)

        def fwd(x, w, gamma, beta, z):
            if bwd == "analytic":
                return fwd_run(x, w, gamma, beta, z)
            return f(x, w, gamma, beta, z), (x, w, gamma, beta, z)

        def fbwd(res, cots):
            if bwd == "analytic":
                return analytic_bwd(res, cots)
            _, vjp = jax.vjp(ref, *res)
            return vjp(cots)

        f.defvjp(fwd, fbwd)
        return f

    @jax.custom_vjp
    def h(x, w, gamma, beta):
        return conv_bn_act(x, w, gamma, beta, None, interpret=interpret,
                           **cfg)

    def hfwd(x, w, gamma, beta):
        if bwd == "analytic":
            return fwd_run(x, w, gamma, beta, None)
        return h(x, w, gamma, beta), (x, w, gamma, beta)

    def hbwd(res, cots):
        if bwd == "analytic":
            return analytic_bwd(res, cots)
        x, w, gamma, beta = res
        _, vjp = jax.vjp(lambda a, b, c, d: ref(a, b, c, d, None),
                         x, w, gamma, beta)
        return vjp(cots)

    h.defvjp(hfwd, hbwd)
    return h
