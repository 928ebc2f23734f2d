"""Known-bad regression corpus: each builder re-creates one hazard class
this repo actually shipped (or nearly shipped) and returns the captured
ProgramArtifacts.  tests/test_analysis.py asserts the linter flags each
with the right detector id, and ``lint_programs.py --inject <name>``
splices them into a zoo run so the CI gate's nonzero exit is provable
end-to-end.

These are small on purpose — every builder AOT-compiles chip-less in
seconds, so the corpus runs in tier-1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .capture import capture_fn, ProgramArtifacts

__all__ = ["CORPUS", "build_corpus_program", "corpus_extra_bytes"]


def _broadcast_lse_operand() -> ProgramArtifacts:
    """The pre-PR-1 flash-attention residual bug: an lse-shaped [N]
    vector broadcast-materialized to [N, 128] as a pallas custom-call
    operand.  'XLA fuses it' was false — custom-call operands materialize
    at full size (67 MB/tensor at longcontext)."""
    import jax.experimental.pallas as pl

    def _add_kernel(x_ref, b_ref, o_ref):
        o_ref[...] = x_ref[...] + b_ref[...]

    def fn(x, lse):
        # the bug shape: per-row scalar state padded to the 128-lane width
        b = jnp.broadcast_to(lse[:, None], (x.shape[0], 128))
        return pl.pallas_call(
            _add_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x, b)

    return capture_fn(
        fn,
        jax.ShapeDtypeStruct((512, 128), jnp.float32),
        jax.ShapeDtypeStruct((512,), jnp.float32),
        name="corpus_broadcast_lse")


def _conv_relayout_sandwich() -> ProgramArtifacts:
    """The ROADMAP 'layout tax': a conv feeding a pallas custom call on
    the [N, H, H, C] activation and another conv consuming it.  XLA
    prefers {3,0,2,1} for conv activations while the custom call pins
    row-major, so the compiled module brackets the call with relayout
    copies."""
    import jax.experimental.pallas as pl

    N, H, C = 2, 56, 64

    def _scale_kernel(x_ref, g_ref, o_ref):
        o_ref[...] = x_ref[...] * g_ref[...]

    def fn(x, w0, g, w2):
        h = jax.lax.conv_general_dilated(
            x, w0, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = pl.pallas_call(
            _scale_kernel, grid=(N,),
            in_specs=[pl.BlockSpec((1, H, H, C), lambda n: (n, 0, 0, 0)),
                      pl.BlockSpec((1, 1, 1, C), lambda n: (0, 0, 0, 0))],
            out_specs=pl.BlockSpec((1, H, H, C), lambda n: (n, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype),
        )(h, g.reshape(1, 1, 1, C))
        return jax.lax.conv_general_dilated(
            h, w2, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    wsd = jax.ShapeDtypeStruct((3, 3, C, C), jnp.float32)
    return capture_fn(
        fn, jax.ShapeDtypeStruct((N, H, H, C), jnp.float32),
        wsd, jax.ShapeDtypeStruct((C,), jnp.float32), wsd,
        name="corpus_relayout_sandwich")


def _missed_donation() -> ProgramArtifacts:
    """A train-step-shaped fn whose state is eligible for aliasing but
    never donated: the executable keeps input AND output buffers
    resident — at real model scale, double the param memory."""
    def fn(state, x):
        return [s + x for s in state], jnp.sum(x)

    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    return capture_fn(
        fn, [a, a, a], a,
        donate_argnums=(), donatable_argnums=(0,),
        name="corpus_missed_donation")


def _weak_type_scalar() -> ProgramArtifacts:
    """A python scalar leaked into the trace: the lr rides as a
    weak-typed f32 scalar, so the same step called with a numpy/jax
    array lr silently lands on a different trace key and recompiles."""
    def fn(x, lr):
        return x - lr * x

    return capture_fn(
        fn, jax.ShapeDtypeStruct((128, 128), jnp.float32), 0.1,
        name="corpus_weak_type")


def _bf16_promotion_escape() -> ProgramArtifacts:
    """A silent bf16->fp32 promotion whose full-width result escapes to
    the program output: keep-tier bf16 is defeated — the activation hits
    HBM at 2x the bytes."""
    def fn(x):
        # the hazard: a strongly-typed fp32 constant promotes the whole
        # activation, and nothing narrows it back before the HBM write
        return x.astype(jnp.float32) * 2.0 + 1.0

    return capture_fn(
        fn, jax.ShapeDtypeStruct((1024, 512), jnp.bfloat16),
        name="corpus_bf16_escape")


def _all_gather_replicated() -> ProgramArtifacts:
    """The SPMD placement hazard (ISSUE 10): a shard_map body
    all-gathers a >=1MB sharded activation onto EVERY chip and then
    consumes it with a plain reduction — the gather moves and
    materializes n_shards x the bytes a psum/psum_scatter placement
    would have (each chip only needed its shard's contribution)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..core.aot_tpu import tpu_topology

    topo = tpu_topology("v5e:2x2", chips_per_host=(2, 2, 1))
    mesh = Mesh(np.array(topo.devices), ("tp",))

    def body(xl):
        g = jax.lax.all_gather(xl, "tp", axis=0, tiled=True)  # full [S, D]
        return jnp.sum(g * g, axis=0)

    def fn(x):
        # check_vma off: the checker cannot infer that a gathered-then-
        # reduced value is replicated — which is part of the smell
        return jax.shard_map(body, mesh=mesh, in_specs=P("tp", None),
                             out_specs=P(), check_vma=False)(x)

    return capture_fn(
        fn, jax.ShapeDtypeStruct((4096, 128), jnp.float32),
        name="corpus_all_gather", topology=topo,
        in_shardings=(NamedSharding(mesh, P("tp", None)),),
        out_shardings=NamedSharding(mesh, P()))


def _host_callback() -> ProgramArtifacts:
    """A host callback inside the step body: every execution round-trips
    the host, draining the device pipeline."""
    import numpy as np

    def fn(x):
        s = jax.pure_callback(
            lambda v: np.asarray(v).sum(),
            jax.ShapeDtypeStruct((), jnp.float32), x)
        return x * s

    return capture_fn(
        fn, jax.ShapeDtypeStruct((64, 128), jnp.float32),
        name="corpus_host_callback")


def _vmem_overflow() -> ProgramArtifacts:
    """The kernel-interior hazard class (ISSUE 14): a BlockSpec working
    set no v5e core can hold — here a whole-array 64 MB block, double-
    buffered to 256 MB against a 16 MB VMEM.  Today this class either
    silently falls back off the fast path or dies in a chip-only Mosaic
    RESOURCE_EXHAUSTED; the vmem-overflow detector prices it from the
    traced jaxpr before any compile (the AOT pipeline may well reject
    the program too — the gate fails either way, which is the point)."""
    import jax.experimental.pallas as pl

    def _scale_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    N = 4096  # one f32 [N, N] block = 64 MB

    def fn(x):
        return pl.pallas_call(
            _scale_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((1, N, N), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, N, N), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((2, N, N), jnp.float32))(x)

    return capture_fn(
        fn, jax.ShapeDtypeStruct((2, N, N), jnp.float32),
        name="corpus_vmem_overflow")


def _scan_widened_carry() -> ProgramArtifacts:
    """The scan-carry widening class the ROADMAP names for new hot
    paths: bf16 rows accumulated into a carry whose init silently
    traced fp32 (a forgotten dtype= in zeros), so jax forces the whole
    loop wide — every iteration rewrites the loop-resident buffer at 2x
    the bytes and the stacked fp32 history escapes to the program
    output unnarrowed."""
    def fn(x):  # x: [T, N] bf16 activations
        def body(c, row):
            c = c + row  # bf16 row joins the f32 carry -> widens
            return c, c

        c0 = jnp.zeros((x.shape[1],))  # the bug: traced fp32, not bf16
        _, history = jax.lax.scan(body, c0, x)
        return history  # [T, N] fp32 — 2x the bf16 bytes, every step

    return capture_fn(
        fn, jax.ShapeDtypeStruct((512, 1024), jnp.bfloat16),
        name="corpus_scan_widening")


def _spec_verify_gather() -> ProgramArtifacts:
    """The speculative-verify regression the spec_verify zoo entry
    gates on: a multi-token verify step that re-materializes the full
    contiguous [B, H, S, D] KV gather (reference tier — gather + group
    broadcast + dense attention) instead of streaming pages through
    the q_lengths kernel.  Structurally healthy, so no detector flags
    it — it must trip the BYTES tolerance: the artifact shares the zoo
    entry's capture (and name) via ``zoo.capture_spec_verify``, so
    ``lint_programs --inject spec_verify_gather --gate`` prices it
    against the banked page-stream baseline and exits 3.  Its traffic
    is fully XLA-visible (that IS the hazard), so it carries no
    analytic correction."""
    from .zoo import capture_spec_verify

    return capture_spec_verify(gather=True)


def _spec_verify_spmd_gather() -> ProgramArtifacts:
    """The mesh twin of spec_verify_gather (ISSUE 16): the shard-mapped
    Sq=1+d verify step whose per-shard attention re-materializes the
    contiguous [B, H_local, S, D] gather (reference tier — gather +
    group broadcast + dense attention) instead of walking pages.  On a
    GQA pool the gather also re-expands K/V over the query group, so
    the per-chip traffic prices far above the banked stream.  The
    artifact shares the zoo entry's capture (and name) via
    ``zoo.capture_spec_verify_spmd``, so ``lint_programs --inject
    spec_verify_spmd_gather --gate`` prices it against the banked
    per-chip page-stream baseline and exits 3 on the BYTES tolerance
    (at this scale the group-broadcast re-expansion is also big enough
    for the broadcast-operand detector to flag — belt and braces, the
    gate fails either way).  Its traffic is fully XLA-visible (that IS
    the hazard), so it carries no analytic correction."""
    from .zoo import capture_spec_verify_spmd

    return capture_spec_verify_spmd(gather=True)


def _longctx_flat_pool() -> ProgramArtifacts:
    """The long-context SMEM regression the longctx_decode zoo entry
    gates on (ISSUE 20): the SAME windowed GQA int8 decode geometry
    (~1k pages/seq, 16k-page pool) walked through the FLAT page-table
    contract — the scalar-prefetch operands ([B, max_pages] table +
    starts rows plus two POOL-sized [P] fp32 scale rows) total ~160 KB
    against the ~128 KB v5e SMEM envelope.  The smem-overflow detector
    prices it straight from the traced jaxpr (the AOT pipeline may
    reject the kernel too — the gate fails either way), so
    ``lint_programs --inject longctx_flat_pool --gate`` exits 3 against
    the banked two-level baseline.  The artifact shares the zoo entry's
    capture (and name) via ``zoo.capture_longctx_decode``, so retuning
    the zoo geometry retunes this check with it."""
    from .zoo import capture_longctx_decode

    return capture_longctx_decode(two_level=False)


def _longctx_flat_pool_extra_bytes() -> float:
    """The flat arm streams the same analytic int8 page walk as the
    banked two-level entry — the hazard is SMEM, not HBM, and charging
    the honest stream keeps the bytes verdict quiet so the gate failure
    is unambiguously the detector's."""
    from .zoo import longctx_decode_stream_bytes

    return longctx_decode_stream_bytes()


def _gqa_full_pool() -> ProgramArtifacts:
    """The GQA regression the gqa_decode zoo entry gates on: a model
    configured for grouped KV heads served from a FULL H_q pool (the
    grouping dropped somewhere between config and pool construction, so
    every page stores and streams H_q/H_kv x the bytes).  No detector
    flags it — the program is structurally healthy — which is exactly
    why it must trip the BYTES tolerance instead: the artifact shares
    the zoo entry's capture (and name) via ``zoo.capture_gqa_decode``,
    just with H_q pool heads, so ``lint_programs --inject gqa_full_pool
    --gate`` prices it against the banked grouped baseline and exits 3
    rather than silently passing — and retuning the zoo geometry
    retunes this check with it."""
    from .zoo import GQA_DECODE_GEOM, capture_gqa_decode

    return capture_gqa_decode(GQA_DECODE_GEOM["heads"])  # full H_q!


def _gqa_full_pool_extra_bytes() -> float:
    """The full-H_q analytic page stream the known-bad pool pays —
    without it the corpus program's XLA-visible bytes alone would gate
    BELOW the banked grouped baseline and pass."""
    from .zoo import GQA_DECODE_GEOM, gqa_decode_stream_bytes

    return gqa_decode_stream_bytes(GQA_DECODE_GEOM["heads"])


# name -> (builder, detector id the linter must flag it with; None for
# programs that trip the zoo BYTES gate instead of a detector)
CORPUS = {
    "broadcast_lse": (_broadcast_lse_operand, "broadcast-operand"),
    "relayout_sandwich": (_conv_relayout_sandwich, "relayout-copy-pair"),
    "missed_donation": (_missed_donation, "missed-donation"),
    "weak_type": (_weak_type_scalar, "recompile-hazard"),
    "bf16_escape": (_bf16_promotion_escape, "dtype-promotion"),
    "host_callback": (_host_callback, "host-sync"),
    "vmem_overflow": (_vmem_overflow, "vmem-overflow"),
    "scan_widening": (_scan_widened_carry, "scan-widening"),
    "all_gather_replicated": (_all_gather_replicated,
                              "collective-placement"),
    "gqa_full_pool": (_gqa_full_pool, None),
    "longctx_flat_pool": (_longctx_flat_pool, "smem-overflow"),
    "spec_verify_gather": (_spec_verify_gather, None),
    "spec_verify_spmd_gather": (_spec_verify_spmd_gather, None),
}

# corpus programs whose hazard prices in the analytic page-stream
# correction (zoo._corpus_builder adds it to the XLA-visible bytes,
# mirroring the real zoo entries' methodology); default 0
_EXTRA_BYTES = {
    "gqa_full_pool": _gqa_full_pool_extra_bytes,
    "longctx_flat_pool": _longctx_flat_pool_extra_bytes,
}


def corpus_extra_bytes(name: str) -> float:
    """Analytic bytes/step correction for one corpus program (0 for
    programs whose hazard is fully XLA-visible)."""
    fn = _EXTRA_BYTES.get(name)
    return float(fn()) if fn else 0.0


@functools.lru_cache(maxsize=None)
def build_corpus_program(name: str) -> ProgramArtifacts:
    """Build (and memoize — corpus programs are immutable) one known-bad
    program by name."""
    builder, _expected = CORPUS[name]
    return builder()
