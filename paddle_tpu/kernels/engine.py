"""Which engine a kernel-backed op runs, decided in one place.

Such an op (ops/ -> kernels/ -> this module; nothing in kernels/ imports
ops/) is ONE algorithm with two engines: its Pallas kernels, and a
jax.numpy form XLA compiles.  Which a site runs is read from what the
program is for, the site's shape and the mesh, never from a flag or an
argument a model sets.  Here is every part of that decision but a kernel's
own working-set count: the platform test and the door (`on_tpu`,
`use_pallas`), the budget, the limit a call declares and the lane width,
the row-tiled kernels' tile search (`widest`; flash's `_fewest_steps` and
`gated_delta.kernel_tiles` search score blocks and rows of one head and
take the budget alone), the mesh rule (`wants_kernels`, `shard_over_mesh`),
a site under its `.lower` span (`site`), and what the row-tiled kernel
bodies share.

The mesh rule as it stands (ROADMAP D25) is three behaviours, because XLA
cannot partition a Mosaic kernel: flash attention (`shard_over_mesh`) and
the dropout mask (kernels/dropout_mask.py, its own specs) wrap their
kernels in a `shard_map`; compressed_conv_qkv, kda_conv_decay,
kda_gated_norm, the mhc_* ops, eva_attention and selective_scan run their
jax.numpy form on a mesh of several devices (`wants_kernels(force,
mesh)`, which `tiles_or_none` and `site` ask); sparse_attention and
gated_delta_attention read no mesh.  No four-chip cell measures the last
two groups: the PR that makes the three one edits `wants_kernels` and
needs that cell first.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp

from ..analysis.pallas import V5E_VMEM_BYTES
from ..observability import span

# The door, `force`: the tests' and the probes' argument, never a model's.
# "auto": the kernels where the program is for a TPU; "pallas": the kernels
# wherever (a chip-less compile); "interpret": the kernels in the Pallas
# interpreter (the CPU tests); "jax": never.
DOOR = ("auto", "pallas", "interpret", "jax")

# a vector register's lanes: the tile of the last dimension
LANES = 128

# What a plan's working set (the declared blocks, twice where the pipeline
# double-buffers them, and the kernel's live fp32 temporaries, as each
# kernel file counts its own) may take: 3/4 of the v5e's 16 MiB of scoped
# VMEM, which leaves the compiler its headroom.  Settled on the chip with
# the flash forward (tools/flash_fwd_probe.py --sweep, PERF.md PR 28): at
# 32 x 2048 x 128 causal the time falls with the grid steps all the way to
# 1024 x 1024 blocks (11.5 MB by the plan's count); the count is cautious,
# Mosaic still compiles 20.5 MB of it and refuses 22.  The row-tiled
# kernels' sweeps (PERF.md PRs 45, 48, 49, 51) all chose inside it.
PLAN_VMEM_BUDGET = (3 * V5E_VMEM_BYTES) // 4

F32 = jnp.float32


def on_tpu() -> bool:
    """True when the program being traced is for a TPU: the attached
    device is one, or an Executor opened the TPU trace scope for a
    chip-less compile (cost_analysis(platform="tpu"), the lowering gate,
    analysis capture) — so that what those compile is the chip's program,
    kernels included."""
    from .. import flags

    return flags.tpu_trace_active() or jax.devices()[0].platform == "tpu"


def use_pallas(force: str) -> bool:
    """Whether `force` asks for the compiled kernels."""
    if force not in DOOR:
        raise ValueError(f"force={force!r} is none of {' | '.join(DOOR)}")
    return force == "pallas" or (force == "auto" and on_tpu())


def several_devices(mesh) -> bool:
    return mesh is not None and mesh.num_devices > 1


def wants_kernels(force: str, mesh=None) -> bool:
    """Whether a site may run its kernels, compiled or interpreted: `force`
    and the platform allow them, and the site is not on a `mesh` of several
    devices (the mesh rule of an op that falls back; one that reads no mesh
    passes none)."""
    return ((force == "interpret" or use_pallas(force))
            and not several_devices(mesh))


def tiles_or_none(force: str, mesh, plan):
    """`plan()`'s tiles (the kernel file's own planner: None where the
    shape does not tile) where a site may run its kernels, None where it
    runs its jax.numpy form."""
    return plan() if wants_kernels(force, mesh) else None


def site(name: str, fields, mesh, plan, kernels, fallback,
         force: str = "auto", **fixed):
    """The outputs of one site of an op, run under its `.lower` span (at
    lowering): `kernels(tiles, interpret)` where `tiles_or_none` gives
    `plan()`'s tiles, `fallback()`, the op's jax.numpy form, where it gives
    None.  The span says the `fixed` fields, `engine` (pallas | xla) and
    the tiles' `fields`, 0 under xla.  `force` is a test's."""
    with span(name, **fixed) as sp:
        tiles = tiles_or_none(force, mesh, plan)
        out = (fallback() if tiles is None
               else kernels(tiles, force == "interpret"))
        sp.set(engine="xla" if tiles is None else "pallas",
               **{f: 0 if tiles is None else getattr(tiles, f)
                  for f in fields})
    return out


def shard_over_mesh(attend, mesh, n_head: int, has_lengths: bool,
                    heads_last: bool = False):
    """`attend(q, k, v[, k_lengths])`, flash attention's call, as a program
    for a TPU on a `mesh` of several devices needs it: in a shard_map over
    the mesh, batch over dp, heads over tp where tp divides them, sequence
    whole (heads-first operands are [B, H, S, D]; `heads_last` ones [B, S,
    H * D], whose lanes a cut over tp splits into whole heads); `attend`
    itself anywhere else.
    XLA cannot partition a Mosaic kernel by itself ("Mosaic kernels cannot
    be automatically partitioned") — without this the SPMD step of a
    flash-attention model does not compile for more than one chip.  Each
    device runs the kernel on its own [B/dp, H/tp, S, D] block; attention
    never mixes batch rows or heads, so no collective is needed."""
    if not (several_devices(mesh) and use_pallas("auto")):
        return attend
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AXIS_DP, AXIS_TP

    dp = AXIS_DP if mesh.has_axis(AXIS_DP) else None
    # `n_head`: the key/value heads where they are fewer than the query's
    tp = AXIS_TP if (mesh.has_axis(AXIS_TP)
                     and n_head % mesh.axis_size(AXIS_TP) == 0) else None
    qkv = P(dp, None, tp) if heads_last else P(dp, tp, None, None)
    in_specs = (qkv, qkv, qkv) + ((P(dp),) if has_lengths else ())
    # check_vma off: pallas_call has no replication rule
    return jax.shard_map(attend, mesh=mesh.mesh, in_specs=in_specs,
                         out_specs=qkv, check_vma=False)


def compiler_params(semantics, need: int):
    """A call's Mosaic parameters: the grid axes' `semantics` and a scoped
    VMEM limit of twice the plan's count `need` (the count is an estimate,
    the limit what the compiler may really take), the chip's default at
    least."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=int(max(V5E_VMEM_BYTES, 2 * need)))


def widest(seq, width, unit, need, rows, channels):
    """(rows, channels) of the first tile of `channels` x `rows` (a kernel
    file's candidates, widest first; the block of channels outermost: it
    counts for more than the rows, kernels/kda_mix.py's sweep) that divides
    the [seq, width] shape, is whole `unit`s of channels and whole sublane
    tiles of rows, and whose working set `need(rows, channels)` fits the
    budget; None where none does."""
    for c in channels:
        for r in rows:
            if (seq % r == 0 and width % c == 0 and c % unit == 0
                    and r % 8 == 0 and need(r, c) <= PLAN_VMEM_BUDGET):
                return r, c
    return None


def one_dtype(*tensors) -> bool:
    """Whether the streams share one dtype, and one the row-tiled kernels
    take (bf16, fp32)."""
    dtypes = {jnp.dtype(t.dtype) for t in tensors}
    return len(dtypes) == 1 and dtypes <= {
        jnp.dtype(jnp.bfloat16), jnp.dtype(F32)}


# ---------------------------------------------------------------------------
# what the row-tiled kernel bodies share, on fp32 [rows, lanes] values
# ---------------------------------------------------------------------------
def halo_rows(dtype) -> int:
    """A block of the narrowest aligned height: 8 rows of 32 bits, 16 of
    16."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def add_up(terms):
    """The terms' sum with no 0 to start from (the builtin's)."""
    return functools.reduce(operator.add, terms)


def roll(x, shift, axis=0):
    """y[r] = x[r - shift] along `axis` (jnp.roll's)."""
    from jax.experimental.pallas import tpu as pltpu

    shift %= x.shape[axis]
    return x if shift == 0 else pltpu.roll(x, shift, axis)


def back(x, steps):
    """y[e] = x[e - steps]; the first `steps` rows wrap and are never
    read."""
    return roll(x, steps, 0)


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def columns(width, unit):
    return [slice(c, c + unit) for c in range(0, width, unit)]
