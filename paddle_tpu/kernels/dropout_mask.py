"""A dropout site's keep mask, drawn once and stored.

`jax.random.bernoulli(key, 1 - p, shape)` is to XLA an elementwise function
of an iota and the key's two words, so XLA never stores it: it clones the
whole generator, threefry round by round, into the fusion of every
consumer: the forward's select, the input gradient and each matmul that
reads the dropped value or its gradient (PERF.md, PR 55: 272 of
`transformer-train`'s fusions carried a threefry for 62 sites, and the MXU
waited for the vector units).  `draw` gives the mask as a value XLA has to
store, one byte an element, which every reader reads:

- `pallas` (the program is for a TPU and the shape tiles, `tiles`): a
  kernel seeds the core's generator anew at every grid step
  (`pltpu.prng_seed`, which takes two words on this chip: a step's two are
  threefry's own bits of the site's key folded with the shard's index,
  `_seeds`, two words for half a million elements) and writes the bytes
  of a tile of rows from `pltpu.prng_random_bits`: a custom call, which
  XLA cannot clone into a consumer.  On a mesh of several
  devices the call sits under a `shard_map` over the data-parallel axis: a
  chip draws its own rows and no more, from a seed no other chip has.
- `xla` (anywhere else: the CPU, a shape that does not tile): threefry's
  bits (`jax.random.bits`) against the same threshold, once, behind
  `jax.lax.optimization_barrier`, which XLA cannot fuse through.  The bits
  are partitionable (`jax_threefry_partitionable`): under GSPMD a chip
  draws the counters of its own shard.

Both compare 32 uniform bits with `threshold(p)`, p at 2**-32 of resolution
(`jax.random.bernoulli`'s float32 uniform has 2**-23).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import engine
from .engine import LANES

__all__ = ["Drawn", "threshold", "tiles", "draw"]

# an int8 tile is 32 sublanes of 128 lanes
_SUBLANES = 32
# elements a grid step draws: 2 MB of bits beside 0.5 MB of bytes, twice
# (the output's two buffers); tools/dropout_probe.py --sweep
_BLOCK_ELEMENTS = 512 * 1024
_WIDTHS = (2048, 1024, 512, 256, 128)


class Drawn(NamedTuple):
    """What `draw` says of a site on the span `dropout.lower`."""
    engine: str        # pallas | xla
    generator: str     # the name of what made the bits
    block_rows: int    # rows of a grid step's tile (0 under xla)


def threshold(p: float) -> int:
    """Of the 2**32 values a draw takes, those below are dropped."""
    return int(np.clip(round(float(p) * 2.0 ** 32), 0, 2 ** 32))


def tiles(shape) -> Optional[Tuple[int, int, int]]:
    """(rows, columns, rows a grid step) of the [rows, columns] view the
    kernel writes a mask of `shape` in, or None where none tiles: the
    columns are the last axis where that is whole 128-lane vectors (the
    view is then the array's own layout), else the widest of `_WIDTHS`
    that cuts the elements into whole tiles of 32 rows (the reshape back
    is a copy of the bytes, XLA's to place)."""
    n = int(np.prod(shape)) if len(shape) else 0
    last = int(shape[-1]) if len(shape) else 0
    own = last % LANES == 0 and 0 < last <= _BLOCK_ELEMENTS // _SUBLANES
    for cols in ((last,) if own else ()) + _WIDTHS:
        if n == 0 or n % (cols * _SUBLANES):
            continue
        rows = n // cols
        step = max(_BLOCK_ELEMENTS // cols // _SUBLANES * _SUBLANES, _SUBLANES)
        while rows % step:
            step -= _SUBLANES
        return rows, cols, step
    return None


def _kernel(seeds_ref, mask_ref, *, below: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    pltpu.prng_seed(seeds_ref[2 * i], seeds_ref[2 * i + 1])
    bits = pltpu.prng_random_bits(mask_ref.shape)
    # the bits as int32 are uniform over [-2**31, 2**31): dropped below
    # `below` - 2**31, which is `below` of the 2**32 values
    mask_ref[...] = (bits >= below - 2 ** 31).astype(mask_ref.dtype)


@functools.lru_cache(maxsize=64)
def _call(rows: int, cols: int, step: int, below: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return jax.jit(pl.pallas_call(
        functools.partial(_kernel, below=below),
        grid=(rows // step,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((step, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="dropout_mask"))


def _seeds(key, steps: int, shard=0):
    """[2 steps] int32, a grid step's two seed words after the other's:
    threefry's bits of `key` folded with the shard's index, so that no two
    steps, shards or sites seed the generator alike."""
    bits = jax.random.bits(jax.random.fold_in(key, shard), (2 * steps,),
                           jnp.uint32)
    return jax.lax.bitcast_convert_type(bits, jnp.int32)


def _pallas(key, shape, below: int, tiled, shard=0):
    rows, cols, step = tiled
    mask = _call(rows, cols, step, below)(_seeds(key, rows // step, shard))
    return mask.astype(jnp.uint8).reshape(shape)


def _kernels(key, shape, below: int, mesh):
    """(mask, Drawn) by the kernel, or None where the site does not tile.
    On a mesh of several devices under a shard_map over its data-parallel
    axis, a shard of the leading axis a device (XLA cannot partition a
    Mosaic kernel: kernels/engine.py's mesh rule), where that axis
    divides."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AXIS_DP

    several = engine.several_devices(mesh)
    local = shape
    if several:
        if not (shape and mesh.has_axis(AXIS_DP)
                and shape[0] % mesh.axis_size(AXIS_DP) == 0):
            return None
        local = (shape[0] // mesh.axis_size(AXIS_DP),) + shape[1:]
    tiled = tiles(local)
    if tiled is None:
        return None
    if several:
        # check_vma off: pallas_call has no replication rule
        mask = jax.shard_map(
            lambda key: _pallas(key, local, below, tiled,
                                shard=jax.lax.axis_index(AXIS_DP)),
            mesh=mesh.mesh, in_specs=(P(),),
            out_specs=P(AXIS_DP, *([None] * (len(shape) - 1))),
            check_vma=False)(key)
    else:
        mask = _pallas(key, shape, below, tiled)
    return mask, Drawn("pallas", "tpu_prng", tiled[2])


def draw(key, shape, p: float, *, mesh=None, force: str = "auto"):
    """(mask, Drawn): uint8 [shape], 1 where the element is kept, each
    independently with probability 1 - p, from `key`; a value XLA stores.
    `force`: kernels/engine.py's door (the core's generator has no
    interpreter, jax's reads zeros: "interpret" draws as "jax" does)."""
    shape = tuple(int(d) for d in shape)
    below = threshold(p)
    if below in (0, 2 ** 32):   # nothing to draw
        return jnp.full(shape, below == 0, jnp.uint8), Drawn("xla", "none", 0)
    if engine.use_pallas(force):
        drawn = _kernels(key, shape, below, mesh)
        if drawn is not None:
            return drawn
    mask = (jax.random.bits(key, shape, jnp.uint32)
            >= np.uint32(below)).astype(jnp.uint8)
    return jax.lax.optimization_barrier(mask), Drawn("xla", "threefry", 0)
