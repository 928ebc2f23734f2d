"""Kernel-interior static analysis: price a Pallas kernel's on-chip
working set from its TRACED parameters — no Mosaic compile, no chip.

The HLO-level detectors stop at the custom-call boundary: a
``pallas_call`` is one opaque instruction to them, so the bug classes
that live INSIDE the kernel — a BlockSpec working set that cannot fit
v5e VMEM (today it silently falls back, or dies in a chip-only Mosaic
RESOURCE_EXHAUSTED) — were invisible until hardware.  Everything the
estimator needs is already in the traced jaxpr: the ``pallas_call``
equation's ``grid_mapping`` carries every operand's block shape and
memory space, the kernel jaxpr's invars carry the scalar-prefetch SMEM
operands and the scratch shapes.  ``kernel_vmem_bytes()`` prices them
the way the chip allocates them:

- each in/out block is padded to whole (sublane, lane) tiles — (8, 128)
  fp32, (16, 128) bf16, (32, 128) int8 — because Mosaic stores partial
  tiles at full tile footprint;
- blocks of a gridded kernel are DOUBLE-buffered (the pipeline DMAs the
  next block while the current one computes), so they charge 2x;
- VMEM scratch charges once (it persists across grid steps, that is its
  point); SMEM operands/scratch price separately (scalars, page tables
  — a different, much smaller budget).

``detect_vmem_overflow`` flags any program whose kernel invocation
exceeds the configurable v5e budget (``FLAGS_analysis_vmem_budget``,
default the full 16 MiB/core — kernels/flash_attention.py plans its own
blocks against the stricter 3/4 share to leave the compiler headroom).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterator, List, Tuple

from .findings import Finding

__all__ = [
    "KernelCost",
    "V5E_SMEM_BYTES",
    "V5E_VMEM_BYTES",
    "default_smem_budget",
    "default_vmem_budget",
    "detect_smem_overflow",
    "detect_vmem_overflow",
    "iter_pallas_calls",
    "iter_subjaxprs",
    "kernel_cost",
    "kernel_smem_bytes",
    "kernel_vmem_bytes",
    "tile_padded_bytes",
]

# one v5e core's vector memory — the hard envelope every kernel
# invocation's blocks + scratch must fit inside (with the compiler's
# own spills); the authoritative constant the kernel tile planners
# derive their headroomed budgets from
V5E_VMEM_BYTES = 16 * 1024 * 1024

# the modeled scalar-memory envelope per core: where scalar-prefetch
# operands live — grid indices, the paged-attention page tables, the
# per-page int8 scales.  Orders of magnitude smaller than VMEM, which
# is exactly why long contexts hit it FIRST: a flat [B, ~1k] page
# table plus two pool-sized [P] fp32 scale rows is already past this
# at 128k, while the two-level view (L1 directory + walked L2 blocks,
# kernels/paged_attention.TwoLevelTables) stays bounded by live blocks
V5E_SMEM_BYTES = 128 * 1024

_LANE = 128


def default_vmem_budget() -> int:
    """The detector's budget: FLAGS_analysis_vmem_budget (default the
    full v5e VMEM)."""
    from .. import flags

    return int(flags.flag("analysis_vmem_budget"))


def default_smem_budget() -> int:
    """The smem-overflow detector's budget: FLAGS_analysis_smem_budget
    (default the modeled V5E_SMEM_BYTES envelope)."""
    from .. import flags

    return int(flags.flag("analysis_smem_budget"))


def tile_padded_bytes(shape, dtype) -> int:
    """Bytes one buffer occupies in VMEM: the last two dims padded to a
    whole (sublane, lane) tile — sublane 32/itemsize (8 fp32, 16 bf16,
    32 int8), lane 128 — leading dims multiplying.  Rank-0/1 buffers
    price as one (1, n) plane; squeezed/None block dims count as 1."""
    import numpy as np

    dt = np.dtype(dtype)
    sub = max(1, 32 // max(dt.itemsize, 1))
    dims = [int(d) if isinstance(d, int) else 1 for d in (shape or (1,))]
    if len(dims) < 2:
        dims = [1] + dims
    lane = -(-dims[-1] // _LANE) * _LANE
    sublane = -(-dims[-2] // sub) * sub
    n = lane * sublane * dt.itemsize
    for d in dims[:-2]:
        n *= d
    return n


@dataclass
class KernelCost:
    """The statically-priced on-chip working set of ONE pallas_call.

    buffers: (role, shape, dtype, charged_bytes) per operand — role is
    'in'/'out' (block, charged 2x when double-buffered), 'scratch'
    (VMEM, charged once) or 'smem' (scalar-prefetch operand / SMEM
    scratch, outside the VMEM sum)."""

    name: str
    grid: Tuple[int, ...]
    vmem_bytes: int
    smem_bytes: int
    double_buffered: bool
    buffers: List[Tuple[str, Tuple[int, ...], str, int]] = field(
        default_factory=list)


def iter_subjaxprs(jaxpr) -> Iterator[Tuple[object, int]]:
    """(jaxpr, depth) over an open jaxpr and everything nested in eqn
    params (pjit bodies, cond branches, scan/while bodies, remat...)."""
    stack = [(jaxpr, 0)]
    while stack:
        j, d = stack.pop()
        yield j, d
        for eqn in j.eqns:
            for v in eqn.params.values():
                vals = v if isinstance(v, (list, tuple)) else [v]
                for item in vals:
                    inner = getattr(item, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        stack.append((inner, d + 1))
                    elif hasattr(item, "eqns"):
                        stack.append((item, d + 1))


def iter_pallas_calls(jaxpr) -> Iterator[object]:
    """Every pallas_call equation anywhere in the (closed or open)
    jaxpr, nested bodies included."""
    open_jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    if open_jaxpr is None:
        return
    for sub, _ in iter_subjaxprs(open_jaxpr):
        for eqn in sub.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn


def _is_smem(aval) -> bool:
    return "smem" in str(getattr(aval, "memory_space", "") or "").lower()


def _is_semaphore(aval) -> bool:
    space = str(getattr(aval, "memory_space", "") or "").lower()
    return "sem" in space and "smem" not in space


def kernel_cost(eqn) -> KernelCost:
    """Price one pallas_call equation's working set from its
    grid_mapping (block shapes + memory spaces) and its kernel jaxpr's
    invars (scalar-prefetch SMEM operands, scratch shapes)."""
    gm = eqn.params["grid_mapping"]
    kernel_jaxpr = eqn.params["jaxpr"]
    # the name Mosaic gives the kernel: pallas_call's `name=` when one was
    # passed, else the kernel function's own
    name = eqn.params["name"] or kernel_jaxpr.debug_info.func_name
    grid = tuple(int(g) for g in gm.grid if isinstance(g, int))
    grid_size = 1
    for g in grid:
        grid_size *= g
    double = grid_size > 1
    mult = 2 if double else 1
    vmem = smem = 0
    buffers: List[Tuple[str, Tuple[int, ...], str, int]] = []
    n_in = int(getattr(gm, "num_inputs", len(gm.block_mappings)))
    for i, bm in enumerate(gm.block_mappings):
        aval = bm.transformed_block_aval
        role = "in" if i < n_in else "out"
        shape = tuple(getattr(aval, "shape", bm.block_shape))
        dtype = str(getattr(aval, "dtype", "float32"))
        if _is_smem(aval):
            b = _flat_bytes(shape, dtype)
            smem += b
            buffers.append(("smem", shape, dtype, b))
            continue
        b = mult * tile_padded_bytes(shape, dtype)
        vmem += b
        buffers.append((role, shape, dtype, b))
    invars = list(kernel_jaxpr.invars)
    n_idx = int(getattr(gm, "num_index_operands", 0))
    n_scratch = int(getattr(gm, "num_scratch_operands", 0))
    for v in invars[:n_idx]:
        aval = v.aval
        b = _flat_bytes(getattr(aval, "shape", ()), str(aval.dtype))
        smem += b
        buffers.append(("smem", tuple(aval.shape), str(aval.dtype), b))
    for v in invars[len(invars) - n_scratch:] if n_scratch else []:
        aval = v.aval
        shape = tuple(getattr(aval, "shape", ()))
        dtype = str(getattr(aval, "dtype", "float32"))
        if _is_semaphore(aval):
            continue
        if _is_smem(aval):
            b = _flat_bytes(shape, dtype)
            smem += b
            buffers.append(("smem", shape, dtype, b))
        else:
            b = tile_padded_bytes(shape, dtype)
            vmem += b
            buffers.append(("scratch", shape, dtype, b))
    return KernelCost(name=name, grid=grid, vmem_bytes=vmem,
                      smem_bytes=smem, double_buffered=double,
                      buffers=buffers)


def _flat_bytes(shape, dtype) -> int:
    import numpy as np

    n = np.dtype(dtype).itemsize
    for d in shape or ():
        if isinstance(d, int):
            n *= d
    return n


def kernel_vmem_bytes(eqn) -> int:
    """The VMEM working set of one pallas_call equation: double-buffered
    padded in/out blocks + VMEM scratch (SMEM operands excluded — see
    kernel_cost for the breakdown)."""
    return kernel_cost(eqn).vmem_bytes


def kernel_smem_bytes(eqn) -> int:
    """The SMEM working set of one pallas_call equation: every
    scalar-prefetch operand + SMEM-space blocks/scratch, flat bytes
    (scalars are not tiled)."""
    return kernel_cost(eqn).smem_bytes


def detect_vmem_overflow(art) -> List[Finding]:
    """Flag every pallas_call whose statically-priced VMEM working set
    exceeds the v5e budget.  Today such a kernel either falls back off
    the fast path or dies with a chip-only Mosaic RESOURCE_EXHAUSTED —
    the linter sees it from the traced jaxpr before any compile."""
    budget = default_vmem_budget()
    findings: List[Finding] = []
    for eqn in iter_pallas_calls(art.jaxpr):
        cost = kernel_cost(eqn)
        if cost.vmem_bytes <= budget:
            continue
        top = sorted(cost.buffers, key=lambda b: -b[3])[:2]
        worst = ", ".join(
            f"{role} {dtype}{list(shape)}={b} B" for role, shape, dtype, b
            in top)
        findings.append(Finding(
            detector="vmem-overflow", severity="error",
            program=art.name, fingerprint=art.fingerprint,
            where=f"pallas_call:{cost.name}",
            vmem_bytes=cost.vmem_bytes, budget=budget,
            message=(f"kernel {cost.name} needs {cost.vmem_bytes} bytes "
                     f"of VMEM (budget {budget}): grid {cost.grid} "
                     f"{'double-buffers' if cost.double_buffered else 'holds'}"
                     f" its blocks — biggest: {worst}; this shape "
                     "compiles nowhere on a v5e core — shrink the "
                     "BlockSpecs or tile the grid finer"),
        ))
    return findings


def detect_smem_overflow(art) -> List[Finding]:
    """Flag every pallas_call whose scalar-prefetch operands + SMEM
    scratch exceed the scalar-memory budget — the LONG-CONTEXT failure
    class (ISSUE 20): a flat [B, max_pages] page table plus two
    pool-sized [P] int8 scale rows grows with total pages and blows
    SMEM near ~1k pages/seq, where the two-level table view's L1
    directory + walked L2 blocks (with block-gathered scales) stays
    bounded by live blocks.  Like vmem-overflow, the linter prices it
    from the traced jaxpr — no Mosaic compile, no chip."""
    budget = default_smem_budget()
    findings: List[Finding] = []
    for eqn in iter_pallas_calls(art.jaxpr):
        cost = kernel_cost(eqn)
        if cost.smem_bytes <= budget:
            continue
        smem_bufs = [b for b in cost.buffers if b[0] == "smem"]
        top = sorted(smem_bufs, key=lambda b: -b[3])[:3]
        worst = ", ".join(
            f"{dtype}{list(shape)}={b} B" for _, shape, dtype, b in top)
        findings.append(Finding(
            detector="smem-overflow", severity="error",
            program=art.name, fingerprint=art.fingerprint,
            where=f"pallas_call:{cost.name}",
            vmem_bytes=cost.smem_bytes, budget=budget,
            message=(f"kernel {cost.name} prefetches {cost.smem_bytes} "
                     f"bytes of scalars into SMEM (budget {budget}) — "
                     f"biggest: {worst}; scalar operands growing with "
                     "total pool pages (flat page tables, [P] scale "
                     "rows) are the long-context killer — use the "
                     "two-level table view so SMEM rides the walked "
                     "blocks"),
        ))
    return findings
