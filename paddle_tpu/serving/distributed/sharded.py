"""Tensor-parallel decode: the transformer decode step under shard_map.

The single-device decode step (serving/generate.py) tops out at one
chip's HBM bandwidth and one chip's page pool.  This module shards the
SAME model across a mesh axis (``tp``) the classic Megatron way, mapped
onto jax:

- **Column-parallel QKV**: ``wq [d, d]`` / ``wk/wv [d, H_kv*Dh]``
  split on the OUTPUT dim, so shard ``i`` computes query heads
  ``[i*H/n, (i+1)*H/n)`` and KV heads ``[i*H_kv/n, (i+1)*H_kv/n)`` —
  no collective, each shard's Q/K/V are exactly its own heads', and
  under GQA (``cfg.n_kv_head < n_head``) the query-group alignment is
  automatic: H/n local query heads are exactly (H/H_kv) groups over
  H_kv/n local KV heads, so the grouped paged kernel runs per-shard
  unchanged.  Both head counts must divide by the mesh axis.
- **Local paged KV**: :class:`ShardedKVCachePool` shards the pool
  arrays on the KV-HEAD axis (``[L, H_kv/n, P, page_size, D]`` per
  device — the GQA shrink compounds with the mesh split: each device
  holds H_kv/(H*n) of a full-head single-device pool).  Page tables
  and the free list stay host-side and global (one admission decision
  covers all shards); the K/V write and the paged-attention page walk
  are per-shard local — the pallas kernel runs unchanged, its grid was
  already per-(KV-)head.  int8 pages are NOT yet supported here: the
  sharded step writes K/V inside the shard_map body, where the
  host-side amax scale bookkeeping cannot reach (a device-side scale
  table is the follow-up); the constructor rejects ``dtype="int8"``
  loudly rather than storing garbage.
- **Row-parallel joins**: ``wo [d, d]`` splits on the INPUT dim; each
  shard contributes ``attn_local @ wo_local`` and one ``psum`` over ICI
  joins the partials (same for the MLP's ``w1``/``w2`` pair).  ``psum``
  rather than ``psum_scatter``: the joined activation immediately feeds
  the next layer's column-parallel matmuls on EVERY shard, so a
  scattered result would force an all-gather right back — the linter's
  ``collective-placement`` detector exists to catch that shape.
- **Replicated everything else**: embeddings, positions, layernorm
  scales, and the logits matmul (V is small next to the KV stream; the
  returned ``[B, V]`` logits are bit-identical on every shard, which is
  also shard_map's replication check on the output spec).

Speculation (ISSUE 16): ``verify_step_fn`` compiles the same sharded
model for Sq = 1+d ragged query rows (``q_lengths`` is a first-class
operand of the paged kernel), and ``ShardedDecodeProgram.verify_step``
drives ``generate.verify_step``'s exact host protocol — so a
program-driven ``ContinuousBatchingLoop(speculate=d)`` commits up to
d+1 tokens per mesh step instead of degrading to d=0.

Chip-less verification: an N-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``) runs the real
SPMD program; tests/test_distributed_serving.py holds continuous-
batching decode over it token-identical to the single-device oracle.
The AOT v5e tier (core/aot_tpu.py) compiles the same program for a
2x2 slice and banks its per-chip bytes/step (analysis zoo entry
``sharded_decode``).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...kernels.flash_attention import flash_attention
from ...kernels.paged_attention import (
    paged_decode_attention,
    repeat_kv,
    resolve_paged_impl,
)
from ..generate import DecodeConfig, _layernorm
from ..kvcache import KVCachePool

__all__ = [
    "KV_POOL_MAJOR_TO_MINOR",
    "ShardedDecodeProgram",
    "ShardedKVCachePool",
    "decode_step_fn",
    "host_mesh_devices",
    "kv_pool_layout",
    "param_partition_specs",
    "param_shape_dtypes",
    "prefill_step_fn",
    "verify_step_fn",
]

AXIS_TP = "tp"


def host_mesh_devices(n: int):
    """The first `n` local devices for a chip-less tensor-parallel mesh.
    Raises with the XLA_FLAGS recipe when the initialized platform has
    fewer — the flag only works BEFORE the backend initializes, so this
    cannot respawn, it can only tell the caller how to."""
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for the mesh but the initialized platform "
            f"has {len(devs)}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax "
            "initializes (tests: the conftest host_devices fixture)")
    return devs[:n]


# ---------------------------------------------------------------------------
# parameter sharding vocabulary


def param_partition_specs(cfg: DecodeConfig, axis: str = AXIS_TP) -> Dict:
    """PartitionSpec pytree matching init_decode_params' structure:
    QKV column-parallel (output dim -> heads), wo/w2 row-parallel
    (input dim), w1/b1 column-parallel, everything else replicated."""
    layer = {
        "wq": P(None, axis), "wk": P(None, axis), "wv": P(None, axis),
        "wo": P(axis, None),
        "ln1_g": P(), "ln1_b": P(),
        "w1": P(None, axis), "b1": P(axis),
        "w2": P(axis, None), "b2": P(),
        "ln2_g": P(), "ln2_b": P(),
    }
    return {
        "embed": P(),
        "pos": P(),
        "layers": [dict(layer) for _ in range(cfg.n_layer)],
    }


def param_shape_dtypes(cfg: DecodeConfig) -> Dict:
    """ShapeDtypeStruct pytree of init_decode_params(cfg) — the AOT
    capture path's abstract arguments (no host weights materialized)."""
    d, f = cfg.d_model, cfg.d_inner
    d_kv = cfg.num_kv_heads * cfg.head_dim
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    layer = {
        "wq": sds(d, d), "wk": sds(d, d_kv), "wv": sds(d, d_kv),
        "wo": sds(d, d),
        "ln1_g": sds(d), "ln1_b": sds(d),
        "w1": sds(d, f), "b1": sds(f), "w2": sds(f, d), "b2": sds(d),
        "ln2_g": sds(d), "ln2_b": sds(d),
    }
    return {
        "embed": sds(cfg.vocab_size, d),
        "pos": sds(cfg.max_length, d),
        "layers": [dict(layer) for _ in range(cfg.n_layer)],
    }


def _kv_spec(axis: str = AXIS_TP) -> P:
    """Pool arrays [L, H, P, page_size, D]: heads sharded, rest local."""
    return P(None, axis, None, None, None)


# The pool-shard LAYOUT contract (the ROADMAP "layout tax" fix, ISSUE
# 14).  The SPMD step scatter-updates the pool in place (one [H, D] row
# per appended token), so XLA prefers D, then H, innermost — physical
# [L, P, ps, H, D], i.e. major_to_minor (0, 2, 3, 1, 4) on the logical
# [L, H, P, ps, D] arrays — and the paged kernel's pool_layout="xla"
# arm consumes exactly that view.  Requesting it at the program
# boundary (entry params AND outputs — the donated pool aliases, so
# they must agree) erases every relayout copy: the banked
# sharded_decode zoo entry pins relayout-copy-pair at 0 and the
# bytes/step win.  Verified against Layout.AUTO: XLA picks this same
# layout when left free.
KV_POOL_MAJOR_TO_MINOR = (0, 2, 3, 1, 4)


def kv_pool_layout(sharding: NamedSharding):
    """The XLA-preferred pool-shard layout wrapped over `sharding` — the
    in/out sharding entry the kv pool args carry on TPU compiles (the
    AOT zoo capture and the real TPU program use the same one)."""
    from jax.experimental.layout import Format, Layout

    return Format(Layout(major_to_minor=KV_POOL_MAJOR_TO_MINOR), sharding)


# ---------------------------------------------------------------------------
# the SPMD step bodies (pure; every array a shard_map gives them is the
# LOCAL shard — H_local = n_head / n_shards heads per device)


def _local_heads(cfg: DecodeConfig, n_shards: int) -> Tuple[int, int]:
    """(query, KV) heads per shard — BOTH head counts must divide by
    the mesh axis.  Under GQA the local query heads are then exactly
    H/H_kv whole groups over the local KV heads (H/n = (H/H_kv) *
    H_kv/n), so shard-local grouping matches the global mapping."""
    if cfg.n_head % n_shards:
        raise ValueError(
            f"n_head={cfg.n_head} must divide by n_shards={n_shards}")
    if cfg.num_kv_heads % n_shards:
        raise ValueError(
            f"n_kv_head={cfg.num_kv_heads} must divide by n_shards="
            f"{n_shards} — the pool shards over the KV-head axis")
    return cfg.n_head // n_shards, cfg.num_kv_heads // n_shards


def decode_step_fn(cfg: DecodeConfig, n_shards: int, axis: str = AXIS_TP,
                   impl: str = "reference", force: str = "auto"):
    """Build the shard_map body for one continuous-batching decode step.

    fn(params, tokens [B], positions [B], pages [B], slots [B],
       tables [B, maxp], lengths [B], k_pages, v_pages)
      -> (logits [B, V] replicated, new k_pages, new v_pages)

    The K/V append is the write_kv contract on the LOCAL KV-head shard;
    the paged attention walks the (global, replicated) page tables over
    the LOCAL pool arrays — every byte the hot path touches lives on
    the device that computes with it."""
    H_local, Hkv_local = _local_heads(cfg, n_shards)
    d, Dh = cfg.d_model, cfg.head_dim

    def step(params, tokens, positions, pages, slots, tables, lengths,
             k_pages, v_pages):
        B = tokens.shape[0]
        h = jnp.asarray(params["embed"])[tokens] * np.sqrt(d) \
            + jnp.asarray(params["pos"])[positions]
        for li, lp in enumerate(params["layers"]):
            q = (h @ lp["wq"]).reshape(B, H_local, Dh)
            k = (h @ lp["wk"]).reshape(B, Hkv_local, Dh)
            v = (h @ lp["wv"]).reshape(B, Hkv_local, Dh)
            k_pages = k_pages.at[li, :, pages, slots].set(k)
            v_pages = v_pages.at[li, :, pages, slots].set(v)
            attn = paged_decode_attention(
                q[:, :, None, :], k_pages[li], v_pages[li],
                tables, lengths, scale=Dh ** -0.5, impl=impl, force=force,
                # the pool was scatter-updated two lines up, INSIDE this
                # program: consume the layout XLA prefers for that
                # scatter instead of pinning kernel-native row-major —
                # this is what drives the banked sharded_decode
                # relayout-copy-pair count to zero
                pool_layout="xla",
            )  # [B, H_local, 1, Dh]
            attn = attn[:, :, 0, :].reshape(B, H_local * Dh)
            # row-parallel wo: each shard's heads contribute a [B, d]
            # partial; one psum over ICI joins them
            attn_out = jax.lax.psum(attn @ lp["wo"], axis)
            h = _layernorm(h + attn_out, lp["ln1_g"], lp["ln1_b"])
            ff = jax.lax.psum(
                jnp.maximum(h @ lp["w1"] + lp["b1"], 0.0) @ lp["w2"],
                axis) + lp["b2"]
            h = _layernorm(h + ff, lp["ln2_g"], lp["ln2_b"])
        return h @ jnp.asarray(params["embed"]).T, k_pages, v_pages

    return step


def verify_step_fn(cfg: DecodeConfig, n_shards: int, axis: str = AXIS_TP,
                   impl: str = "reference", force: str = "auto"):
    """Build the shard_map body for one speculative VERIFY step — the
    mesh twin of ``generate.verify_step`` (ISSUE 16): Sq = 1+d ragged
    query rows per sequence through ``paged_decode_attention``'s
    ``q_lengths`` arm, over the LOCAL KV-head pool shard.

    fn(params, tokens [B, Sqm], pos_c [B, Sqm], q_lens [B],
       tables [B, maxp], lengths [B], pages [B*Sqm], slots [B*Sqm],
       b_idx [B*Sqm], t_idx [B*Sqm], k_pages, v_pages)
      -> (logits [B, Sqm, V] replicated, new k_pages, new v_pages)

    The K/V append reuses the prefill body's stable-shape scatter (the
    host pads the claim to B*Sqm rows by repeating the last one —
    duplicate indices with identical values are a no-op); the page
    stream is the SAME as the decode step's (each live page reads once
    per sequence), which is the amortization mesh speculation banks.
    Rows past ``q_lens[i]`` are padding garbage the caller ignores."""
    H_local, Hkv_local = _local_heads(cfg, n_shards)
    d, Dh = cfg.d_model, cfg.head_dim

    def step(params, tokens, pos_c, q_lens, tables, lengths,
             pages, slots, b_idx, t_idx, k_pages, v_pages):
        B, Sqm = tokens.shape
        h = jnp.asarray(params["embed"])[tokens] * np.sqrt(d) \
            + jnp.asarray(params["pos"])[pos_c]  # [B, Sqm, d]
        for li, lp in enumerate(params["layers"]):
            q = (h @ lp["wq"]).reshape(B, Sqm, H_local, Dh)
            k = (h @ lp["wk"]).reshape(B, Sqm, Hkv_local, Dh)
            v = (h @ lp["wv"]).reshape(B, Sqm, Hkv_local, Dh)
            k_pages = k_pages.at[li, :, pages, slots].set(k[b_idx, t_idx])
            v_pages = v_pages.at[li, :, pages, slots].set(v[b_idx, t_idx])
            attn = paged_decode_attention(
                q.transpose(0, 2, 1, 3), k_pages[li], v_pages[li],
                tables, lengths, scale=Dh ** -0.5, impl=impl,
                force=force, q_lengths=q_lens,
                pool_layout="xla",
            )  # [B, H_local, Sqm, Dh]
            attn = attn.transpose(0, 2, 1, 3).reshape(B, Sqm,
                                                      H_local * Dh)
            attn_out = jax.lax.psum(attn @ lp["wo"], axis)
            h = _layernorm(h + attn_out, lp["ln1_g"], lp["ln1_b"])
            ff = jax.lax.psum(
                jnp.maximum(h @ lp["w1"] + lp["b1"], 0.0) @ lp["w2"],
                axis) + lp["b2"]
            h = _layernorm(h + ff, lp["ln2_g"], lp["ln2_b"])
        return h @ jnp.asarray(params["embed"]).T, k_pages, v_pages

    return step


def prefill_step_fn(cfg: DecodeConfig, n_shards: int, axis: str = AXIS_TP,
                    force: str = "auto"):
    """Build the shard_map body for one batched whole-prompt prefill.

    fn(params, tokens [B, Smax], lens [B], pages [T], slots [T],
       b_idx [T], t_idx [T], k_pages, v_pages)
      -> (last-position logits [B, V] replicated, new k_pages, new
          v_pages)

    Same sharding as the decode step; the causal pass runs through the
    flash ``k_lengths`` tier over the LOCAL heads (GQA repeats each
    local KV head over its query group for the compute — the pool
    write stays at H_kv/n heads)."""
    H_local, Hkv_local = _local_heads(cfg, n_shards)
    G = cfg.group_size
    d, Dh = cfg.d_model, cfg.head_dim

    def step(params, tokens, lens, pages, slots, b_idx, t_idx,
             k_pages, v_pages):
        B, Smax = tokens.shape
        h = jnp.asarray(params["embed"])[tokens] * np.sqrt(d) \
            + jnp.asarray(params["pos"])[None, :Smax]
        for li, lp in enumerate(params["layers"]):
            q = (h @ lp["wq"]).reshape(B, Smax, H_local, Dh)
            k = (h @ lp["wk"]).reshape(B, Smax, Hkv_local, Dh)
            v = (h @ lp["wv"]).reshape(B, Smax, Hkv_local, Dh)
            k_pages = k_pages.at[li, :, pages, slots].set(k[b_idx, t_idx])
            v_pages = v_pages.at[li, :, pages, slots].set(v[b_idx, t_idx])
            kh, vh = repeat_kv(k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3), G)
            attn = flash_attention(
                q.transpose(0, 2, 1, 3), kh, vh, causal=True,
                scale=Dh ** -0.5, k_lengths=lens, force=force)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, Smax, H_local * Dh)
            attn_out = jax.lax.psum(attn @ lp["wo"], axis)
            h = _layernorm(h + attn_out, lp["ln1_g"], lp["ln1_b"])
            ff = jax.lax.psum(
                jnp.maximum(h @ lp["w1"] + lp["b1"], 0.0) @ lp["w2"],
                axis) + lp["b2"]
            h = _layernorm(h + ff, lp["ln2_g"], lp["ln2_b"])
        h_last = h[jnp.arange(B), lens - 1]
        return h_last @ jnp.asarray(params["embed"]).T, k_pages, v_pages

    return step


def _shard_param(leaf, spec: P, mesh: Mesh):
    """Place one host weight onto the mesh under its PartitionSpec —
    column/row shards land distributed, replicated leaves everywhere."""
    return jax.device_put(jnp.asarray(leaf, jnp.float32),
                          NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# the sharded pool


class ShardedKVCachePool(KVCachePool):
    """KVCachePool whose pages live head-sharded across a mesh axis.

    The HOST side — per-sequence page tables, the free list, admission
    accounting, check_invariants/reclaim_orphans — is inherited
    unchanged and stays global: one page id means the same (per-shard)
    page on every device, so one admission decision reserves capacity
    for the whole mesh.  The DEVICE side shards axis 1 (heads): each
    device holds ``[L, H/n_shards, num_pages, page_size, D]`` — exactly
    1/n_shards of the single-device pool's HBM footprint, which is the
    capacity play: n chips hold n× the concurrent sequences.

    K/V writes on the sharded path happen INSIDE the shard-mapped step
    (each device writes its own heads); the program hands the updated
    arrays back through :meth:`store`.

    Prefix caching (ISSUE 11) rides the host-global bookkeeping for
    free: page refcounts, ``attach_prefix``, LRU eviction, and the
    invariant audit are pure table/free-list state — inherited
    unchanged — and the copy-on-write page copy is a functional update
    along the (unsharded) page axis, so one ``_cow_tail`` executes as
    a per-shard local copy on every device.  A
    ``serving.PrefixCache(pool)`` over this pool therefore shares an
    N-way prefix at 1/n_shards bytes per device with no SPMD-side
    changes; the loop feeds cached-prefix tails through the program's
    decode step (its prefill body starts at position 0)."""

    def __init__(self, num_pages: int, page_size: int, num_layers: int,
                 num_heads: int, head_dim: int, dtype="float32",
                 name: str = "kv", mesh: Optional[Mesh] = None,
                 n_shards: Optional[int] = None, axis: str = AXIS_TP,
                 num_kv_heads: Optional[int] = None):
        import jax.numpy as jnp

        if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
            raise ValueError(
                "int8 KV pages are not supported on the mesh-sharded "
                "pool yet: the SPMD step writes K/V inside shard_map "
                "where the host-side per-page scale bookkeeping cannot "
                "reach — use a replicated single-device pool for int8, "
                "or fp32/bf16 on the mesh")
        if mesh is None:
            n = int(n_shards or 1)
            mesh = Mesh(np.asarray(host_mesh_devices(n)), (axis,))
        self.mesh = mesh
        self.axis = axis
        self.n_shards = int(mesh.shape[axis])
        h_kv = int(num_kv_heads if num_kv_heads is not None else num_heads)
        if h_kv % self.n_shards:
            raise ValueError(
                f"num_kv_heads={h_kv} must divide by the mesh's "
                f"{axis} axis ({self.n_shards}) — the pool shards over "
                "the KV-head dim")
        super().__init__(num_pages, page_size, num_layers, num_heads,
                         head_dim, dtype=dtype, name=name,
                         num_kv_heads=num_kv_heads)
        self.sharding = NamedSharding(mesh, _kv_spec(axis))
        # TPU: place the pool in the XLA-preferred layout from birth
        # (kv_pool_layout) so the first step never reshards; CPU has no
        # layout choice
        placement = (kv_pool_layout(self.sharding)
                     if mesh.devices.flat[0].platform == "tpu"
                     else self.sharding)
        self.k_pages = jax.device_put(self.k_pages, placement)
        self.v_pages = jax.device_put(self.v_pages, placement)

    @property
    def heads_per_shard(self) -> int:
        return self.num_kv_heads // self.n_shards

    def bytes_per_page_per_shard(self) -> int:
        """One page's K+V bytes on ONE device (the admission math a
        per-chip HBM budget divides by)."""
        return self.bytes_per_page() // self.n_shards

    def store(self, k_pages, v_pages) -> None:
        """Adopt the step's functionally-updated pool arrays (under the
        pool lock, like every other mutation)."""
        with self._lock:
            self.k_pages = k_pages
            self.v_pages = v_pages


# ---------------------------------------------------------------------------
# the program


class ShardedDecodeProgram:
    """The decode/prefill step pair, jitted once over a tp mesh.

    Drives the same host-side protocol as serving/generate.py's module
    functions — claim (page, slot)s from the pool, run the step, adopt
    the updated pool arrays — so ``ContinuousBatchingLoop(...,
    program=...)`` swaps the single-device math for the SPMD program
    with no loop changes: admission, quarantine, retirement, and the
    page-leak invariants all run unmodified.

    ``paged_impl``: like the loop's — None reads FLAGS_serving_paged_impl
    and resolves against the pool geometry on first use ('auto' is the
    reference gather on CPU meshes; the pallas page reader runs
    per-shard unchanged on TPU, its grid was already per-head).
    """

    def __init__(self, params: Dict, cfg: DecodeConfig,
                 n_shards: Optional[int] = None,
                 devices: Optional[Sequence] = None, axis: str = AXIS_TP,
                 force: str = "auto", paged_impl: Optional[str] = None):
        if devices is None:
            devices = host_mesh_devices(int(n_shards or 1))
        elif n_shards is not None:
            if len(devices) < int(n_shards):
                raise ValueError(
                    f"n_shards={n_shards} but only {len(devices)} devices "
                    "were supplied — a silently smaller mesh would change "
                    "per-chip pool capacity and cost")
            devices = list(devices)[: int(n_shards)]
        self.cfg = cfg
        self.axis = axis
        self.n_shards = len(devices)
        _local_heads(cfg, self.n_shards)  # both head counts must split
        self.force = force
        self._requested_impl = paged_impl
        self.paged_impl: Optional[str] = None  # resolved on first pool use
        self.mesh = Mesh(np.asarray(devices), (axis,))
        self._pspecs = param_partition_specs(cfg, axis)
        # PartitionSpec is a tuple subclass, so a naive two-tree
        # tree_map would flatten INTO the specs; flatten_up_to stops at
        # the params treedef's leaves instead
        leaves, treedef = jax.tree_util.tree_flatten(dict(params))
        spec_leaves = treedef.flatten_up_to(self._pspecs)
        self.params = jax.tree_util.tree_unflatten(treedef, [
            _shard_param(leaf, spec, self.mesh)
            for leaf, spec in zip(leaves, spec_leaves)])
        self._decode_jit = None
        self._prefill_jit = None
        self._verify_jit = None

    # -- pool ----------------------------------------------------------

    def make_pool(self, num_pages: int, page_size: int,
                  dtype="float32", name: str = "kv") -> ShardedKVCachePool:
        """A pool shaped for this program's model (H_kv heads for a GQA
        config), KV-head-sharded over the program's mesh."""
        return ShardedKVCachePool(
            num_pages, page_size, self.cfg.n_layer, self.cfg.n_head,
            self.cfg.head_dim, dtype=dtype, name=name, mesh=self.mesh,
            axis=self.axis, num_kv_heads=self.cfg.num_kv_heads)

    def resolve_impl(self, pool: KVCachePool) -> str:
        """Resolve (once) the paged-attention impl against this pool's
        geometry — the label every metric carries."""
        if self.paged_impl is None:
            self.paged_impl = resolve_paged_impl(
                self._requested_impl, pool.page_size, self.cfg.head_dim,
                pool.k_pages.dtype)
        return self.paged_impl

    def _check_pool(self, pool) -> None:
        if getattr(pool, "mesh", None) is not self.mesh:
            raise ValueError(
                "pool is not sharded over this program's mesh — build it "
                "with program.make_pool(...) (a replicated or "
                "foreign-mesh pool would reshard every step)")

    # -- jit construction ----------------------------------------------

    def _build(self, body, n_rep: int = 6):
        """Jit one shard-mapped step body: `n_rep` replicated operands
        ride between the params pytree and the two kv pool shards (6
        for decode/prefill, 9 for the wider verify signature)."""
        kv = _kv_spec(self.axis)
        rep = P()
        # check_vma off: pallas_call has no replication rule, and the
        # logits ARE replicated by construction (every shard holds the
        # same psum-joined activations) — tests pin bit-identity
        fn = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(self._pspecs,) + (rep,) * n_rep + (kv, kv),
            out_specs=(rep, kv, kv), check_vma=False)
        if self.mesh.devices.flat[0].platform != "tpu":
            # CPU meshes have no layout choice to make — and no tax
            return jax.jit(fn)
        # TPU: pin the pool args/results (aliased across steps via
        # store()) to the XLA-preferred layout the kernel consumes, so
        # the pool lives relayout-free across the whole serving life
        ns = lambda spec: NamedSharding(self.mesh, spec)
        kv_io = kv_pool_layout(ns(kv))
        param_sh = jax.tree_util.tree_map(
            ns, self._pspecs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.jit(
            fn,
            in_shardings=(param_sh,) + (ns(rep),) * n_rep
            + (kv_io, kv_io),
            out_shardings=(ns(rep), kv_io, kv_io))

    def _decode(self):
        if self._decode_jit is None:
            self._decode_jit = self._build(decode_step_fn(
                self.cfg, self.n_shards, self.axis,
                impl=self.paged_impl or "reference", force=self.force))
        return self._decode_jit

    def _prefill(self):
        if self._prefill_jit is None:
            self._prefill_jit = self._build(prefill_step_fn(
                self.cfg, self.n_shards, self.axis, force=self.force))
        return self._prefill_jit

    def _verify(self):
        if self._verify_jit is None:
            self._verify_jit = self._build(verify_step_fn(
                self.cfg, self.n_shards, self.axis,
                impl=self.paged_impl or "reference", force=self.force),
                n_rep=9)
        return self._verify_jit

    # -- the ContinuousBatchingLoop program protocol --------------------

    def decode_step(self, pool: ShardedKVCachePool,
                    seq_ids: Sequence[int], tokens, positions
                    ) -> np.ndarray:
        """One continuous-batching decode step (generate.decode_step's
        contract): claim one (page, slot) per sequence, run the SPMD
        step, adopt the updated pool shards; returns logits [B, V]."""
        self._check_pool(pool)
        self.resolve_impl(pool)
        tokens = np.asarray(tokens, np.int32)
        positions = np.asarray(positions, np.int32)
        pages, slots = pool.append_token(seq_ids)
        tables, lengths = pool.page_table_batch(seq_ids)
        logits, k_pages, v_pages = self._decode()(
            self.params, tokens, positions, pages, slots,
            tables, lengths, pool.k_pages, pool.v_pages)
        pool.store(k_pages, v_pages)
        return np.asarray(logits)

    def verify_step(self, pool: ShardedKVCachePool,
                    seq_ids: Sequence[int],
                    blocks: Sequence[Sequence[int]],
                    start_positions: Sequence[int],
                    pad_to: Optional[int] = None) -> np.ndarray:
        """One speculative verify step under the SPMD program —
        ``generate.verify_step``'s exact host protocol (ONE atomic
        ``append_tokens`` claim, 8-bucketed page tables, stable-shape
        scatter padding, rows past ``len(blocks[i])`` are garbage) so
        ``ContinuousBatchingLoop(..., program=...)`` speculates with no
        loop changes; returns logits [B, Sq_max, V].  The caller owns
        acceptance and rollback (``pool.truncate_seq``)."""
        self._check_pool(pool)
        self.resolve_impl(pool)
        lens = np.asarray([len(b) for b in blocks], np.int32)
        if not len(lens) or lens.min() < 1:
            raise ValueError("verify needs >= 1 fed token per sequence")
        starts = np.asarray(start_positions, np.int32)
        B, Sqm = len(blocks), int(lens.max())
        if pad_to is not None:
            if pad_to < Sqm:
                raise ValueError(
                    f"pad_to {pad_to} < longest block {Sqm}")
            Sqm = int(pad_to)
        if int((starts + lens).max()) > self.cfg.max_length:
            # before append_tokens: a failed verify must not leave
            # claimed slots with no K/V behind (the pool's atomicity
            # contract)
            raise ValueError(
                f"verify block reaches position "
                f"{int((starts + lens).max())} > max_length "
                f"{self.cfg.max_length}")
        tokens = np.zeros((B, Sqm), np.int32)
        for i, b in enumerate(blocks):
            tokens[i, :lens[i]] = b
        pages, slots = pool.append_tokens(seq_ids, lens)
        tables, lengths = pool.page_table_batch(seq_ids)
        if tables.shape[1] % 8:
            # 8-bucketed table width: one compile shape per 8 pages of
            # growth (padded entries are length-masked page-0 walks)
            padded = -(-tables.shape[1] // 8) * 8
            tables = np.pad(tables,
                            ((0, 0), (0, padded - tables.shape[1])))
        b_idx = np.repeat(np.arange(B), lens)
        t_idx = np.concatenate([np.arange(n) for n in lens])
        # stable-shape scatter: pad the claim to B*Sqm rows by
        # repeating the last (page, slot) and its source row —
        # duplicate indices with identical values are a no-op
        pad_rows = B * Sqm - len(b_idx)
        if pad_rows:
            b_idx = np.concatenate([b_idx,
                                    np.full(pad_rows, b_idx[-1])])
            t_idx = np.concatenate([t_idx,
                                    np.full(pad_rows, t_idx[-1])])
            pages = np.concatenate([pages, np.full(pad_rows, pages[-1],
                                                   pages.dtype)])
            slots = np.concatenate([slots, np.full(pad_rows, slots[-1],
                                                   slots.dtype)])
        pos = starts[:, None] + np.arange(Sqm)[None, :]
        pos_c = np.minimum(pos, self.cfg.max_length - 1)
        logits, k_pages, v_pages = self._verify()(
            self.params, tokens, pos_c.astype(np.int32), lens, tables,
            lengths, np.asarray(pages), np.asarray(slots),
            b_idx.astype(np.int32), t_idx.astype(np.int32),
            pool.k_pages, pool.v_pages)
        pool.store(k_pages, v_pages)
        return np.asarray(logits)

    def prefill_step(self, pool: ShardedKVCachePool,
                     seq_ids: Sequence[int],
                     prompts: Sequence[Sequence[int]]) -> np.ndarray:
        """Batched whole-prompt prefill (generate.prefill_step's
        contract) under the SPMD program; returns last-position logits
        [B, V]."""
        self._check_pool(pool)
        self.resolve_impl(pool)
        lens = np.asarray([len(p) for p in prompts], np.int32)
        if not len(lens) or lens.min() < 1:
            raise ValueError("prefill needs non-empty prompts")
        B, Smax = len(prompts), int(lens.max())
        if Smax > self.cfg.max_length:
            raise ValueError(
                f"prompt length {Smax} > max_length {self.cfg.max_length}")
        tokens = np.zeros((B, Smax), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :lens[i]] = p
        pages, slots = pool.append_tokens(seq_ids, lens)
        b_idx = np.repeat(np.arange(B), lens).astype(np.int32)
        t_idx = np.concatenate([np.arange(n) for n in lens]).astype(np.int32)
        logits, k_pages, v_pages = self._prefill()(
            self.params, tokens, lens, pages, slots, b_idx, t_idx,
            pool.k_pages, pool.v_pages)
        pool.store(k_pages, v_pages)
        return np.asarray(logits)
