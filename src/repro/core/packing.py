"""Packed-buffer transport for the aggregation engine (DESIGN.md §7, §11).

The packed ``(C, N_total)`` buffer is the *canonical round state* of the
flat engine (DESIGN.md §11): ``state["params"]`` IS this buffer, clients
train on per-leaf views reconstructed from the :class:`PackSpec` slots
(`unpack_views` — reshape-of-slice, fused into consumers under jit), and
trained leaves are written back in place with `write_slots` (donated-buffer
dynamic-update-slices). ``pack`` / ``unpack`` survive only at the edges:
``make_state``, checkpoint PUT, and model dispatch to serving.

Layer buckets reuse `compression.leaf_layer_ids`: each slot of the buffer
spans a contiguous range of Eq. 6 score buckets (scan-stacked layers map to
one bucket per layer; all unstacked tensors share the final "misc" bucket).
The bucket structure is kept *slot-wise* (offset + bucket count per leaf)
rather than as a materialized per-element id vector, so building a
``PackSpec`` for a 314B-param arch costs nothing; the explicit ``(N,)`` id
vector is only materialized for the Pallas kernel path and benchmarks.

Reduction tiling (the CPU-reference side of the §11 re-tile): XLA CPU runs
ONE whole-buffer elementwise fusion multi-threaded, but serializes a
concat of many small per-slot fusions, and batched/sliced dot_generals
transpose-copy their operands. The reducers below therefore lower to a
small number of fused multiply-add chains over *maximal merged runs* of
slots (`merged_runs`), with the 1/den division folded into the per-bucket
weights so no (C, N) weight or intermediate buffer ever materializes.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import compression as comp
from repro.models.params import is_info

PyTree = Any


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    name: str  # keystr path, for debugging/benchmarks
    shape: tuple[int, ...]  # per-client leaf shape (no leading C)
    offset: int  # element offset into the packed buffer
    size: int  # number of elements
    bucket_off: int  # first Eq.6 score bucket this slot touches
    n_buckets: int  # contiguous buckets spanned (layers, or 1 for misc)

    @property
    def per_bucket(self) -> int:
        return self.size // self.n_buckets


@dataclasses.dataclass(frozen=True)
class PackSpec:
    n_total: int
    n_buckets: int  # total score buckets (cfg.n_layers + 1)
    slots: tuple[LeafSlot, ...]


def build_pack_spec(cfg, template: PyTree) -> PackSpec:
    """Flatten the param template into slot metadata (trace-time, cheap)."""
    leaves = jax.tree_util.tree_flatten_with_path(template, is_leaf=is_info)[0]
    slots: list[LeafSlot] = []
    off = 0
    for path, info in leaves:
        size = max(math.prod(info.shape), 1)
        kind, boff = comp.leaf_layer_ids(path, info, cfg)
        if kind == "stack2":
            nb = info.shape[0] * info.shape[1]
        elif kind == "stack1":
            nb = info.shape[0]
        else:
            nb = 1
        slots.append(LeafSlot(jax.tree_util.keystr(path), tuple(info.shape), off, size, boff, nb))
        off += size
    return PackSpec(off, comp.n_score_buckets(cfg), tuple(slots))


def packed_pspec(spec: PackSpec, client_axis: str, mesh=None, axis_sizes: dict | None = None):
    """PartitionSpec for the (C, N_total) buffer: client dim on the client
    axis, flat dim sharded over the "model" axis when it exists and divides
    N_total (restores per-device memory scaling for the persistent packed
    state of quant8 at FSDP scale), else replicated."""
    from jax.sharding import PartitionSpec as P

    from repro.models.params import PROD_AXIS_SIZES

    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    else:
        sizes = PROD_AXIS_SIZES if axis_sizes is None else axis_sizes
    if "model" in sizes and spec.n_total % sizes["model"] == 0:
        return P(client_axis, "model")
    return P(client_axis, None)


@functools.lru_cache(maxsize=16)
def bucket_ids(spec: PackSpec) -> np.ndarray:
    """Explicit (N_total,) int32 bucket id per element — Pallas/bench path
    only; the jnp reference path never materializes it."""
    return np.concatenate(
        [
            np.repeat(np.arange(s.n_buckets, dtype=np.int32) + s.bucket_off, s.per_bucket)
            for s in spec.slots
        ]
    )


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

def pack(spec: PackSpec, stacked: PyTree, dtype=None) -> jax.Array:
    """Client-stacked pytree -> one (C, N_total) buffer (one concat/round).

    With dtype=None the buffer takes the promoted dtype of all leaves, so a
    mixed-precision tree (bf16 weights + f32 norms) packs without rounding
    any leaf; unpack casts each slot back to its own dtype.
    """
    leaves = jax.tree.leaves(stacked)
    C = leaves[0].shape[0]
    if dtype is None:
        dtype = functools.reduce(jnp.promote_types, (x.dtype for x in leaves))
    return jnp.concatenate([x.reshape(C, -1).astype(dtype) for x in leaves], axis=1)


def unpack(spec: PackSpec, packed: jax.Array, like: PyTree) -> PyTree:
    """(C, N_total) buffer -> pytree shaped/dtyped like `like`."""
    leaves, treedef = jax.tree.flatten(like)
    C = packed.shape[0]
    out = [
        packed[:, s.offset : s.offset + s.size].reshape((C,) + s.shape).astype(l.dtype)
        for s, l in zip(spec.slots, leaves)
    ]
    return jax.tree.unflatten(treedef, out)


def unpack_views(spec: PackSpec, packed: jax.Array, like: PyTree) -> PyTree:
    """Per-leaf *views* of the packed round state: reshape-of-slice only.

    The flat engine's replacement for `unpack` inside the jitted round: each
    leaf is ``packed[:, off:off+size].reshape((C,) + shape)`` in the buffer's
    own dtype, so XLA fuses the slice into whatever consumes the leaf — no
    (C, N_total) copy materializes on the round boundary. `like` supplies
    only the tree structure (a ParamInfo template or any matching pytree);
    dtype-converting reconstruction is `unpack`'s job and stays at the edges.
    """
    from repro.models.params import is_info

    treedef = jax.tree.structure(like, is_leaf=is_info)
    C = packed.shape[0]
    out = [
        jax.lax.slice_in_dim(packed, s.offset, s.offset + s.size, axis=1).reshape((C,) + s.shape)
        for s in spec.slots
    ]
    return jax.tree.unflatten(treedef, out)


def write_slots(spec: PackSpec, packed: jax.Array, stacked: PyTree) -> jax.Array:
    """Write trained leaves back into the packed buffer (unpack_views'
    inverse). One dynamic-update-slice per slot; under the donated round jit
    XLA aliases these into the incoming buffer, so the write-back is the
    only data movement on the round boundary — there is no pack concat."""
    C = packed.shape[0]
    for s, leaf in zip(spec.slots, jax.tree.leaves(stacked)):
        packed = jax.lax.dynamic_update_slice(
            packed, leaf.reshape(C, s.size).astype(packed.dtype), (0, s.offset)
        )
    return packed


# ---------------------------------------------------------------------------
# reduction tiling: maximal merged runs of uniform-width buckets
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)
def merged_runs(spec: PackSpec) -> tuple[tuple[int, int, int, int], ...]:
    """Maximal contiguous (column, bucket) runs with one per-bucket width.

    Each run ``(col0, bucket0, n_buckets, per)`` satisfies
    ``bucket(col0 + i) == bucket0 + i // per``: adjacent slots merge when
    both their columns and their bucket ranges continue the run (scan-stacked
    leaves of one tensor; same-shape misc tensors do NOT merge — they share
    one bucket). The fused reducers iterate runs, not slots, so a uniform
    32-leaf buffer is ONE multiply-add chain XLA can thread across.
    """
    runs: list[tuple[int, int, int, int]] = []
    for s in spec.slots:
        if runs:
            col0, b0, nb, per = runs[-1]
            if (
                per == s.per_bucket
                and s.offset == col0 + nb * per
                and s.bucket_off == b0 + nb
            ):
                runs[-1] = (col0, b0, nb + s.n_buckets, per)
                continue
        runs.append((s.offset, s.bucket_off, s.n_buckets, s.per_bucket))
    return tuple(runs)


# clients beyond this fall back to contraction ops: the fused chains unroll
# one multiply-add per client. Measured on the CPU reference (N=262k, B=32):
# the chain's RUNTIME still wins to C~128 (97ms vs 181ms einsum at C=128),
# but its compile time grows with the unroll (6s at C=512, 16s at C=1024 vs
# a flat 1.5s for the contraction) — 64 is where the remaining runtime edge
# stops paying for the trace/compile blow-up at federation scale.
CHAIN_MAX_CLIENTS = 64


# ---------------------------------------------------------------------------
# bucket <-> element maps (no N-sized constants: slot-wise broadcasts)
# ---------------------------------------------------------------------------

def expand_bucket_vec(spec: PackSpec, vec: jax.Array) -> jax.Array:
    """(..., n_buckets) bucket vector -> (..., N_total) per-element vector.

    Iterates `merged_runs`, not slots: a uniform buffer expands as ONE
    broadcast instead of one slice/broadcast/concat triple per leaf."""
    parts = []
    for (_, b0, nb, per) in merged_runs(spec):
        v = jax.lax.slice_in_dim(vec, b0, b0 + nb, axis=-1)
        v = jnp.broadcast_to(v[..., None], v.shape + (per,))
        parts.append(v.reshape(v.shape[:-2] + (nb * per,)))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def bucket_sums(spec: PackSpec, packed: jax.Array) -> jax.Array:
    """Per-bucket signed element sums: (C, N_total) -> (C, n_buckets) f32.

    Packed-buffer equivalent of `compression.layer_sums` (Eq. 6 inner sums),
    vectorized over the client dim.
    """
    C = packed.shape[0]
    out = jnp.zeros((C, spec.n_buckets), jnp.float32)
    for s in spec.slots:
        x = packed[:, s.offset : s.offset + s.size].astype(jnp.float32)
        sums = x.reshape(C, s.n_buckets, s.per_bucket).sum(axis=-1)
        out = out.at[:, s.bucket_off : s.bucket_off + s.n_buckets].add(sums)
    return out


# ---------------------------------------------------------------------------
# the one masked/weighted reduction every mode lowers to
# ---------------------------------------------------------------------------

def weighted_mean(packed: jax.Array, weights: jax.Array, mask: jax.Array | None = None) -> jax.Array:
    """Unmasked Eq. 5 over the flat buffer: (C, N), (C,) -> (N,) f32.

    The fast path for modes whose upload mask is uniform across buckets
    (dense, server-optimizer). `mask` is the optional (C,) 0/1 participation
    vector from the scheduler — masked-out client rows drop from both
    numerator and denominator. The 1/sum(w) normalization is folded into the
    per-client weights, so the reduction is a single whole-buffer fused
    multiply-add chain (one threaded XLA fusion; see module docstring) for
    small C, or one contraction beyond CHAIN_MAX_CLIENTS.
    """
    C = packed.shape[0]
    w = weights.astype(jnp.float32)
    if mask is not None:
        w = w * mask.astype(jnp.float32)
    wn = w / jnp.maximum(jnp.sum(w), 1e-12)
    if C > CHAIN_MAX_CLIENTS:
        return jnp.einsum("c,cn->n", wn, packed.astype(jnp.float32))
    acc = packed[0].astype(jnp.float32) * wn[0]
    for c in range(1, C):
        acc = acc + packed[c].astype(jnp.float32) * wn[c]
    return acc


def grouped_weighted_mean(
    packed: jax.Array,
    weights: jax.Array,
    group_size: int,
    mask: jax.Array | None = None,
    *,
    impl: str = "ref",
) -> tuple[jax.Array, jax.Array]:
    """Per-group renormalized Eq. 5 — the hierarchical inner reduce.

    packed (C, N), weights (C,), C % group_size == 0 ->
    (rows (C/G, N) f32, den (C/G,) f32) with
    ``rows[g] = sum_i w[gG+i] x[gG+i] / den[g]`` and
    ``den[g] = sum_i w[gG+i]`` (mask folded in). A group nobody in
    participated has den 0 and a zero row — callers must mask it out of the
    outer reduce (`aggregators/hier.py` does). The 1/den renormalization is
    folded into the per-member weights exactly like `weighted_mean`, so each
    group is one fused multiply-add chain over its members (G <= cutover) or
    the whole buffer is ONE batched contraction (G above it).
    """
    C, N = packed.shape
    G = group_size
    if G < 1 or C % G:
        raise ValueError(f"group_size={G} must divide n_clients={C}")
    ngroups = C // G
    w = weights.astype(jnp.float32)
    if mask is not None:
        w = w * mask.astype(jnp.float32)
    wg = w.reshape(ngroups, G)
    den = jnp.sum(wg, axis=1)  # (C/G,)
    wn = wg / jnp.maximum(den, 1e-12)[:, None]
    if impl == "pallas":
        from repro.kernels import pack as _pk  # deferred: kernels are optional here

        return _pk.grouped_reduce(packed, wn), den
    xg = packed.astype(jnp.float32).reshape(ngroups, G, N)
    if G > CHAIN_MAX_CLIENTS:
        return jnp.einsum("gi,gin->gn", wn, xg), den
    acc = xg[:, 0] * wn[:, 0][:, None]
    for i in range(1, G):
        acc = acc + xg[:, i] * wn[:, i][:, None]
    return acc, den


def masked_bucket_mean(
    packed: jax.Array,
    wmask: jax.Array,
    spec: PackSpec,
    mask: jax.Array | None = None,
    *,
    impl: str = "ref",
) -> tuple[jax.Array, jax.Array]:
    """Weighted mean over clients under a per-(client, bucket) mask.

    packed: (C, N); wmask: (C, B) — participation weight times the 0/1
    upload mask per score bucket; mask: optional (C,) 0/1 participation
    vector (None -> everyone). Returns (global (N,) f32, den (B,) f32):
    ``global[n] = sum_c mask[c] wmask[c, b(n)] x[c, n] / den[b(n)]`` with
    ``den[b] = sum_c mask[c] wmask[c, b]`` (0 where nobody uploaded). den is
    the per-BUCKET denominator — expand with `expand_bucket_vec` (consumers
    fuse the expansion into their own passes; a materialized (N,) den would
    cost the reduction an extra write pass for pure bookkeeping).

    The ref impl folds 1/den into the per-bucket weights and runs one fused
    multiply-add chain per `merged_runs` tile — no (C, N) weight expansion,
    no per-slot dot_generals (XLA CPU transpose-copies their operands), and
    the division costs no extra pass over the buffer.
    """
    C = packed.shape[0]
    wm = wmask.astype(jnp.float32)
    if mask is not None:
        wm = wm * mask.astype(jnp.float32)[:, None]
    den_b = jnp.sum(wm, axis=0)  # (B,)
    if impl == "pallas":
        from repro.kernels import pack as _pk  # deferred: kernels are optional here

        ids = jnp.asarray(bucket_ids(spec))
        # the tile bound MUST be computed for the kernel's actual N-block
        # width — a wider block spans more buckets than a narrower bound
        # and the out-of-window ids would silently one-hot to zero
        num, den = _pk.packed_bucket_reduce(
            packed, wmask, ids, mask,
            bucket_tile=bucket_tile_bound(spec, _pk.BLOCK_N),
        )
        return num / jnp.maximum(den, 1e-12), den_b
    wn = wm / jnp.maximum(den_b, 1e-12)[None, :]
    runs = merged_runs(spec)
    if C > CHAIN_MAX_CLIENTS:
        parts = [
            jnp.einsum(
                "cb,cbp->bp",
                jax.lax.slice_in_dim(wn, b0, b0 + nb, axis=1),
                packed[:, col0 : col0 + nb * per].astype(jnp.float32).reshape(C, nb, per),
            ).reshape(nb * per)
            for (col0, b0, nb, per) in runs
        ]
    else:
        parts = []
        for (col0, b0, nb, per) in runs:
            xs = jax.lax.slice_in_dim(packed, col0, col0 + nb * per, axis=1)
            xs = xs.astype(jnp.float32).reshape(C, nb, per)
            wt = jax.lax.slice_in_dim(wn, b0, b0 + nb, axis=1)  # (C, nb)
            acc = xs[0] * wt[0][:, None]
            for c in range(1, C):
                acc = acc + xs[c] * wt[c][:, None]
            parts.append(acc.reshape(nb * per))
    g = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return g, den_b


@functools.lru_cache(maxsize=16)
def bucket_tile_bound(spec: PackSpec, block_n: int = 1024) -> int:
    """Max distinct buckets any block_n-aligned window of the packed buffer
    touches (padding id B included) — the Pallas kernel's bucket-tile width.
    Host-side and cached: derived from slot metadata via the id vector."""
    ids = bucket_ids(spec)
    pad = (-len(ids)) % block_n
    if pad:
        ids = np.concatenate([ids, np.full(pad, spec.n_buckets, np.int32)])
    win = ids.reshape(-1, block_n)
    # ids need not be monotonic across slot boundaries (a later slot can
    # restart at bucket 0), so the span is max - min per window
    return int((win.max(axis=1) - win.min(axis=1)).max()) + 1


# ---------------------------------------------------------------------------
# row-block int8 quantization of the packed buffer (quant8 transport)
# ---------------------------------------------------------------------------

def quantize_rows_ref(x: jax.Array, block: int) -> tuple[jax.Array, jax.Array]:
    """(C, N) f32 -> (q int8 (C, N), scales f32 (C, ceil(N/block)))."""
    C, N = x.shape
    pad = (-N) % block
    xb = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad))).reshape(C, -1, block)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(C, -1)[:, :N], scale


def dequantize_rows_ref(q: jax.Array, scales: jax.Array, block: int, dtype=jnp.float32) -> jax.Array:
    C, N = q.shape
    pad = (-N) % block
    qb = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, pad))).reshape(C, -1, block)
    return (qb * scales[..., None]).reshape(C, -1)[:, :N].astype(dtype)


def quant8_mean_ref(delta: jax.Array, weights: jax.Array, block: int) -> jax.Array:
    """Fused quant8 encode -> reduce: (C, N), (C,) -> (N,) f32 weighted sum
    of dequant(quant(delta)) with NO materialized int8 payload or (C, N)
    dequant buffer. ``clip(round(x/s), -127, 127)`` in f32 is bit-identical
    to the int8 round-trip (|q| <= 127 is exact in f32), so this is the
    collective-free transport path: per-client dequantized rows feed one
    fused multiply-add chain. Weights are used as-is (the scheduler
    normalizes them); fold the participation mask in before calling.
    """
    C, N = delta.shape
    pad = (-N) % block
    x = jnp.pad(delta.astype(jnp.float32), ((0, 0), (0, pad)))
    w = weights.astype(jnp.float32)

    def dq(row):  # (N+pad,) -> dequantized (N+pad,) f32
        xb = row.reshape(-1, block)
        amax = jnp.max(jnp.abs(xb), axis=-1)
        scale = jnp.maximum(amax, 1e-12) / 127.0
        q = jnp.clip(jnp.round(xb / scale[:, None]), -127, 127)
        return (q * scale[:, None]).reshape(-1)

    if C > CHAIN_MAX_CLIENTS:
        acc = jnp.einsum("c,cn->n", w, jax.vmap(dq)(x))
    else:
        acc = dq(x[0]) * w[0]
        for c in range(1, C):
            acc = acc + dq(x[c]) * w[c]
    return acc[:N] if pad else acc


def dequant_reduce_ref(q: jax.Array, scales: jax.Array, weights: jax.Array, block: int) -> jax.Array:
    """Fused decode -> reduce for the gathered int8 transport: (C, N) int8 +
    (C, ceil(N/block)) scales + (C,) weights -> (N,) f32 weighted sum,
    without materializing the (C, N) f32 dequant buffer."""
    C, N = q.shape
    pad = (-N) % block
    qp = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, pad)))
    w = weights.astype(jnp.float32)

    def dq(row, s):
        return (row.reshape(-1, block) * s[:, None]).reshape(-1)

    if C > CHAIN_MAX_CLIENTS:
        acc = jnp.einsum("c,cn->n", w, jax.vmap(dq)(qp, scales))
    else:
        acc = dq(qp[0], scales[0]) * w[0]
        for c in range(1, C):
            acc = acc + dq(qp[c], scales[c]) * w[c]
    return acc[:N] if pad else acc


# ---------------------------------------------------------------------------
# communication frontier (DESIGN.md §15): counter PRNG, 4-bit transport,
# pairwise integer masking — jnp twins of the kernels.ref NumPy oracles
# ---------------------------------------------------------------------------

# constants shared bit-for-bit with kernels.ref (the NumPy oracles) and the
# kernels.quant4 / kernels.mask Pallas bodies
FMIX_C1 = 0x85EBCA6B
FMIX_C2 = 0xC2B2AE35
GOLDEN = 0x9E3779B9
IDX_C = 0x9E3779B1
IDX_N = 0x85EBCA77
IDX_E = 0xC2B2AE3D


def fmix32(h: jax.Array) -> jax.Array:
    """murmur3 fmix32 over uint32 lanes (ref.fmix32_np's traced twin)."""
    h = jnp.asarray(h).astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(FMIX_C1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(FMIX_C2)
    h = h ^ (h >> 16)
    return h


def round_key(seed, round_idx) -> jax.Array:
    """Per-round PRNG key from a static session seed and the TRACED round
    counter carried in agg_state — the key is a traced uint32 scalar, so
    per-round randomness never retraces the jitted round."""
    r = jnp.asarray(round_idx).astype(jnp.uint32)
    return fmix32(jnp.uint32(seed & 0xFFFFFFFF) ^ fmix32(r + jnp.uint32(GOLDEN)))


def counter_uniform(key, c_idx, n_idx) -> jax.Array:
    """u in [0, 1) f32 from the (client, element) counter hash; c_idx and
    n_idx broadcast (uint32)."""
    bits = fmix32(
        jnp.asarray(key).astype(jnp.uint32)
        + jnp.asarray(c_idx).astype(jnp.uint32) * jnp.uint32(IDX_C)
        + jnp.asarray(n_idx).astype(jnp.uint32) * jnp.uint32(IDX_N)
    )
    return (bits >> 8).astype(jnp.float32) * jnp.float32(2.0**-24)


def _quant4_dq_block(xb: jax.Array, u, mode: str) -> jax.Array:
    """(nb, block) f32 -> dequant(quant4) per block. u: matching uniforms
    for stochastic mode (ignored for nearest). Clip AFTER the floor: in f32
    7 + u can round to 8.0."""
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 7.0
    v = xb / scale[..., None]
    if mode == "nearest":
        q = jnp.clip(jnp.round(v), -7, 7)
    else:
        q = jnp.clip(jnp.floor(v + u), -7, 7)
    return q * scale[..., None]


def quant4_dequant_rows_ref(x: jax.Array, block: int, key=0, mode: str = "nearest") -> jax.Array:
    """(C, N) -> (C, N) f32 dequant(quant4(x)) per client row — the value a
    client uploads under 4-bit transport (topk_ef x quant4 composition)."""
    C, N = x.shape
    pad = (-N) % block
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad))).reshape(C, -1, block)
    if mode == "stochastic":
        u = counter_uniform(
            key,
            jnp.arange(C, dtype=jnp.uint32)[:, None],
            jnp.arange(N + pad, dtype=jnp.uint32)[None, :],
        ).reshape(C, -1, block)
    else:
        u = jnp.zeros_like(xp)
    return _quant4_dq_block(xp, u, mode).reshape(C, -1)[:, :N]


def quant4_mean_ref(delta: jax.Array, weights: jax.Array, block: int, key=0, mode: str = "nearest") -> jax.Array:
    """Fused 4-bit encode -> reduce (quant8_mean_ref's 4-bit sibling):
    (C, N), (C,) -> (N,) f32 weighted sum of dequant(quant4(delta)) with no
    materialized payload. Weights are used as-is; fold the participation
    mask in before calling. ref.quant4_reduce_np is the NumPy oracle."""
    C, N = delta.shape
    pad = (-N) % block
    x = jnp.pad(delta.astype(jnp.float32), ((0, 0), (0, pad)))
    w = weights.astype(jnp.float32)
    nidx = jnp.arange(N + pad, dtype=jnp.uint32)

    def dq(row, c):
        xb = row.reshape(-1, block)
        if mode == "stochastic":
            u = counter_uniform(key, c, nidx).reshape(-1, block)
        else:
            u = jnp.zeros_like(xb)
        return _quant4_dq_block(xb, u, mode).reshape(-1)

    if C > CHAIN_MAX_CLIENTS:
        acc = jnp.einsum(
            "c,cn->n", w, jax.vmap(dq)(x, jnp.arange(C, dtype=jnp.uint32))
        )
    else:
        acc = dq(x[0], jnp.uint32(0)) * w[0]
        for c in range(1, C):
            acc = acc + dq(x[c], jnp.uint32(c)) * w[c]
    return acc[:N] if pad else acc


def secure_client_masks(rk, participation: jax.Array, n: int) -> jax.Array:
    """(C,) 0/1 participation -> (C, n) uint32 pairwise-mask sums.

    Client c carries sum_{p>c} m_cp - sum_{p<c} m_pc over ACTIVE pairs
    (both endpoints selected), all mod 2^32, so the masks cancel EXACTLY in
    the active-row modular sum — not to float tolerance. A deselected
    client activates no pair, so it contributes no orphan mask. O(C^2 n)
    like any pairwise scheme; the secure aggregator bounds C at build time.
    ref.secure_masked_rows_np is the oracle twin."""
    act = participation.astype(jnp.float32) > 0
    C = act.shape[0]
    cidx = jnp.arange(C, dtype=jnp.uint32)
    nidx = jnp.arange(n, dtype=jnp.uint32)
    M = jnp.zeros((C, n), jnp.uint32)
    for p in range(C):
        pu = jnp.uint32(p)
        lo = jnp.minimum(cidx, pu)
        hi = jnp.maximum(cidx, pu)
        pk = fmix32(fmix32(jnp.asarray(rk).astype(jnp.uint32) + lo * jnp.uint32(IDX_C)) ^ (hi * jnp.uint32(IDX_N)))
        bits = fmix32(pk[:, None] + nidx[None, :] * jnp.uint32(IDX_E))  # (C, n)
        signed = jnp.where((cidx < pu)[:, None], bits, jnp.uint32(0) - bits)
        active = act & act[p] & (cidx != pu)
        M = M + jnp.where(active[:, None], signed, jnp.uint32(0))
    return M


def secure_sum_ref(q: jax.Array, participation: jax.Array, rk, *, use_masks: bool = True) -> jax.Array:
    """q (C, N) int32 -> (N,) int32 sum over participating rows, optionally
    through pairwise uint32 masking. Bitwise-equal either way: the masks
    cancel exactly in the modular sum (ref.secure_sum_np oracle)."""
    act = participation.astype(jnp.float32) > 0
    rows = jax.lax.bitcast_convert_type(q.astype(jnp.int32), jnp.uint32)
    if use_masks:
        rows = rows + secure_client_masks(rk, participation, q.shape[1])
    gated = jnp.where(act[:, None], rows, jnp.uint32(0))
    total = jnp.sum(gated, axis=0, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(total, jnp.int32)
