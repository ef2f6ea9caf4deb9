"""fed_round: one federated round as a single jit-able SPMD program.

Flat-state engine (DESIGN.md §11): for every client-stacked aggregator the
canonical round state ``state["params"]`` IS the packed ``(C, N_total)``
buffer from `core.packing`. One round:
  1. per-leaf *views* of the buffer are reconstructed from the PackSpec
     slots (`packing.unpack_views` — reshape-of-slice, fused into the
     training consumers, no copy);
  2. `vmap` of the local trainer over the views — each mesh slice along the
     client axis trains its own divergent model copy for E local steps
     (lax.scan), with *no* cross-client collectives;
  3. trained leaves are written back in place (`packing.write_slots`) and
     the buffer goes STRAIGHT to the configured
     :mod:`repro.core.aggregators` strategy — no pack concat, no unpack
     copy on the round boundary; pack/unpack survive only at the
     `make_state` / checkpoint / serving edges.
Jit the round with :func:`jit_fed_round` so the state (and with it the
packed operand chain) is donated — XLA aliases the round's buffers in
place instead of double-buffering the model state.

``FedConfig.state_layout="tree"`` keeps the PR 3 engine (param pytree state,
pack -> aggregate -> unpack each round) as the numerical reference:
tests/test_flat_engine.py pins the flat engine against it bit-for-bit under
full participation (1-2 ulp under masked/compact, where the surrounding
program shape changes the compiler's FMA contraction choices).

Partial participation (DESIGN.md §8): the Task Scheduler's selection enters
the jitted round as a *traced* participation pytree (`participation_input`),
so per-round selection changes never retrace. `FedConfig.participation`
picks the round body:
  - ``full``   — every client trains; weights alone shape the aggregate
                 (PR 1 behavior, and the numerical reference);
  - ``masked`` — per-client `lax.cond` gates the whole local-training scan
                 on the mask; unselected clients carry params/opt through
                 unchanged and drop out of the aggregation denominator;
  - ``compact``— a static budget K = max_participants gathers the selected
                 client rows into a compact (K, ...) axis, trains only
                 those, and scatters back — per-round local-training work is
                 K/C of full participation (on the flat state the gather is
                 K rows of the packed buffer).

There is no mode-specific branching here: `FedConfig.aggregation` names any
registered aggregator, whose cross-round state lives under ``state["agg"]``.
The same builder also yields `make_state`, `state_template`, and the
sharding specs used by the launcher and the dry-run.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import aggregators, packing
from repro.models import params as mp
from repro.models import transformer, yolov3
from repro.optim import Optimizer

PyTree = Any


@dataclasses.dataclass(frozen=True)
class FedConfig:
    n_clients: int
    local_steps: int = 1
    aggregation: str = "eq6"  # any name in repro.core.aggregators.names()
    topn: int = 8  # Eq. 6 / static_topn upload budget (layer buckets)
    client_axis: str = "pod"  # mesh axis acting as the federation
    data_axis: str | None = "data"  # within-client data-parallel axis
    round_idx_static: int = 0  # static_topn: trace-time round phase
    microbatches: int = 1  # grad-accumulation splits of each local step
    agg_impl: str = "ref"  # ref (jnp) | pallas (packed kernels; mode from the backend)
    quant_block: int = 1024  # quant8: elements per int8 scale block
    server_lr: float = 1.0  # fedavgm/fedadam server step (fedadam wants ~0.01-0.1)
    server_momentum: float = 0.9  # fedavgm momentum / fedadam b1
    server_beta2: float = 0.99  # fedadam second-moment decay
    server_eps: float = 1e-3  # fedadam adaptivity floor (Reddi et al. tau)
    trim_ratio: float = 0.25  # trimmed_mean: fraction trimmed per side (>=1 client)
    participation: str = "full"  # full | masked | compact (DESIGN.md §8)
    max_participants: int = 0  # compact: static per-round budget K (0 -> C)
    state_layout: str = "flat"  # flat (packed (C,N) round state) | tree (PR 3 reference)
    mode: str = "sync"  # sync | async (buffered FedBuff-style engine, DESIGN.md §12)
    buffer_size: int = 0  # async: K_buf staged updates per flush (0 -> n_clients)
    staleness_alpha: float = 0.5  # async: polynomial staleness discount (1+s)^-alpha
    max_staleness: int = 0  # async: drop updates staler than this (0 -> keep all)
    group_size: int = 0  # hier: edge-group width G (DESIGN.md §13; 0 -> C, one group)
    hier_base: str = "dense"  # hier: the registered reducer composed over group rows
    stream: bool = False  # async: streaming O(buffer_size*N) flush (DESIGN.md §13)
    # --- communication frontier (DESIGN.md §15) ---
    topk_frac: float = 0.1  # topk_ef: uploaded fraction k/N of each client delta
    topk_quant: str = "none"  # topk_ef: quantize the selected values (none | quant4)
    quant4_mode: str = "stochastic"  # quant4: stochastic | nearest | skip (dense passthrough)
    quant4_seed: int = 0  # quant4/topk_ef: session seed of the per-round counter PRNG
    secure_domain: str = "int8"  # secure: shared-scale integer ring width (int8 | int4)
    secure_mask: bool = True  # secure: pairwise masks on (False -> plain integer sum)
    secure_session: int = 0  # secure: session key feeding the per-round mask PRNG
    # --- multi-process transport (DESIGN.md §14) ---
    transport: str = "inproc"  # inproc (SimClock event heap) | socket (real wire)
    wire_codec: str = "dense"  # dense | quant8 | quant4 | topk (see transport/codec.py)
    queue_cap: int = 0  # socket: bounded landing-queue depth (0 -> 2 * n_clients)
    heartbeat_s: float = 0.2  # socket: worker heartbeat period (wall seconds)
    heartbeat_timeout_s: float = 2.0  # socket: silence beyond this marks a client dead
    # --- serving plane (DESIGN.md §17) ---
    serve_batch: int = 8  # inference batch slots of the jitted decode+NMS program
    serve_max_wait_s: float = 0.004  # batcher linger: how long a formed batch waits to fill
    serve_max_detections: int = 16  # NMS output slots per served image
    serve_soft_stale_rounds: int = 2  # freshness: rounds-behind beyond this -> soft_stale
    serve_hard_stale_rounds: int = 8  # freshness: rounds-behind beyond this -> hard_stale
    serve_soft_stale_s: float = 60.0  # freshness: seconds-behind beyond this -> soft_stale
    serve_hard_stale_s: float = 600.0  # freshness: seconds-behind beyond this -> hard_stale


def loss_for(cfg: ArchConfig) -> Callable:
    if cfg.family == "yolo":
        return lambda params, batch: yolov3.yolo_loss(params, batch, cfg)
    return lambda params, batch: transformer.loss_fn(cfg, params, batch)


def make_template(cfg: ArchConfig) -> PyTree:
    if cfg.family == "yolo":
        return yolov3.template(cfg)
    return transformer.template(cfg)


def make_aggregator(cfg: ArchConfig, fed: FedConfig, mesh=None) -> aggregators.Aggregator:
    """Resolve FedConfig.aggregation through the registry (build-time
    validation: unknown names and invalid mode configs fail here)."""
    tpl = make_template(cfg)
    spec = packing.build_pack_spec(cfg, tpl)
    ctx = aggregators.AggContext(cfg=cfg, fed=fed, template=tpl, spec=spec, mesh=mesh)
    return aggregators.get(fed.aggregation)(ctx)


# ---------------------------------------------------------------------------
# Sharding specs
# ---------------------------------------------------------------------------

def stacked_pspecs(template: PyTree, client_axis: str, rules: dict | None = None) -> PyTree:
    """Param PartitionSpecs with the leading client dim on `client_axis`."""
    base = mp.pspecs(template, rules)
    return jax.tree.map(lambda s: P(client_axis, *s), base, is_leaf=lambda x: isinstance(x, P))


def batch_pspecs(batch_template: PyTree, fed: FedConfig) -> PyTree:
    spec = P(fed.client_axis, None, fed.data_axis)  # (C, E, b, ...)
    return jax.tree.map(lambda _: spec, batch_template)


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------

def _layout(fed: FedConfig) -> str:
    if fed.state_layout not in ("flat", "tree"):
        raise ValueError(
            f"unknown state_layout {fed.state_layout!r}; expected flat|tree"
        )
    return fed.state_layout


def state_template(cfg: ArchConfig, fed: FedConfig, optimizer: Optimizer, dtype) -> PyTree:
    """Abstract FedState (ShapeDtypeStructs) for dry-run lowering."""
    agg = make_aggregator(cfg, fed)
    tpl = agg.ctx.template
    pabs = mp.abstract(tpl, dtype)
    if not agg.stacked:
        stack = lambda t: t  # FedSGD-equivalent: one shared model copy
    else:
        stack = lambda t: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((fed.n_clients,) + s.shape, s.dtype), t
        )
    opt_abs = jax.eval_shape(optimizer.init, pabs)
    packed_abs = jax.ShapeDtypeStruct((fed.n_clients, agg.ctx.spec.n_total), dtype)
    if agg.stacked and _layout(fed) == "flat":
        params_abs = packed_abs  # the packed buffer IS the round state
    else:
        params_abs = stack(pabs)
    return {
        "params": params_abs,
        "opt": stack(opt_abs),
        "agg": jax.eval_shape(agg.init_state, packed_abs) if agg.stacked else {},
        "round": jax.ShapeDtypeStruct((), jnp.int32),
    }


def make_state(cfg: ArchConfig, fed: FedConfig, optimizer: Optimizer, rng, dtype=jnp.float32) -> PyTree:
    agg = make_aggregator(cfg, fed)
    tpl = agg.ctx.template
    if not agg.stacked:
        params = mp.init_params(tpl, rng, dtype)
        return {"params": params, "opt": optimizer.init(params), "agg": {}, "round": jnp.int32(0)}
    keys = jax.random.split(rng, fed.n_clients)
    params = jax.vmap(lambda k: mp.init_params(tpl, k, dtype))(keys)
    # clients start from the same global model (server dispatch)
    params = jax.tree.map(lambda x: jnp.broadcast_to(x[:1], x.shape), params)
    opt = jax.vmap(optimizer.init)(params)
    if _layout(fed) == "flat":
        # the ONE pack of the flat engine: init is an edge, not the round
        packed = packing.pack(agg.ctx.spec, params, dtype)
        return {
            "params": packed,
            "opt": opt,
            "agg": agg.init_state(packed),
            "round": jnp.int32(0),
        }
    # tree layout: pack the initial params only for aggregators that keep
    # packed state — eval_shape first so stateless modes skip the O(C*N)
    # concat entirely
    packed_abs = jax.ShapeDtypeStruct((fed.n_clients, agg.ctx.spec.n_total), dtype)
    agg_abs = jax.eval_shape(agg.init_state, packed_abs)
    agg_state = (
        agg.init_state(packing.pack(agg.ctx.spec, params))
        if jax.tree.leaves(agg_abs)
        else agg_abs
    )
    return {
        "params": params,
        "opt": opt,
        "agg": agg_state,
        "round": jnp.int32(0),
    }


def unpacked_params(cfg: ArchConfig, fed: FedConfig, state: PyTree, dtype=jnp.float32) -> PyTree:
    """Edge helper: the client-stacked param *pytree* from a FedState,
    whatever the layout — flat states unpack (one copy, edge cost), tree and
    fedsgd states pass through."""
    params = state["params"]
    if not isinstance(params, jax.Array):
        return params
    tpl = make_template(cfg)
    spec = packing.build_pack_spec(cfg, tpl)
    like = jax.tree.map(lambda i: jax.ShapeDtypeStruct(i.shape, dtype), tpl,
                        is_leaf=mp.is_info)
    return packing.unpack(spec, params, like)


def state_pspecs(cfg: ArchConfig, fed: FedConfig, optimizer: Optimizer, rules: dict | None = None, opt_rules: dict | None = None) -> PyTree:
    """opt_rules: optional separate sharding rules for optimizer moments —
    ZeRO-1 style (moments sharded over data while params stay TP-only)."""
    agg = make_aggregator(cfg, fed)
    tpl = agg.ctx.template
    if not agg.stacked:
        pspec = mp.pspecs(tpl, rules)
        mspec = mp.pspecs(tpl, opt_rules) if opt_rules else pspec
    else:
        tree_pspec = stacked_pspecs(tpl, fed.client_axis, rules)
        pspec = (
            packing.packed_pspec(agg.ctx.spec, fed.client_axis)
            if _layout(fed) == "flat"
            else tree_pspec
        )
        mspec = stacked_pspecs(tpl, fed.client_axis, opt_rules) if opt_rules else tree_pspec
    opt_shape = jax.eval_shape(optimizer.init, mp.abstract(tpl, jnp.float32))
    ospec = {k: (mspec if k in ("mu", "m", "v") else P()) for k in opt_shape}
    return {
        "params": pspec,
        "opt": ospec,
        "agg": agg.state_pspecs() if agg.stacked else {},
        "round": P(),
    }


# ---------------------------------------------------------------------------
# Participation input
# ---------------------------------------------------------------------------

def static_budget(fed: FedConfig) -> int:
    """Compact mode's static per-round participant count K."""
    return fed.max_participants or fed.n_clients


def participation_input(fed: FedConfig, mask, weights, idx=None) -> dict:
    """Host arrays from the scheduler -> the traced pytree fed_round takes.

    mask: (C,) 0/1; weights: (C,) normalized over participants; idx: (K,)
    int32 selected-client indices, required (and only used) in compact mode.
    The structure is fixed per FedConfig, so only leaf *values* change per
    round — selection never retraces the jitted round.
    """
    part = {
        "mask": jnp.asarray(mask, jnp.float32),
        "weights": jnp.asarray(weights, jnp.float32),
    }
    if fed.participation == "compact":
        if idx is None:
            raise ValueError("compact participation needs the (K,) idx vector")
        idx = jnp.asarray(idx, jnp.int32)
        if idx.shape != (static_budget(fed),):
            raise ValueError(
                f"compact idx has shape {idx.shape}; the static budget is "
                f"({static_budget(fed)},) — the scheduler must emit exactly K indices"
            )
        if len(np.unique(np.asarray(idx))) != idx.shape[0]:
            # the engines rely on distinctness: gather/scatter by idx must
            # be invertible (and the K == C flat fast path treats idx as a
            # permutation) — a duplicate would silently train a client twice
            raise ValueError(
                f"compact idx {np.asarray(idx).tolist()} has duplicate "
                "client indices; the scheduler must select K distinct clients"
            )
        part["idx"] = idx
    return part


def _parse_participation(fed: FedConfig, part) -> tuple[jax.Array, jax.Array | None, jax.Array | None]:
    """Normalize fed_round's third argument.

    A bare (C,) array is the PR 1 calling convention: weights only, full
    participation (mask None keeps the aggregation graph bit-identical to
    the pre-participation engine). A dict is participation_input's output.
    """
    if isinstance(part, dict):
        return part["weights"].astype(jnp.float32), part["mask"].astype(jnp.float32), part.get("idx")
    return part.astype(jnp.float32), None, None


# ---------------------------------------------------------------------------
# The round
# ---------------------------------------------------------------------------

def build_fed_round(cfg: ArchConfig, fed: FedConfig, optimizer: Optimizer, mesh=None, rules: dict | None = None) -> Callable:
    """Returns fed_round(state, batch, part) -> (state, metrics).

    batch leaves: (C, E, per_step_shard...). part: either a bare (C,)
    normalized weight vector (full participation, the PR 1 convention) or
    the `participation_input` pytree {mask, weights[, idx]} from the
    scheduler. metrics: {"loss": participant mean, "client_loss": (C,)}.

    `FedConfig.state_layout` picks the engine: "flat" trains on slot views
    of the packed (C, N_total) round state and writes back in place (jit via
    `jit_fed_round` to donate the state); "tree" is the PR 3 reference
    (param pytree state, pack -> aggregate -> unpack every round).

    `rules` shapes the per-leaf training-state shardings (consumed via
    state_pspecs by the launcher); the packed aggregation operand itself
    shards (client_axis, "model") when divisible — packing.packed_pspec.
    """
    agg = make_aggregator(cfg, fed, mesh)
    if fed.mode != "sync":
        # this builder always emits the synchronous round — silently
        # ignoring buffer_size/staleness_alpha here would masquerade as
        # async. The buffered control plane lives in
        # core/async_engine.BufferedAsyncEngine (which calls back into this
        # builder with mode="sync" for its full-buffer flush).
        raise ValueError(
            f"build_fed_round builds the synchronous round (mode='sync'), got "
            f"mode={fed.mode!r}; drive async mode through "
            "core/async_engine.BufferedAsyncEngine or FLServer"
        )
    if fed.participation not in ("full", "masked", "compact"):
        raise ValueError(
            f"unknown participation {fed.participation!r}; expected full|masked|compact"
        )
    if fed.participation != "full" and not agg.stacked:
        raise ValueError(
            f"participation={fed.participation!r} needs a client-stacked "
            "topology; fedsgd runs one shared model copy (use participation='full')"
        )
    if fed.participation == "compact":
        K = static_budget(fed)
        if not 1 <= K <= fed.n_clients:
            raise ValueError(
                f"compact participation: max_participants={fed.max_participants} "
                f"must be in [1, n_clients={fed.n_clients}]"
            )
    if _layout(fed) == "tree":
        return _build_tree_round(cfg, fed, optimizer, agg)
    return _build_flat_round(cfg, fed, optimizer, agg, mesh)


def jit_fed_round(round_fn: Callable) -> Callable:
    """Jit a fed_round with the state donated (DESIGN.md §11 donation
    contract): the incoming FedState's buffers — including the packed
    (C, N_total) params of the flat engine — are reused in place by XLA, so
    the round holds ONE copy of the model state instead of two. Callers must
    drop the old state (``state, m = fr(state, ...)``); timing loops that
    replay one state must use plain `jax.jit`."""
    return jax.jit(round_fn, donate_argnums=(0,))


def _local_training(cfg: ArchConfig, fed: FedConfig, optimizer: Optimizer):
    """The shared per-client training kernels: (local_train,
    gated_local_train) over param/opt pytrees — identical computation in
    both state layouts (the flat engine feeds slot views instead of
    materialized leaves)."""
    loss_fn = loss_for(cfg)

    def grads_of(params, step_batch):
        """Gradients for one local step, with microbatch accumulation.

        (A measured alternative — putting the micro scan inside the
        differentiated function so the gradient tree is produced once —
        left the collective term unchanged and tripled temp memory on the
        gemma3 single-pod dry-run; see EXPERIMENTS.md §Perf hillclimb #2.)
        """
        if fed.microbatches <= 1:
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, step_batch)
            return loss, grads
        micro = jax.tree.map(
            lambda x: x.reshape((fed.microbatches, x.shape[0] // fed.microbatches) + x.shape[1:]),
            step_batch,
        )

        def acc(carry, mb):
            tot, g_acc = carry
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            return (tot + loss, jax.tree.map(jnp.add, g_acc, g)), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (tot, g_sum), _ = jax.lax.scan(acc, (jnp.float32(0), zeros), micro)
        n = jnp.float32(fed.microbatches)
        return tot / n, jax.tree.map(lambda g: (g / n.astype(g.dtype)), g_sum)

    def local_train(params, opt, client_batch):
        def step(carry, micro):
            p, o = carry
            with jax.named_scope("forward_backward"):
                loss, grads = grads_of(p, micro)
            with jax.named_scope("optimizer"):
                p, o = optimizer.update(p, grads, o)
            return (p, o), loss

        (params, opt), losses = jax.lax.scan(step, (params, opt), client_batch)
        return params, opt, jnp.mean(losses)

    def gated_local_train(on, params, opt, client_batch):
        """Whole-client gate: the masked branch carries params/opt through
        untouched (vmap lowers the cond to a select along the client axis)."""
        return jax.lax.cond(
            on > 0,
            local_train,
            lambda p, o, b: (p, o, jnp.float32(0.0)),
            params, opt, client_batch,
        )

    return local_train, gated_local_train


def _train_clients_fn(fed: FedConfig, local_train, gated_local_train, mesh=None):
    """full/masked dispatch over materialized-or-view param trees; compact's
    gather/scatter stays with each engine (it moves state rows).

    With a mesh whose client axis has more than one shard, training runs
    shard-local under shard_map: each device vmaps only its own clients.
    Left to the SPMD partitioner instead, the vmapped round hands it the
    client-grouped convolutions vmap makes of a per-client conv (forward
    and weight gradient), which it splits wrongly — the detector's sharded
    round then diverges from the one-device round."""

    def train_clients(params, opt, batch, mask, spmd_axis_name=fed.client_axis):
        if fed.participation == "masked":
            rows = jax.tree.leaves(params)[0].shape[:1]  # the shard's clients under shard_map
            on = jnp.ones(rows, jnp.float32) if mask is None else mask
            return jax.vmap(gated_local_train, spmd_axis_name=spmd_axis_name)(
                on, params, opt, batch
            )
        return jax.vmap(local_train, spmd_axis_name=spmd_axis_name)(params, opt, batch)

    if _client_shards(fed, mesh) <= 1:
        return train_clients
    rows = P(fed.client_axis)
    return jax.shard_map(
        functools.partial(train_clients, spmd_axis_name=None),
        mesh=mesh, in_specs=(rows, rows, rows, rows), out_specs=(rows, rows, rows),
        axis_names={fed.client_axis}, check_vma=False,
    )


def _round_metrics(fed: FedConfig, loss, mask):
    if mask is None:
        mean_loss = jnp.mean(loss)
    else:
        mean_loss = jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return {"loss": mean_loss, "client_loss": loss}


def _check_compact_idx(fed: FedConfig, idx):
    if fed.participation == "compact" and idx is None:
        raise ValueError(
            "compact participation: pass participation_input(fed, mask, "
            "weights, idx), not a bare weight vector"
        )


def _fedsgd_round(fed: FedConfig, local_train, state, batch):
    # FedSGD-equivalent: clients = data-parallel shards, E=1,
    # param-averaging == gradient-averaging (DESIGN.md §5). One
    # shared model copy, so FSDP-style rules fit huge archs.
    p, o, loss = local_train(state["params"], state["opt"], batch)
    return (
        {**state, "params": p, "opt": o, "round": state["round"] + 1},
        {"loss": loss, "client_loss": jnp.full((fed.n_clients,), loss)},
    )


def _build_tree_round(cfg: ArchConfig, fed: FedConfig, optimizer: Optimizer, agg) -> Callable:
    """The PR 3 engine: pytree state, pack -> aggregate -> unpack per round.

    Kept verbatim as the numerical reference for the flat engine — the
    equivalence suite demands bit-for-bit agreement, so the computation here
    must not drift."""
    spec = agg.ctx.spec
    local_train, gated = _local_training(cfg, fed, optimizer)
    train_clients = _train_clients_fn(fed, local_train, gated)

    def fed_round(state, batch, part):
        weights, mask, idx = _parse_participation(fed, part)
        if not agg.stacked:
            return _fedsgd_round(fed, local_train, state, batch)
        _check_compact_idx(fed, idx)
        if fed.participation == "compact":
            # gather the K selected client rows into a compact axis: local
            # training runs K clients' worth of work, not C (DESIGN.md §8).
            take = lambda t: jax.tree.map(lambda x: jnp.take(x, idx, axis=0), t)
            p_k, o_k, loss_k = jax.vmap(local_train)(
                take(state["params"]), take(state["opt"]), take(batch)
            )
            put = lambda full, upd: jax.tree.map(lambda x, u: x.at[idx].set(u), full, upd)
            loss = jnp.zeros((fed.n_clients,), jnp.float32).at[idx].set(loss_k)
            new_p, new_o = put(state["params"], p_k), put(state["opt"], o_k)
        else:
            new_p, new_o, loss = train_clients(state["params"], state["opt"], batch, mask)
        packed = packing.pack(spec, new_p)
        packed_out, agg_state = agg.aggregate(packed, weights, state["agg"], mask)
        out = {
            **state,
            "params": packing.unpack(spec, packed_out, new_p),
            "opt": new_o,
            "agg": agg_state,
            "round": state["round"] + 1,
        }
        return out, _round_metrics(fed, loss, mask)

    return fed_round


def _client_shards(fed: FedConfig, mesh) -> int:
    """Size of the mesh axis acting as the federation (1 without a mesh)."""
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(fed.client_axis, 1)


def _build_flat_round(cfg: ArchConfig, fed: FedConfig, optimizer: Optimizer, agg, mesh=None) -> Callable:
    """The flat-state engine (DESIGN.md §11): state["params"] is the packed
    (C, N_total) buffer. Training consumes slot views (reshape-of-slice) and
    writes trained leaves back in place; the aggregator reads the buffer
    directly — the per-round pack/unpack copies of the tree engine are gone,
    and under `jit_fed_round`'s donation XLA reuses the state buffers.

    With a mesh whose client axis has more than one shard, the round pins
    the buffer's C dim to that axis (`packing.packed_pspec`) on entry and
    exit — per-client training and the hier inner reduce then run
    shard-local, the single cross-shard merge lives inside the aggregator,
    and `jit_fed_round` still emits ONE donated program (DESIGN.md §13).
    A 1-shard client axis adds no constraint, keeping the single-device
    program bit-identical to the meshless build.

    Named scopes put each op of the round under ``forward_backward``,
    ``optimizer`` (both from `_local_training`), ``write_slots`` or
    ``aggregate`` in its ``op_name``, so a device trace splits the round by
    them; they change metadata only, not the numbers."""
    spec = agg.ctx.spec
    tpl = agg.ctx.template
    local_train, gated = _local_training(cfg, fed, optimizer)
    train_clients = _train_clients_fn(fed, local_train, gated, mesh)
    constrain = None
    if _client_shards(fed, mesh) > 1:
        if fed.n_clients % _client_shards(fed, mesh):
            raise ValueError(
                f"sharded client axis: n_clients={fed.n_clients} must be "
                f"divisible by the '{fed.client_axis}' mesh axis "
                f"({_client_shards(fed, mesh)} shards)"
            )
        sharding = jax.sharding.NamedSharding(
            mesh, packing.packed_pspec(spec, fed.client_axis, mesh)
        )
        constrain = lambda x: jax.lax.with_sharding_constraint(x, sharding)

    def fed_round(state, batch, part):
        weights, mask, idx = _parse_participation(fed, part)
        if not agg.stacked:
            return _fedsgd_round(fed, local_train, state, batch)
        _check_compact_idx(fed, idx)
        packed = state["params"]
        if constrain is not None:
            packed = constrain(packed)
        if fed.participation == "compact" and static_budget(fed) == fed.n_clients:
            # K == C: the scheduler's idx is a permutation, so gathering
            # rows by idx and scattering them back is an identity — train
            # the views directly and skip two (C, N) row moves. No loss
            # scatter either: the vmap output is already in client order
            # (gather-then-scatter by the same permutation would restore
            # exactly this ordering).
            p_k, o_k, loss = jax.vmap(local_train)(
                packing.unpack_views(spec, packed, tpl), state["opt"], batch
            )
            with jax.named_scope("write_slots"):
                packed_new = packing.write_slots(spec, packed, p_k)
            new_o = o_k
        elif fed.participation == "compact":
            # K rows of the packed buffer gather into the compact axis; the
            # trained rows scatter straight back — row moves, not tree walks
            take = lambda t: jax.tree.map(lambda x: jnp.take(x, idx, axis=0), t)
            sub = jnp.take(packed, idx, axis=0)  # (K, N)
            p_k, o_k, loss_k = jax.vmap(local_train)(
                packing.unpack_views(spec, sub, tpl), take(state["opt"]), take(batch)
            )
            put = lambda full, upd: jax.tree.map(lambda x, u: x.at[idx].set(u), full, upd)
            loss = jnp.zeros((fed.n_clients,), jnp.float32).at[idx].set(loss_k)
            with jax.named_scope("write_slots"):
                packed_new = packed.at[idx].set(packing.write_slots(spec, sub, p_k))
            new_o = put(state["opt"], o_k)
        else:
            new_p, new_o, loss = train_clients(
                packing.unpack_views(spec, packed, tpl), state["opt"], batch, mask
            )
            with jax.named_scope("write_slots"):
                packed_new = packing.write_slots(spec, packed, new_p)
        with jax.named_scope("aggregate"):
            packed_out, agg_state = agg.aggregate(packed_new, weights, state["agg"], mask)
        if constrain is not None:
            packed_out = constrain(packed_out)
        out = {
            **state,
            "params": packed_out,
            "opt": new_o,
            "agg": agg_state,
            "round": state["round"] + 1,
        }
        return out, _round_metrics(fed, loss, mask)

    return fed_round


def uniform_weights(n_clients: int) -> jax.Array:
    """Paper Eq. 5: unweighted average."""
    return jnp.full((n_clients,), 1.0 / n_clients, jnp.float32)
