"""fed_sync: synchronous federated rounds through ``FLServer.run_round``.

Set-up makes the initial global weights on the device from the seed (one
jitted call, the reference's own initialiser), a pool of ``pool_rounds``
distinct round batches kept in device memory, and one ``FLServer`` whose
state takes those weights. It then drives that server through its first
three rounds with the window's own call and feed; they compile the round
and give the readings the reference is compared with:

- each client's loss in each of the three rounds;
- the norm, leaf by leaf, of each client's first gradient as AdamW got it
  (its first moment after one step, divided by 1 - b1);
- the norm, leaf by leaf, of each client's parameter change after the
  three rounds (local steps and Eq. 6 aggregation), read before round four.

The window then runs rounds on the same server, cycling the pool, for
``--seconds``. ``round_ms`` is the window's seconds over the rounds it
completed; ``run_round`` reads each round's loss back, so every round ends
synced to the device. Once the window has closed and peak memory is read,
the server is dropped and the plain float32 reference replays the three
rounds client by client at HIGHEST precision.

Each reading is compared leaf by leaf: the gap between the program's norm
and the reference's, over the larger of the reference's norm of that leaf
and of the client's median leaf. Leaves whose reference gradient is under
a thousandth of the median leaf's move by round-off alone under AdamW and
are left out of the change.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from bench import harness
from bench.reference import fed as ref_fed
from bench.reference import rounding

ROUNDS_CHECKED = 3
NEGLIGIBLE_GRAD = 1e-3  # of the median leaf's gradient norm


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def build_server(run: harness.Run, weights):
    """One FLServer for the cell, its state holding ``weights`` on every row."""
    from repro.core import packing
    from repro.core.rounds import FedConfig
    from repro.core.server import FLServer
    from repro.optim import adamw

    cell = run.cell
    fed = FedConfig(n_clients=cell["clients"], local_steps=cell["local_steps"],
                    aggregation=cell["aggregation"], topn=cell["topn"],
                    microbatches=cell.get("microbatches", 1), client_axis="data", data_axis=None)
    mesh = jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        server = FLServer(harness.arch(run.conf), fed, adamw(**cell["optimizer"]), mesh=mesh,
                          task_id=run.workload)
    spec = server.aggregator.ctx.spec
    mine = [(n, tuple(x.shape)) for n, x in zip(leaf_names(weights), jax.tree.leaves(weights))]
    theirs = [(s.name, tuple(s.shape)) for s in spec.slots]
    if mine != theirs:
        raise ValueError(f"benchmark weights do not match the program's layout:\n{mine}\n{theirs}")
    C = fed.n_clients
    server.state["params"] = None  # free the program's own initial rows first
    packed = jax.jit(lambda w: packing.pack(
        spec, jax.tree.map(lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), w), jnp.float32))(weights)
    server.state = {**server.state, "params": packed, "agg": server.aggregator.init_state(packed)}
    return server, mesh


def _slot_norms(spec):
    @jax.jit
    def norms(rows, base):
        return jnp.stack([jnp.linalg.norm((rows[:, s.offset:s.offset + s.size] - base[s.offset:s.offset + s.size]),
                                          axis=1) for s in spec.slots], axis=1)
    return norms


@jax.jit
def _tree_norms(tree):
    return jnp.stack([jnp.linalg.norm(x.reshape(x.shape[0], -1), axis=1) for x in jax.tree.leaves(tree)], axis=1)


def program_readings(run: harness.Run, server, pool) -> dict:
    """The first three rounds of the server, with the window's own call."""
    spec = server.aggregator.ctx.spec
    base = jnp.array(server.state["params"][0])  # a copy: the round donates the state
    b1 = run.cell["optimizer"]["b1"]
    losses, grads = [], None
    for r in range(ROUNDS_CHECKED):
        with run.tracer.span("bench.run_round"):
            server.run_round(pool[r % len(pool)])
        losses.append(np.array(server.scheduler.last_loss, np.float64))
        if r == 0:
            grads = np.asarray(_tree_norms(server.state["opt"]["m"]), np.float64) / (1 - b1)
    change = np.asarray(_slot_norms(spec)(server.state["params"], base), np.float64)
    return {"loss": np.stack(losses), "grad": grads, "change": change, "leaves": [s.name for s in spec.slots]}


def reference_readings(conf: dict, cell: dict, seed: int, batches, *, operands: str | None = None) -> dict:
    """The same rounds by the plain reference, one client at a time. The
    control passes ``operands``: the number format every product's operands
    are rounded to (``bench/reference/rounding.py``)."""
    ref = harness.reference(conf)
    C, E, opt = cell["clients"], cell["local_steps"], cell["optimizer"]
    w0 = harness.make_weights(conf, seed)
    rnd = rounding.operand_rounding(operands)
    grad_of = jax.jit(jax.value_and_grad(lambda p, b: ref.loss(conf, p, b, rnd)))
    step = jax.jit(lambda p, g, s: ref_fed.adamw_step(p, g, s, opt))
    agg = jax.jit(lambda rows, prev: ref_fed.eq6_aggregate(
        rows, prev, [1.0 / C] * C, cell["topn"], ref.n_layers(conf), ref.STACKED))
    sums = jax.jit(lambda p: ref_fed.bucket_sums(p, ref.n_layers(conf), ref.STACKED))
    rows = [w0] * C
    states = [ref_fed.adamw_init(w0) for _ in range(C)]
    prev = [sums(w0)] * C
    losses = np.zeros((len(batches), C))
    grads = []
    for r, batch in enumerate(batches):
        for c in range(C):
            ls = []
            for e in range(E):
                loss, g = grad_of(rows[c], jax.tree.map(lambda x: x[c, e], batch))
                if r == 0 and e == 0:
                    grads.append(np.asarray(_tree_norms(jax.tree.map(lambda x: x[None], g)))[0])
                rows[c], states[c] = step(rows[c], g, states[c])
                ls.append(float(loss))
            losses[r, c] = np.mean(ls)
        rows, prev = agg(rows, prev)
    change = np.stack([np.asarray(_tree_norms(jax.tree.map(lambda x, w: (x - w)[None], row, w0)))[0]
                       for row in rows])
    return {"loss": losses, "grad": np.stack(grads).astype(np.float64), "change": change.astype(np.float64),
            "leaves": leaf_names(w0)}


def leaf_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray | None = None) -> float:
    """Worst (client, leaf) gap of norms, over the larger of the reference's
    norm of that leaf and of the client's median leaf."""
    denom = np.maximum(ref, np.median(ref, axis=1, keepdims=True))
    gap = np.abs(prog - ref) / np.maximum(denom, 1e-30)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    return float(np.max(gap))


def gaps(prog: dict, ref: dict) -> dict:
    """Every number the program's readings can be compared by."""
    if prog["leaves"] != ref["leaves"]:
        raise ValueError(f"leaf order differs: {prog['leaves']} != {ref['leaves']}")
    keep = ref["grad"] >= NEGLIGIBLE_GRAD * np.median(ref["grad"], axis=1, keepdims=True)
    values = {
        "loss_gap": float(np.max(np.abs(prog["loss"] - ref["loss"]) / np.abs(ref["loss"]))),
        "grad_gap": leaf_gap(prog["grad"], ref["grad"]),
        "change_gap": leaf_gap(prog["change"], ref["change"], keep),
    }
    return {k: v if math.isfinite(v) else math.inf for k, v in values.items()}


def drive(run: harness.Run) -> harness.Outcome:
    cell, conf = run.cell, run.conf
    dev = jax.devices()[0]
    traffic = harness.traffic(conf)
    weights = harness.make_weights(conf, run.seed)
    pool = traffic.round_pool(conf, cell, run.seed)
    server, mesh = build_server(run, weights)
    del weights
    with jax.set_mesh(mesh):
        prog = program_readings(run, server, pool)
        run.tracer.start()
        compiles = run.compile_stats.compiles
        with run.tracer.span("bench.window"):
            t0 = time.perf_counter()
            n, losses = 0, []
            while True:
                with run.tracer.span("bench.run_round"):
                    rec = server.run_round(pool[(ROUNDS_CHECKED + n) % len(pool)])
                n += 1
                losses.append(rec.loss)
                if time.perf_counter() - t0 >= run.seconds:
                    break
            window_s = time.perf_counter() - t0
        run.tracer.stop()
        compiles_in_window = run.compile_stats.compiles - compiles
    memory = harness.peak_memory(dev)
    run.log(f"fed_sync: {n} rounds in {window_s:.3f} s; window losses first {losses[0]!r} "
            f"last {losses[-1]!r} max {max(losses)!r} finite {all(map(math.isfinite, losses))}")
    server.state = None
    del server
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(conf, cell, run.seed, pool[:ROUNDS_CHECKED])
    run.log(f"fed_sync: reference took {time.perf_counter() - t_ref:.3f} s")
    checks = harness.checks(gaps(prog, ref), cell["limits"], run.log)
    return harness.Outcome(
        metrics={"setup_s": t0 - run.t_start, "round_ms": window_s / n * 1e3},
        record={"rounds": n, "window_s": window_s, "compiles_in_window": compiles_in_window},
        checks=checks, attempted=n, failed=0, memory_peak_bytes=memory)
