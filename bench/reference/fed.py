"""Plain reference of a synchronous federated round with FedVision's Eq. 6
aggregation and AdamW local steps.

One round, for each client c in turn (a block of rows at a time, so it fits
beside nothing else on the chip): loss and gradient of the client's own
batch at its current parameters, one AdamW step on its own moments, then
aggregation over all clients:

- a client's score of layer bucket j is |sum of its parameters in j after
  training - the same sum after its previous training| (Eq. 6); the sums
  start from the initial parameters;
- each client uploads its ``topn`` best-scoring buckets (ties included);
- an uploaded bucket becomes the weighted mean over the clients that
  uploaded it, on every client; a bucket nobody uploaded keeps each
  client's own values.

"Layer buckets": every slice of a parameter stacked on a leading layer axis
is bucket l of layer l; every other parameter falls in the one extra
bucket after the layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def adamw_init(params):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"m": jax.tree.map(z, params), "v": jax.tree.map(z, params), "t": 0}


def adamw_step(params, grads, state, opt: dict):
    """AdamW (decoupled decay), bias-corrected; moments and arithmetic in f32,
    parameters stored back in their own dtype."""
    b1, b2, lr, eps, wd = opt["b1"], opt["b2"], opt["lr"], opt["eps"], opt.get("weight_decay", 0.0)
    t = state["t"] + 1
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32), state["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g.astype(jnp.float32)), state["v"], grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m_, v_):
        step = lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) + lr * wd * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - step).astype(p.dtype)

    return jax.tree.map(upd, params, m, v), {"m": m, "v": v, "t": t}


def bucket_sums(params, n_layers: int, stacked: tuple[str, ...]) -> jnp.ndarray:
    """(n_layers + 1,) f32 signed sums of the parameters in each bucket."""
    out = jnp.zeros((n_layers + 1,), jnp.float32)
    for key, sub in params.items():
        for leaf in jax.tree.leaves(sub):
            x = leaf.astype(jnp.float32)
            if key in stacked:
                out = out.at[: x.shape[0]].add(jnp.sum(x.reshape(x.shape[0], -1), axis=1))
            else:
                out = out.at[n_layers].add(jnp.sum(x))
    return out


def eq6_aggregate(rows: list, prev_sums: list, weights: list[float], topn: int,
                  n_layers: int, stacked: tuple[str, ...]):
    """rows: per-client parameter trees after local training. Returns
    (aggregated rows, the new per-client sums)."""
    sums = [bucket_sums(r, n_layers, stacked) for r in rows]
    n = min(topn, n_layers + 1)
    ups = []
    for s, prev in zip(sums, prev_sums):
        score = jnp.abs(s - prev)
        kth = jnp.sort(score)[::-1][n - 1]
        ups.append((score >= kth).astype(jnp.float32))
    wm = [u * w for u, w in zip(ups, weights)]  # (B,) per client
    den = sum(wm)

    def mix(key, *leaves):
        # per-element bucket of this leaf: its layer index, or the extra one
        stacked_leaf = key in stacked
        def wcol(w):
            if stacked_leaf:
                return w[: leaves[0].shape[0]].reshape((-1,) + (1,) * (leaves[0].ndim - 1))
            return w[n_layers]
        d = wcol(den)
        g = sum(wcol(w) * x.astype(jnp.float32) for w, x in zip(wm, leaves)) / jnp.maximum(d, 1e-30)
        return [jnp.where(d > 0, g, x.astype(jnp.float32)).astype(x.dtype) for x in leaves]

    out = [dict() for _ in rows]
    for key in rows[0]:
        subs = [r[key] for r in rows]
        flat = [jax.tree.flatten(s) for s in subs]
        treedef = flat[0][1]
        mixed = [mix(key, *ls) for ls in zip(*[f[0] for f in flat])]
        for c in range(len(rows)):
            out[c][key] = jax.tree.unflatten(treedef, [m[c] for m in mixed])
    return out, sums
