"""Hierarchical two-level aggregation (DESIGN.md §13).

Pins the PR 6 tentpole invariants:
  - `hier` at G=1 and G=C is bit-for-bit the flat engine (params/opt/agg/
    loss) for EVERY registered stacked base — the degenerate geometries
    delegate to the same program by construction;
  - the genuine two-level path (1 < G < C) with a dense base matches the
    flat dense mean analytically (per-group renormalization telescopes);
  - `grouped_weighted_mean` (ref + Pallas `grouped_reduce`) matches the
    NumPy oracle, including masked-out members and empty groups;
  - build-time geometry validation: hier group divisibility, recursion and
    fedsgd-base rejection, quant8's C % G / G % shards check;
  - the sharded client axis reproduces the unsharded round at 1e-6
    (subprocess: tests run on one CPU device, the sharded round forces 2).
"""
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.core import aggregators, packing
from repro.core import rounds as R
from repro.core.rounds import FedConfig
from repro.kernels import pack as pk
from repro.optim import sgd

CFG = get_arch("qwen3-1.7b").reduced()
TPL = R.make_template(CFG)
SPEC = packing.build_pack_spec(CFG, TPL)
C = 4
STACKED_MODES = [
    ("dense", {}),
    ("eq6", {}),
    ("quant8", {}),
    ("static_topn", {}),
    ("fedavgm", {}),
    ("fedadam", {"server_lr": 0.02}),
    ("trimmed_mean", {"trim_ratio": 0.3}),
]


def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _fed(mode, **kw):
    base = dict(n_clients=C, local_steps=1, aggregation=mode, topn=2,
                client_axis="data", data_axis=None, state_layout="flat")
    base.update(kw)
    return FedConfig(**base)


def _toks(seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, CFG.vocab_size, (C, 1, 2, 16)), jnp.int32)


def _run(fed, n=2, seed=0):
    opt = sgd(lr=0.05)
    mesh = _mesh()
    with jax.set_mesh(mesh):
        state = R.make_state(CFG, fed, opt, jax.random.key(seed))
        fr = jax.jit(R.build_fed_round(CFG, fed, opt, mesh))
        for _ in range(n):
            state, m = fr(state, {"tokens": _toks()}, jnp.asarray([0.4, 0.1, 0.3, 0.2], jnp.float32))
    return state, m


_FLAT_CACHE: dict = {}


def _flat(mode, kw):
    key = mode
    if key not in _FLAT_CACHE:
        _FLAT_CACHE[key] = _run(_fed(mode, **kw))
    return _FLAT_CACHE[key]


# ----------------- degenerate geometries == flat, bit for bit ----------------


@pytest.mark.parametrize("mode,kw", STACKED_MODES, ids=[m for m, _ in STACKED_MODES])
@pytest.mark.parametrize("G", [1, C], ids=["G1", "GC"])
def test_hier_degenerate_bitwise_flat(mode, kw, G):
    sf, mf = _flat(mode, kw)
    sh, mh = _run(_fed("hier", group_size=G, hier_base=mode, **kw))
    fl, hl = jax.tree.leaves(sf), jax.tree.leaves(sh)
    assert len(fl) == len(hl)
    for a, b in zip(fl, hl):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert jnp.array_equal(a, b), f"{mode} G={G}: state leaf diverged"
    assert float(mf["loss"]) == float(mh["loss"])


def test_hier_middle_g_dense_matches_flat():
    # per-group renormalization telescopes for the linear dense reduce, so
    # the genuine two-level program agrees with flat to reduction-order ulps
    sf, mf = _flat("dense", {})
    sh, mh = _run(_fed("hier", group_size=2, hier_base="dense"))
    pf = np.asarray(sf["params"], np.float64)
    ph = np.asarray(sh["params"], np.float64)
    scale = max(np.max(np.abs(pf)), 1e-9)
    assert np.max(np.abs(pf - ph)) / scale < 1e-6
    assert abs(float(mf["loss"]) - float(mh["loss"])) < 1e-6


def test_hier_pallas_impl_round_runs():
    s, m = _run(_fed("hier", group_size=2, hier_base="dense", agg_impl="pallas"), n=1)
    sf, _ = _flat("dense", {})
    pf = np.asarray(sf["params"], np.float64)
    # flat cache ran 2 rounds; rerun 1-round flat for the comparison
    s1, _ = _run(_fed("dense"), n=1)
    d = np.abs(np.asarray(s1["params"], np.float64) - np.asarray(s["params"], np.float64))
    assert d.max() / max(np.max(np.abs(pf)), 1e-9) < 1e-5


# ----------------- grouped reduce oracles ------------------------------------


def test_grouped_weighted_mean_matches_numpy_oracle():
    rng = np.random.default_rng(0)
    Cb, N, G = 24, 513, 6
    x = rng.normal(size=(Cb, N)).astype(np.float32)
    w = rng.uniform(0.1, 1.0, Cb).astype(np.float32)
    mask = (rng.uniform(size=Cb) > 0.3).astype(np.float32)
    mask[:G] = 0.0  # group 0 fully masked: zero row, zero den
    rows, den = packing.grouped_weighted_mean(jnp.asarray(x), jnp.asarray(w), G, jnp.asarray(mask))
    wm = (w * mask).reshape(-1, G)
    den_np = wm.sum(axis=1)
    exp = np.einsum("gi,gin->gn", wm / np.maximum(den_np, 1e-12)[:, None], x.reshape(-1, G, N))
    np.testing.assert_allclose(np.asarray(rows), exp, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(den), den_np, rtol=1e-6)
    assert float(den[0]) == 0.0 and float(np.abs(np.asarray(rows)[0]).max()) == 0.0


@pytest.mark.parametrize("G", [1, 4, 8, 32])
def test_grouped_reduce_pallas_matches_ref(G):
    rng = np.random.default_rng(G)
    Cb, N = 32, 2100  # N not a block multiple: exercises padding
    x = jnp.asarray(rng.normal(size=(Cb, N)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.1, 1.0, Cb).astype(np.float32))
    ref_rows, ref_den = packing.grouped_weighted_mean(x, w, G, impl="ref")
    pal_rows, pal_den = packing.grouped_weighted_mean(x, w, G, impl="pallas")
    np.testing.assert_allclose(np.asarray(pal_rows), np.asarray(ref_rows), rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pal_den), np.asarray(ref_den), rtol=1e-6)


def test_client_block_widens_for_large_c():
    assert pk.client_block(8) == pk.BLOCK_C
    assert pk.client_block(64) == pk.BLOCK_C
    assert pk.client_block(256) > pk.BLOCK_C
    assert pk.client_block(1024) > pk.BLOCK_C


# ----------------- build-time validation -------------------------------------


def test_hier_validation_errors():
    with pytest.raises(ValueError, match="group_size"):
        R.make_aggregator(CFG, _fed("hier", group_size=3))  # 4 % 3 != 0
    with pytest.raises(ValueError, match="recurse"):
        R.make_aggregator(CFG, _fed("hier", group_size=2, hier_base="hier"))
    with pytest.raises(ValueError, match="stacked"):
        R.make_aggregator(CFG, _fed("hier", group_size=2, hier_base="fedsgd"))
    with pytest.raises(ValueError, match="unknown aggregation"):
        R.make_aggregator(CFG, _fed("hier", group_size=2, hier_base="nope"))


def _fake_mesh(shards):
    return types.SimpleNamespace(
        axis_names=("data", "model"), devices=np.zeros((shards, 1))
    )


def test_quant8_group_geometry_validation():
    from repro.core.aggregators.quant import Quant8
    import dataclasses as dc

    agg = R.make_aggregator(CFG, _fed("quant8"))
    # valid: C=4, G=2, 2 shards -> C % G == 0 and G % shards == 0
    Quant8(dc.replace(agg.ctx, fed=_fed("quant8", group_size=2), mesh=_fake_mesh(2)))
    # invalid: G does not divide C
    with pytest.raises(ValueError) as e:
        Quant8(dc.replace(agg.ctx, fed=_fed("quant8", group_size=3), mesh=_fake_mesh(2)))
    assert "n_clients=4" in str(e.value) and "group_size=3" in str(e.value) and "shards=2" in str(e.value)
    # invalid: shards do not divide G
    with pytest.raises(ValueError, match="group_size % shards"):
        Quant8(dc.replace(agg.ctx, fed=_fed("quant8", group_size=2), mesh=_fake_mesh(4)))
    # groupless config keeps the original C % shards check
    with pytest.raises(ValueError, match="divisible"):
        Quant8(dc.replace(agg.ctx, mesh=_fake_mesh(3)))


def test_hier_shard_local_group_validation():
    from repro.core.aggregators.hier import Hier
    import dataclasses as dc

    agg = R.make_aggregator(CFG, _fed("dense"))
    # 4 clients over 4 shards leaves 1 row/shard: group_size=2 straddles
    with pytest.raises(ValueError, match="shard-local"):
        Hier(dc.replace(agg.ctx, fed=_fed("hier", group_size=2), mesh=_fake_mesh(4)))


# ----------------- sharded == unsharded (subprocess: needs 2 devices) --------

_SHARDED_SCRIPT = r"""
import os
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_arch
from repro.core import rounds as R
from repro.core.rounds import FedConfig
from repro.optim import sgd

CFG = get_arch("qwen3-1.7b").reduced()
C = 4

def run(n_shards):
    fed = FedConfig(n_clients=C, local_steps=1, aggregation="hier",
                    group_size=2, hier_base="dense", topn=2,
                    client_axis="data", data_axis=None, state_layout="flat")
    opt = sgd(lr=0.05)
    mesh = jax.make_mesh((n_shards, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:n_shards])
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (C, 1, 2, 16)), jnp.int32)
    with jax.set_mesh(mesh):
        state = R.make_state(CFG, fed, opt, jax.random.key(0))
        fr = jax.jit(R.build_fed_round(CFG, fed, opt, mesh))
        w = jnp.asarray([0.4, 0.1, 0.3, 0.2], jnp.float32)
        for _ in range(2):
            state, m = fr(state, {"tokens": toks}, w)
    return np.asarray(jax.device_get(state["params"]), np.float64), float(m["loss"])

assert jax.device_count() == 2, jax.device_count()
p1, l1 = run(1)
p2, l2 = run(2)
scale = max(np.max(np.abs(p1)), 1e-9)
print("MAXDIFF", np.max(np.abs(p1 - p2)) / scale, "LOSSDIFF", abs(l1 - l2))
assert np.max(np.abs(p1 - p2)) / scale < 1e-6, np.max(np.abs(p1 - p2)) / scale
assert abs(l1 - l2) < 1e-6
print("SHARDED_OK")
"""


# The detector's convolutions under a sharded client axis: vmap turns each
# client's conv into one feature-grouped convolution (and its weight
# gradient into a batch-grouped one), which XLA's SPMD partitioner splits
# wrongly; local training therefore runs under shard_map when the client
# axis is sharded (rounds._train_clients_fn).
_SHARDED_DETECTOR_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_arch
from repro.core import rounds as R
from repro.core.rounds import FedConfig
from repro.data.pipeline import detection_suite
from repro.optim import sgd

CFG = get_arch("fedyolov3").reduced()
C = 4
fed = FedConfig(n_clients=C, local_steps=1, aggregation="hier", group_size=2,
                hier_base="dense", topn=2, client_axis="data", data_axis=None)
gen, _, _ = detection_suite(CFG, fed, batch=1, img_size=32, scenario="iid", pool_scenes=16)
batch = jax.tree.map(jnp.asarray, next(gen))

def run(n_shards):
    opt = sgd(lr=1e-3)
    mesh = jax.make_mesh((n_shards, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:n_shards])
    with jax.set_mesh(mesh):
        state = R.make_state(CFG, fed, opt, jax.random.key(0))
        fr = jax.jit(R.build_fed_round(CFG, fed, opt, mesh))
        w = jnp.asarray([0.4, 0.1, 0.3, 0.2], jnp.float32)
        for _ in range(2):
            state, m = fr(state, batch, w)
    return np.asarray(jax.device_get(state["params"]), np.float64), float(m["loss"])

assert jax.device_count() == 2, jax.device_count()
p1, l1 = run(1)
p2, l2 = run(2)
scale = max(np.max(np.abs(p1)), 1e-9)
print("MAXDIFF", np.max(np.abs(p1 - p2)) / scale, "LOSSDIFF", abs(l1 - l2) / abs(l1))
assert np.max(np.abs(p1 - p2)) / scale < 1e-6, np.max(np.abs(p1 - p2)) / scale
assert abs(l1 - l2) < 1e-6 * abs(l1)
print("SHARDED_OK")
"""


def _run_on_two_cpu_devices(script: str) -> None:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"), env.get("PYTHONPATH", "")]
    )
    out = subprocess.run(
        [sys.executable, "-c", script], env=env,
        capture_output=True, text=True, timeout=420,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARDED_OK" in out.stdout, out.stdout


def test_sharded_hier_matches_unsharded():
    _run_on_two_cpu_devices(_SHARDED_SCRIPT)


def test_sharded_hier_detector_matches_unsharded():
    _run_on_two_cpu_devices(_SHARDED_DETECTOR_SCRIPT)
