"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing here runs on a chip: each test compiles for a described (not
attached) v5e, so the TPU compiler refuses here what it would refuse there
— unaligned blocks, unsupported ops — at no chip time. Interpret-mode
tests cannot see those refusals. Shapes are the real ones: the serving
batch, the eval batch, and the packed buffer of full-width `fedyolov3`.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import this
file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core import packing, serving
from repro.core import rounds as R
from repro.kernels import detect, mask, pack, quant4
from repro.models import params as mp

C = 8  # clients of the aggregation kernels
SERVE_B, SERVE_K = 8, 16  # FedConfig.serve_batch / serve_max_detections
EVAL_B, EVAL_K = 16, 64  # 4 clients x 4 eval images / evaluate_round's max_detections
IMG = 416


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mpatch:
        mpatch.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep this module's compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def yolo():
    cfg = get_arch("fedyolov3")  # full width, unreduced
    spec = packing.build_pack_spec(cfg, R.make_template(cfg))
    return cfg, spec


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; returns the compiled text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("B,N", [(SERVE_B, SERVE_K), (EVAL_B, EVAL_K)])
def test_nms_compiles(one_chip, B, N):
    text = _compile(
        lambda b, s: detect.nms(b, s, score_thresh=0.05, max_keep=N // 2, interpret=False),
        _shape(one_chip, (B, N, 4)), _shape(one_chip, (B, N)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B,N,M", [(SERVE_B, SERVE_K, 8), (EVAL_B, EVAL_K, 3), (2, 300, 130)])
def test_pairwise_iou_compiles(one_chip, B, N, M):
    text = _compile(
        lambda a, b: detect.pairwise_iou(a, b, interpret=False),
        _shape(one_chip, (B, N, 4)), _shape(one_chip, (B, M, 4)),
    )
    assert "tpu_custom_call" in text


def test_service_program_compiles_with_the_kernel(one_chip, yolo, monkeypatch):
    """The whole 416-px decode+NMS program the service runs, with the kernel
    mode the backend switch picks on a TPU (steered here: the process's
    backend is the CPU). Kernel jit caches are cleared on both sides so no
    interpreted trace leaks in, and no compiled one leaks out."""
    cfg, _ = yolo
    params = jax.tree.map(
        lambda s: _shape(one_chip, s.shape), mp.abstract(R.make_template(cfg), jnp.float32)
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    try:
        prog = serving.detection_program.__wrapped__(cfg, SERVE_K)
        text = prog.lower(params, _shape(one_chip, (SERVE_B, IMG, IMG, 3))).compile().as_text()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in text


def _agg_kernels(spec, n):
    """name -> (kernel call, [(shape, dtype) per argument]) at C x n."""
    ids = jnp.asarray(packing.bucket_ids(spec))
    tile = packing.bucket_tile_bound(spec, pack.BLOCK_N)
    f32, i8, u32 = jnp.float32, jnp.int8, jnp.uint32
    rows, per_client = ((C, n), f32), ((C,), f32)
    return {
        "packed_bucket_reduce": (
            lambda x, wm, m: pack.packed_bucket_reduce(x, wm, ids, m, bucket_tile=tile, interpret=False),
            [rows, ((C, spec.n_buckets), f32), per_client],
        ),
        "quantize_rows": (lambda x: pack.quantize_rows(x, interpret=False), [rows]),
        "dequantize_rows": (
            lambda q, s: pack.dequantize_rows(q, s, interpret=False),
            [((C, n), i8), ((C, -(-n // pack.BLOCK_N)), f32)],
        ),
        "quant8_reduce": (lambda x, w: pack.quant8_reduce(x, w, interpret=False), [rows, per_client]),
        "quant4_reduce": (
            lambda x, w, k: quant4.quant4_reduce(x, w, k, mode="stochastic", interpret=False),
            [rows, per_client, ((), u32)],
        ),
        "grouped_reduce": (
            lambda x, w: pack.grouped_reduce(x, w, interpret=False), [rows, ((C // 2, 2), f32)]
        ),
        "masked_u32_sum": (
            lambda r, m: mask.masked_u32_sum(r, m, interpret=False), [((C, n), u32), per_client]
        ),
    }


@pytest.mark.parametrize("name", [
    "packed_bucket_reduce", "quantize_rows", "dequantize_rows", "quant8_reduce",
    "quant4_reduce", "grouped_reduce", "masked_u32_sum",
])
def test_aggregation_kernel_compiles_at_fedyolov3_size(one_chip, yolo, name):
    _, spec = yolo
    fn, args = _agg_kernels(spec, spec.n_total)[name]
    assert "tpu_custom_call" in _compile(fn, *(_shape(one_chip, *a) for a in args))
