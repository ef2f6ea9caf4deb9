"""Operation counts from shapes against XLA's own cost analysis (CPU)."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.flops import yolo
from bench.reference import yolo as ref_yolo


def _xla_flops(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"])


def test_yolo_count_matches_xla_at_416_px():
    conf = harness.cell_files("fedyolov3-416.sync")[1]
    from repro.models import yolov3

    arch = harness.arch(conf)
    params = jax.eval_shape(lambda k: ref_yolo.init(conf, k), jax.random.key(0))
    img = jax.ShapeDtypeStruct((1, 416, 416, 3), jnp.float32)
    xla = _xla_flops(lambda p, x: yolov3.forward(p, x, arch), params, img)
    ours = yolo.forward_per_image(conf, 416)
    assert ours == pytest.approx(16.78e9, rel=0.01)
    assert ours == pytest.approx(xla, rel=0.01)



def test_the_configuration_file_states_the_sizes_that_run():
    conf = harness.cell_files("fedyolov3-416.sync")[1]
    shapes = ref_yolo.shapes(conf)
    assert [st["down"][3] for st in shapes["stages"]] == conf["stage_filters"]
    assert shapes["stem"][3] == conf["stem_filters"] and len(shapes["stages"]) == conf["stages"]
    assert [list(map(list, s)) for s in ref_yolo.ANCHORS] == conf["anchors"]
    params = jax.eval_shape(lambda k: ref_yolo.init(conf, k), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(params)) == conf["parameters"]
    assert set(conf["reduced"]) <= set(conf["published"]) and all(k in conf for k in conf["reduced"])
