"""The in-process span and counter recorder (`core/telemetry.py`) and the
spans the service and the round leave in it.

Pins:
  - the ring's bound and order, span nesting, self time as a span less its
    children, the window helpers the benchmark's readers use;
  - one 3-request batch over the socket: each request's spans share its id
    and run recv (parse, then stage) -> queue -> the batch's children in
    order (its ``serve.staged`` mark at the close) -> its send;
  - `FLServer.run_round`'s ``fl.round`` with its children (``fl.device_wait``
    among them) and `RoundRecord.seconds` taken from it;
  - the compiled round carrying the four scope names in ``op_name``, in
    every participation branch;
  - a forced retrace recording one ``jax.compile`` span and counter step;
  - a span moved by `profiler_offset_ns` landing on its TraceAnnotation in
    a CPU profiler trace.

Every test runs under a SIGALRM deadline.
"""
import glob
import os
import re
import signal

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.core import rounds as R
from repro.core import serving, telemetry
from repro.data import synthetic
from repro.models import params as P
from repro.models import yolov3

IMG = 32
CFG = get_arch("fedyolov3").reduced()
SCOPES = ("forward_backward", "optimizer", "write_slots", "aggregate")
DEADLINE_S = 240


@pytest.fixture(autouse=True)
def deadline():
    def expire(signum, frame):
        raise TimeoutError(f"test exceeded {DEADLINE_S} s")

    old = signal.signal(signal.SIGALRM, expire)
    signal.alarm(DEADLINE_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


# --------------------------- the recorder ------------------------------------

def test_ring_is_bounded_and_keeps_the_newest_in_order():
    telemetry.reset()
    n = telemetry.RING_SPANS + 10
    for i in range(n):
        telemetry.add("t.fill", i, i + 1, id=i)
    ring = telemetry.spans("t.fill")
    assert len(ring) == telemetry.RING_SPANS
    assert [s.id for s in ring] == list(range(10, n))  # the oldest dropped
    assert [s.seq for s in ring] == sorted(s.seq for s in ring)
    assert [s.id for s in telemetry.last("t.fill", 3)] == [n - 3, n - 2, n - 1]
    assert telemetry.last("t.fill", n) is None
    assert telemetry.last("t.none", 1) is None and telemetry.last("t.fill", 0) is None


def test_nested_spans_and_self_time():
    telemetry.reset()
    with telemetry.span("t.outer", id=7) as outer:
        with telemetry.span("t.a", id=7):
            pass
        with telemetry.span("t.b", id=7) as b:
            with telemetry.span("t.inner"):
                pass
        late = telemetry.reserve()
        telemetry.add("t.c", b.end_ns, telemetry.now_ns(), parent=outer.seq, seq=late)
    (top,) = telemetry.spans("t.outer")
    kids = telemetry.children()
    assert [c.name for c in kids[top.seq]] == ["t.a", "t.b", "t.c"]
    assert top.parent is None and top.id == 7
    (bspan,) = telemetry.spans("t.b")
    assert [c.name for c in kids[bspan.seq]] == ["t.inner"]  # grandchildren stay with their parent
    assert telemetry.spans("t.c")[0].seq == late
    assert telemetry.self_ns(top, kids) == top.ns - sum(c.ns for c in kids[top.seq])
    assert telemetry.self_ns(top, kids, {"t.b"}) == top.ns - bspan.ns
    assert all(s.ns >= 0 for s in telemetry.spans())
    assert telemetry.median_ms([1_000_000, 3_000_000, 2_000_000]) == 2.0
    assert telemetry.median_ms([]) is None


def test_counters():
    telemetry.reset()
    telemetry.count("t.x")
    telemetry.count("t.x", 4)
    assert telemetry.counters()["t.x"] == 5


def test_retrace_records_one_compile_span():
    @jax.jit
    def retraced(x):
        return x * 2 + 1

    x3, x5 = jnp.ones(3), jnp.ones(5)
    retraced(x3).block_until_ready()
    before = telemetry.counters().get("jax.compiles", 0)
    n_spans = len(telemetry.spans("jax.compile"))
    with telemetry.span("t.window") as window:
        retraced(x5).block_until_ready()  # a new shape: one more compile
        retraced(x5).block_until_ready()  # cached: none
    assert telemetry.counters()["jax.compiles"] == before + 1
    compiles = telemetry.spans("jax.compile")
    assert len(compiles) == n_spans + 1
    assert "retraced" in compiles[-1].id and compiles[-1].parent == window.seq
    assert compiles[-1].ns > 0 and telemetry.counters()["jax.compile_ns"] >= compiles[-1].ns


def test_profiler_offset_puts_a_span_on_its_annotation(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i in range(3):
            with telemetry.span("t.probe", id=i):
                pass
    finally:
        jax.profiler.stop_trace()
    offset = telemetry.profiler_offset_ns()
    data = jax.profiler.ProfileData.from_file(
        sorted(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True))[-1])
    start = dict(next(p for p in data.planes if p.name == "Task Environment").stats)["profile_start_time"]
    traced = sorted(e.start_ns for p in data.planes if p.name.startswith("/host:")
                    for line in p.lines for e in line.events if e.name == "t.probe")
    ring = [s.start_ns + offset - start for s in telemetry.spans("t.probe")[-3:]]
    assert len(traced) == 3
    assert max(abs(a - b) for a, b in zip(ring, traced)) < 100_000  # 100 us


# --------------------------- the service -------------------------------------

def test_one_batch_spans_share_request_ids_in_order():
    fed = R.FedConfig(n_clients=1, serve_batch=3, serve_max_wait_s=2.0)
    slot = serving.ModelSlot()
    slot.publish(1, P.init_params(yolov3.template(CFG), jax.random.key(0), jnp.float32))
    svc = serving.InferenceService(CFG, fed, slot, img_size=IMG).start()
    imgs, _ = synthetic.scene_images(np.random.default_rng(3), 3, IMG, 3)
    telemetry.reset()
    try:
        with serving.InferenceClient(svc.host, svc.port, timeout=120.0) as client:
            for i in range(3):  # requests 0-2 compile, outside the batch under test
                client.send_infer(imgs[i])
            for _ in range(3):
                client.recv_result()
            for i in range(3):  # requests 3-5
                client.send_infer(imgs[i])
            got = [client.recv_result() for _ in range(3)]
        assert len(got) == 3
    finally:
        svc.stop()  # joins the batcher: every batch span is in the ring
    (batch,) = [s for s in telemetry.spans("serve.batch") if set(s.ids) & {3, 4, 5}]
    assert batch.ids == (3, 4, 5)
    kids = telemetry.children()
    phases = [c.name for c in kids[batch.seq]]
    assert phases == ["serve.linger", "serve.staged", "serve.pad", "serve.h2d", "serve.dispatch",
                      "serve.device_wait", "serve.decode", "serve.send"]
    steps = kids[batch.seq]
    assert all(a.end_ns <= b.start_ns for a, b in zip(steps, steps[1:]))
    linger, staged, send = steps[0], steps[1], steps[-1]
    assert staged.id == batch.id and staged.ns == 0 and set(staged.ids) <= set(batch.ids)
    by = lambda name: {s.id: s for s in telemetry.spans(name) if s.id in batch.ids}
    recv, parse, stage, queue, request = (by(n) for n in ("serve.recv", "serve.parse", "serve.stage",
                                                          "serve.queue", "serve.request"))
    for sid in batch.ids:
        r, st, q, req = recv[sid], stage[sid], queue[sid], request[sid]
        assert r.parent == req.seq and q.parent == req.seq and parse[sid].parent == r.seq and st.parent == r.seq
        assert req.start_ns == r.start_ns <= parse[sid].start_ns <= parse[sid].end_ns <= r.end_ns
        assert parse[sid].end_ns <= st.start_ns <= st.end_ns <= q.start_ns
        assert r.start_ns <= q.start_ns <= q.end_ns <= steps[1].start_ns
        assert q.end_ns == staged.start_ns
        assert q.end_ns >= linger.start_ns
        assert send.start_ns <= req.end_ns <= send.end_ns
        assert all(s.ns >= 0 for s in (r, parse[sid], st, q, req))


# --------------------------- the round ---------------------------------------

def _round_inputs(fed):
    from repro.data.pipeline import fed_batches

    return jax.tree.map(jnp.asarray, next(fed_batches(CFG, fed, batch=2, seq=0, img_size=IMG)))


def test_round_span_holds_its_phases_and_sets_seconds(tmp_path):
    from repro.checkpoint import ObjectStore
    from repro.core import monitor
    from repro.core.server import FLServer
    from repro.optim import sgd

    fed = R.FedConfig(n_clients=2, local_steps=1, aggregation="eq6", topn=3, client_axis="data",
                      data_axis=None)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        server = FLServer(CFG, fed, sgd(lr=1e-3), mesh=mesh, store=ObjectStore(tmp_path),
                          checkpoint_every=2)
        batch = _round_inputs(fed)
        recs = [server.run_round(batch) for _ in range(3)]
    rounds = telemetry.spans("fl.round")[-3:]
    assert [s.id for s in rounds] == [0, 1, 2]
    kids = telemetry.children()
    for rnd, rec in zip(rounds, recs):
        phases = kids[rnd.seq]
        names = [c.name for c in phases]
        expect = ["fl.schedule", "fl.dispatch", "fl.device_wait", "fl.report"]
        assert names == expect + (["fl.checkpoint"] if rec.round_idx % 2 == 0 else [])
        assert all(c.id == rnd.id and rnd.start_ns <= c.start_ns <= c.end_ns <= rnd.end_ns for c in phases)
        assert all(a.end_ns <= b.start_ns for a, b in zip(phases, phases[1:]))
        assert rec.seconds == rnd.ns / 1e9 > 0
        assert 0 < telemetry.self_ns(rnd, kids, {"fl.device_wait"}) < rnd.ns
    assert f"round wall {recs[-1].seconds:.2f}s" in monitor.render_task("t", server.history, fed.n_clients)


@pytest.mark.parametrize("participation,k", [("full", 0), ("masked", 0), ("compact", 1), ("compact", 2)])
def test_compiled_round_carries_the_scope_names(participation, k):
    from repro.optim import adamw

    fed = R.FedConfig(n_clients=2, local_steps=1, aggregation="eq6", topn=3, client_axis="data",
                      data_axis=None, participation=participation, max_participants=k)
    opt = adamw(lr=1e-4)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        state = R.make_state(CFG, fed, opt, jax.random.key(0))
        mask, w = np.ones(2, np.float32), np.full(2, 0.5, np.float32)
        part = R.participation_input(fed, mask, w, np.arange(k) if participation == "compact" else None)
        text = jax.jit(R.build_fed_round(CFG, fed, opt, mesh)).lower(
            state, _round_inputs(fed), part).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
