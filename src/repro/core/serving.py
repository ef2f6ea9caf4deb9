"""Production serving plane for the trained detector (DESIGN.md §17).

The paper's third leg — "model dispatch to visual serving" — as a real
inference service instead of a one-shot CLI decode:

- **Request batching into ONE jitted program.** Concurrent INFER requests
  are collected into a fixed ``FedConfig.serve_batch``-slot batch
  (zero-padded, per-request valid slots), and every batch runs the same
  cached jitted decode+NMS program (`detection.decode_predictions`) — the
  packed-buffer discipline applied to the serving axis: fixed shapes, no
  retrace, padding carried by masks. Per-slot decode is a function of that
  slot alone (per-image NMS class-shift stride), so a request's detections
  are bit-identical at any batch occupancy — the padding pin
  tests/test_serving.py holds the service to.

- **Round-versioned hot model swap.** A `ModelSlot` atomically publishes
  ``(round_version, params, published_t)``; training publishes off the
  async engine's *landed* global (`publish_from_engine` reads
  ``engine.global_packed_row()`` — the engine's own global copy, never a
  mid-window in-flight buffer row) as flushes land, and the batcher takes
  one slot snapshot per batch, so a swap is just "the next batch serves
  the new version": no lock spans a jit call, no request is ever dropped
  by a swap, and every RESULT carries the version it was served from.

- **Freshness tiers.** fresh / soft_stale (warning) / hard_stale
  (degraded), computed by ONE evaluator (:func:`freshness_tier`) from
  rounds-behind and wall-seconds-behind thresholds in `FedConfig`. The
  service's STATUS frame and `monitor.render_serving` both call
  :func:`model_status` — one function, two callers, no drift.

The wire is the federation transport's own framing (`transport/wire.py`
CRC'd frames) with the INFER/RESULT/STATUS types; `InferenceClient` is the
consumer half. `benchmarks/serve_bench.py` measures served QPS and
p50/p99 latency across batch occupancies and pins zero dropped requests
across a hot swap under load.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import queue
import socket
import threading
import time
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import detection
from repro.core import rounds as R
from repro.core import telemetry
from repro.core.transport import wire

PyTree = Any

# -- freshness tiers (the Anti-Coin-style status model) ----------------------

FRESH, SOFT_STALE, HARD_STALE = "fresh", "soft_stale", "hard_stale"
TIER_CODES = {FRESH: 0, SOFT_STALE: 1, HARD_STALE: 2}
TIER_NAMES = {v: k for k, v in TIER_CODES.items()}


def freshness_tier(rounds_behind: int, seconds_behind: float, fed: R.FedConfig) -> str:
    """THE status evaluator — the serving path (STATUS frame) and
    `monitor.render_serving` both call this one function, so the wire's
    health report and the dashboard can never disagree.

    A model is ``soft_stale`` (serve, but warn) once it is strictly more
    than ``serve_soft_stale_rounds`` landed rounds OR
    ``serve_soft_stale_s`` wall seconds behind; ``hard_stale`` (degraded:
    still served, loudly flagged) past the hard thresholds. Exactly-at-
    threshold is the lower tier — `tests/test_serving.py` pins the
    boundaries."""
    if (rounds_behind > fed.serve_hard_stale_rounds
            or seconds_behind > fed.serve_hard_stale_s):
        return HARD_STALE
    if (rounds_behind > fed.serve_soft_stale_rounds
            or seconds_behind > fed.serve_soft_stale_s):
        return SOFT_STALE
    return FRESH


def model_status(slot: "ModelSlot", latest_version: int, now: float,
                 fed: R.FedConfig, stats: "ServeStats | None" = None) -> dict:
    """The serving health report: version lineage + freshness tier (+ the
    service's operational counters when given). JSON-able — this dict IS
    the STATUS frame payload and the monitor's input."""
    pub = slot.snapshot()
    rounds_behind = max(0, int(latest_version) - pub.version)
    seconds_behind = max(0.0, float(now) - pub.published_t)
    tier = freshness_tier(rounds_behind, seconds_behind, fed)
    out = {
        "version": pub.version,
        "latest_version": int(latest_version),
        "rounds_behind": rounds_behind,
        "seconds_behind": seconds_behind,
        "tier": tier,
        "degraded": tier == HARD_STALE,
        "swaps": slot.swaps,
    }
    if stats is not None:
        out.update(stats.as_dict())
    return out


# -- the hot-swap slot -------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PublishedModel:
    """One atomic publication: the model, the landed round version it came
    from, and when it was published (the freshness clock's anchor)."""

    version: int
    params: PyTree
    published_t: float


class ModelSlot:
    """Atomic publish/snapshot of ``(round_version, params)``.

    Training and serving share one live state through this slot: the
    training side calls :meth:`publish` as rounds land, the batcher calls
    :meth:`snapshot` once per batch. Publish is version-monotonic — a
    publisher racing an already-landed newer round is refused (returns
    False, counted in ``stale_publishes``) so the served model can never
    move backwards.

    ``clock`` is anything with ``.now()`` (a `SimClock` in tests — the
    controlled freshness transitions); None means host monotonic time.
    """

    def __init__(self, clock=None):
        self._lock = threading.Lock()
        self._clock = clock
        self._published: PublishedModel | None = None
        self.swaps = 0  # successful publishes (the first one included)
        self.stale_publishes = 0  # refused version regressions

    def now(self) -> float:
        return self._clock.now() if self._clock is not None else time.monotonic()

    def publish(self, version: int, params: PyTree, t: float | None = None) -> bool:
        pub = PublishedModel(int(version), params,
                             self.now() if t is None else float(t))
        with self._lock:
            if self._published is not None and pub.version < self._published.version:
                self.stale_publishes += 1
                return False
            self._published = pub
            self.swaps += 1
        return True

    def snapshot(self) -> PublishedModel:
        with self._lock:
            if self._published is None:
                raise RuntimeError("ModelSlot is empty: nothing published yet")
            return self._published

    @property
    def empty(self) -> bool:
        with self._lock:
            return self._published is None


def unpack_global(cfg, fed: R.FedConfig, row) -> PyTree:
    """(N_total,) packed global row -> param pytree (one pack/unpack edge —
    the same edge `server.global_params` crosses)."""
    params = R.unpacked_params(cfg, fed, {"params": jnp.asarray(row)[None]})
    return jax.tree.map(lambda x: x[0], params)


def publish_from_engine(slot: ModelSlot, engine, cfg, *, t: float | None = None) -> bool:
    """Publish the engine's landed global at its landed round version.

    Reads ``engine.global_packed_row()`` — each engine's own notion of
    "the current global" (the arrival engine keeps an explicit snapshot
    because its buffer rows mutate on every landing) — NEVER a row indexed
    out of ``state["params"]``, which mid-window may hold a client's next
    trained update. This is what makes the served version equal the
    engine's landed round version by construction."""
    return slot.publish(
        engine.version, unpack_global(cfg, engine.fed, engine.global_packed_row()), t=t
    )


# -- the jitted program cache ------------------------------------------------

@functools.lru_cache(maxsize=16)
def detection_program(cfg, max_detections: int) -> Callable:
    """One cached jitted decode+NMS callable per (cfg, max_detections) —
    every batch the service runs goes through this program (jit re-traces
    per batch shape internally and caches; the wrapper itself is built
    once, the `launch/serve.py::generate` retrace fix applied here too)."""

    @jax.jit
    def program(params, images):
        return detection.decode_predictions(
            cfg, params, images, max_detections=max_detections
        )

    return program


@jax.jit
def stack_frames(*frames):
    """One ``(len(frames), H, W, 3)`` batch from frames already on the device."""
    return jnp.stack(frames)


def decode_result(pred: dict, i: int) -> list[tuple[int, float, tuple]]:
    """Slot ``i`` of a program output -> the RESULT frame's detection list
    (kept slots only, score order preserved)."""
    valid = np.asarray(pred["valid"][i])
    cls = np.asarray(pred["cls"][i])
    scores = np.asarray(pred["scores"][i])
    boxes = np.asarray(pred["boxes"][i])
    return [
        (int(cls[k]), float(scores[k]), tuple(float(v) for v in boxes[k]))
        for k in np.nonzero(valid)[0]
    ]


# -- the service -------------------------------------------------------------

# Frames a service keeps staged on the device ahead of its batches, in
# batches of ``serve_batch``: at 8 slots of 416 px f32, 32 frames (82 MB in
# the v5e's tiled layout, which pads each 416-wide row to 512).
# It bounds the device memory a backlog can take; a frame past it waits on
# the host and crosses when its batch closes.
STAGE_AHEAD_BATCHES = 4


@dataclasses.dataclass(slots=True)
class _Pending:
    """An accepted INFER waiting for the batcher: the reply's socket and
    wire id, the frame (a device array once staged, else the host array;
    None once its batch took it), the service's request id and
    ``serve.request`` span number, and when its first byte arrived and when
    it was enqueued."""

    sock: socket.socket
    rid: int
    frame: jax.Array | np.ndarray | None
    sid: int
    seq: int
    first_ns: int
    enq_ns: int


@dataclasses.dataclass
class ServeStats:
    """Operational counters (rendered by `monitor.render_serving`)."""

    requests: int = 0  # INFER frames accepted into the batcher
    results: int = 0  # RESULT frames sent
    batches: int = 0  # jitted program launches
    occupancy_sum: int = 0  # real (non-padding) slots across launches
    status_requests: int = 0
    protocol_errors: int = 0  # malformed INFER payloads (connection dropped)
    crc_errors: int = 0
    failed: int = 0  # requests answered with an ERROR frame (batch raised)

    @property
    def in_flight(self) -> int:
        """Requests accepted but not yet answered; 0 once the service is
        quiescent — the hot-swap bench's zero-dropped-requests check."""
        return self.requests - self.results - self.failed

    @property
    def avg_occupancy(self) -> float:
        return self.occupancy_sum / self.batches if self.batches else 0.0

    def as_dict(self) -> dict:
        return {
            "requests": self.requests,
            "results": self.results,
            "batches": self.batches,
            "avg_occupancy": round(self.avg_occupancy, 3),
            "in_flight": self.in_flight,
            "status_requests": self.status_requests,
            "protocol_errors": self.protocol_errors,
            "failed": self.failed,
        }


class InferenceService:
    """Socket-served batched detection over the wire framing.

    Reader threads parse INFER frames, put each on the device (while fewer
    than ``STAGE_AHEAD_BATCHES`` batches' worth wait there) and enqueue it
    with the service's own request id; ONE batcher thread (the only jit
    caller) collects up to ``fed.serve_batch`` requests per launch — the
    first request opens the batch, then the batcher lingers
    ``fed.serve_max_wait_s`` for the rest of the slots — stacks their
    frames on the device with one shared zero frame in each empty slot,
    snapshots the `ModelSlot` once, runs the cached program, and answers
    each request with its slot's detections + the snapshot's round version
    + the freshness tier.
    STATUS frames are answered from the reader (they never touch the jit)
    through the same :func:`model_status` evaluator the monitor uses.
    Every request and batch leaves its ``serve.*`` spans in `telemetry`'s
    ring, under the request id and a batch id.

    A batch or a frame's staging that raises (a program the compiler
    refused, say) fails loudly: the first exception is kept in ``error``,
    that batch's or request's and every later request is answered with an
    ERROR frame carrying its message (the client raises it), and
    :meth:`stop` re-raises it.

    ``latest_version``: callable returning the newest landed training
    round (e.g. ``lambda: engine.version``) — what rounds-behind is
    measured against. None means the slot's own version (a serve-only
    restore: rounds_behind 0, freshness then decays on wall time alone).
    """

    def __init__(self, cfg, fed: R.FedConfig, slot: ModelSlot, *,
                 img_size: int, host: str = "127.0.0.1", port: int = 0,
                 latest_version: Callable[[], int] | None = None,
                 max_detections: int = 0):
        if fed.serve_batch < 1:
            raise ValueError(f"serve_batch={fed.serve_batch} must be >= 1")
        self.cfg, self.fed, self.slot = cfg, fed, slot
        self.img_size = int(img_size)
        self.batch = fed.serve_batch
        self.max_wait_s = fed.serve_max_wait_s
        self.max_detections = int(max_detections) or fed.serve_max_detections
        self._latest_version = latest_version
        self._program = detection_program(cfg, self.max_detections)
        self._zero = jnp.zeros((self.img_size, self.img_size, 3), jnp.float32)  # every empty slot
        self._staged = 0  # frames on the device that no batch has taken yet
        self.stats = ServeStats()
        self._stats_lock = threading.Lock()
        self._q: queue.Queue = queue.Queue()
        self._request_ids = itertools.count()  # the service's own request ids (the ring's)
        self._batch_ids = itertools.count()
        self._send_locks: dict[int, threading.Lock] = {}
        self._stopping = threading.Event()
        self.error: Exception | None = None  # first batch or staging failure, re-raised by stop()
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(64)
        self.host, self.port = self._listener.getsockname()[:2]
        self._threads: list[threading.Thread] = []

    # -- status (the one evaluator, called here and by the monitor) ----------

    def latest_version(self) -> int:
        if self._latest_version is not None:
            return int(self._latest_version())
        return self.slot.snapshot().version

    def status(self) -> dict:
        return model_status(
            self.slot, self.latest_version(), self.slot.now(), self.fed, self.stats
        )

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "InferenceService":
        if self.slot.empty:
            raise RuntimeError("publish a model into the ModelSlot before start()")
        accept = threading.Thread(target=self._accept_loop, name="serve-accept",
                                  daemon=True)
        batcher = threading.Thread(target=self._batch_loop, name="serve-batcher",
                                   daemon=True)
        self._threads = [accept, batcher]
        accept.start()
        batcher.start()
        return self

    def stop(self) -> None:
        self._stopping.set()
        try:
            self._listener.close()
        except OSError:
            pass
        for t in self._threads:
            t.join(timeout=5.0)
        if self.error is not None:
            raise self.error

    # -- reader side ---------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stopping.is_set():
            try:
                sock, _ = self._listener.accept()
            except OSError:
                return
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._send_locks[id(sock)] = threading.Lock()
            threading.Thread(target=self._reader, args=(sock,),
                             name="serve-reader", daemon=True).start()

    def _send(self, sock: socket.socket, frame: bytes) -> None:
        lock = self._send_locks.get(id(sock))
        try:
            if lock is None:
                sock.sendall(frame)
            else:
                with lock:
                    sock.sendall(frame)
        except OSError:
            pass  # consumer gone mid-send; its requests die with the socket

    def _reader(self, sock: socket.socket) -> None:
        parser = wire.FrameParser()
        frame_ns = 0  # when the first byte of the frame in progress arrived
        while not self._stopping.is_set():
            try:
                data = sock.recv(1 << 16)
            except OSError:
                break
            if not data:
                break
            got_ns = telemetry.now_ns()
            if not parser.pending:
                frame_ns = got_ns
            try:
                frames = parser.feed(data)
            except ValueError:
                break  # structurally corrupt stream: drop the connection
            if parser.crc_errors:
                with self._stats_lock:
                    self.stats.crc_errors += parser.crc_errors
                break  # poisoned stream (same discipline as the WireServer)
            parse_ns = got_ns  # serve.parse of the first frame covers the feed's CRC and copies
            for ftype, payload in frames:
                if ftype == wire.INFER:
                    try:
                        rid, img = wire.parse_infer(payload)
                    except ValueError:
                        with self._stats_lock:
                            self.stats.protocol_errors += 1
                        sock.close()
                        return
                    if img.shape[:2] != (self.img_size, self.img_size):
                        # shape negotiation happens via STATUS; a wrong-size
                        # image is a protocol error, not a resize request
                        with self._stats_lock:
                            self.stats.protocol_errors += 1
                        sock.close()
                        return
                    with self._stats_lock:
                        self.stats.requests += 1
                    sid, req_seq, recv_seq = next(self._request_ids), telemetry.reserve(), telemetry.reserve()
                    stage_ns = telemetry.now_ns()
                    telemetry.add("serve.parse", parse_ns, stage_ns, id=sid, parent=recv_seq)
                    try:
                        img = self._stage(img)  # rebinding lets go of the host frame
                    except Exception as e:  # noqa: BLE001 — surfaced, never swallowed
                        self._fail(e, [(sock, rid)])
                    else:
                        enq_ns = telemetry.now_ns()
                        if isinstance(img, jax.Array):
                            telemetry.add("serve.stage", stage_ns, enq_ns, id=sid, parent=recv_seq)
                        self._q.put(_Pending(sock, rid, img, sid, req_seq, frame_ns, enq_ns))
                    parse_ns = telemetry.now_ns()
                    telemetry.add("serve.recv", frame_ns, parse_ns, id=sid, parent=req_seq, seq=recv_seq)
                elif ftype == wire.STATUS:
                    with self._stats_lock:
                        self.stats.status_requests += 1
                    self._send(sock, wire.pack_status(self.status()))
                # anything else on a serving socket is ignored (the federation
                # frame types belong to the WireServer's port)
                frame_ns = got_ns  # a later frame's first byte came in this chunk at the earliest

    def _stage(self, img: np.ndarray) -> jax.Array | np.ndarray:
        """Start ``img``'s copy to the device, unless ``STAGE_AHEAD_BATCHES``
        batches' worth of frames already wait there: then the host frame."""
        with self._stats_lock:
            if self._staged >= STAGE_AHEAD_BATCHES * self.batch:
                return img
            self._staged += 1
        try:
            return jax.device_put(img)
        except BaseException:
            with self._stats_lock:
                self._staged -= 1
            raise

    def _take_frames(self, items: list) -> list:
        """The batch's frames, which its requests then no longer hold."""
        frames = [it.frame for it in items]
        for it in items:
            it.frame = None
        with self._stats_lock:
            self._staged -= sum(isinstance(f, jax.Array) for f in frames)
        return frames

    # -- batcher (the only jit caller) ---------------------------------------

    def _batch_loop(self) -> None:
        while not self._stopping.is_set():
            try:
                first = self._q.get(timeout=0.05)
            except queue.Empty:
                continue
            with telemetry.span("serve.batch", id=next(self._batch_ids)) as batch:
                with telemetry.span("serve.linger", id=batch.id):
                    items = [first]
                    deadline = time.monotonic() + self.max_wait_s
                    while len(items) < self.batch:
                        left = deadline - time.monotonic()
                        if left <= 0:
                            break
                        try:
                            items.append(self._q.get(timeout=left))
                        except queue.Empty:
                            break
                batch.ids = tuple(it.sid for it in items)
                closed_ns = telemetry.now_ns()
                for it in items:
                    telemetry.add("serve.queue", it.enq_ns, closed_ns, id=it.sid, parent=it.seq)
                ready = tuple(it.sid for it in items if isinstance(it.frame, jax.Array) and it.frame.is_ready())
                telemetry.add("serve.staged", closed_ns, closed_ns, id=batch.id, parent=batch.seq, ids=ready)
                telemetry.count("serve.frames", len(items))
                telemetry.count("serve.frames_ready", len(ready))
                error = self.error
                if error is None:
                    try:
                        self._run_batch(items, batch.id)
                        continue
                    except Exception as e:  # noqa: BLE001 — surfaced, never swallowed
                        error = e
                self._take_frames(items)
                self._fail(error, [(it.sock, it.rid) for it in items])

    def _fail(self, error: Exception | None, requests: list) -> None:
        """Keep ``error`` if it is the service's first, then answer each
        ``(socket, wire id)`` of ``requests`` with the service's error."""
        with self._stats_lock:
            if self.error is None:
                self.error = error
            self.stats.failed += len(requests)
        msg = f"{type(self.error).__name__}: {self.error}"
        for sock, rid in requests:
            self._send(sock, wire.pack_error(rid, msg))

    def _run_batch(self, items: list, bid: int) -> None:
        # ONE slot snapshot per batch: the whole batch — and every RESULT in
        # it — is served from a single (version, params) pair; a concurrent
        # publish simply lands in the next batch. This is the entire
        # hot-swap protocol: no lock spans the jit, no request can drop.
        with telemetry.span("serve.pad", id=bid):
            pub = self.slot.snapshot()
            frames = self._take_frames(items)
            frames += [self._zero] * (self.batch - len(frames))
        with telemetry.span("serve.h2d", id=bid):
            # a frame that came past the stage-ahead bound crosses now
            imgs = stack_frames(*(f if isinstance(f, jax.Array) else jax.device_put(f) for f in frames))
            del frames
        with telemetry.span("serve.dispatch", id=bid):
            pred = self._program(pub.params, imgs)
        with telemetry.span("serve.device_wait", id=bid):
            pred = jax.tree.map(np.asarray, pred)
        with telemetry.span("serve.decode", id=bid):
            tier = freshness_tier(
                max(0, self.latest_version() - pub.version),
                max(0.0, self.slot.now() - pub.published_t),
                self.fed,
            )
            frames = [wire.pack_result(it.rid, pub.version, TIER_CODES[tier], decode_result(pred, i))
                      for i, it in enumerate(items)]
        # Count the results BEFORE sending them: a client that has received
        # its RESULT must never observe in_flight > 0 for that request, so
        # the quiesce check (in_flight == 0 once every response arrived) is
        # race-free for any outside observer.
        with self._stats_lock:
            self.stats.batches += 1
            self.stats.occupancy_sum += len(items)
            self.stats.results += len(items)
        with telemetry.span("serve.send", id=bid):
            for it, frame in zip(items, frames):
                self._send(it.sock, frame)
                telemetry.add("serve.request", it.first_ns, telemetry.now_ns(), id=it.sid, seq=it.seq)


# -- the consumer half -------------------------------------------------------

class ServiceError(RuntimeError):
    """The service failed the request; the message is the server's own
    exception (type and text), e.g. the compiler's refusal."""


@dataclasses.dataclass
class ServeResult:
    """One RESULT frame, decoded."""

    request_id: int
    version: int  # the landed training round the model was published from
    tier: str  # freshness tier the server evaluated at serve time
    detections: list  # [(label, score, (x, y, w, h)), ...] score-descending


class InferenceClient:
    """One consumer connection: framed INFER/STATUS out, RESULT/STATUS in.

    `infer` is the blocking request/response form; `send_infer` +
    `recv_result` pipeline many requests over one connection (match
    responses by ``request_id`` — the batcher preserves per-connection
    order, but don't lean on it)."""

    def __init__(self, host: str, port: int, timeout: float = 60.0):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._parser = wire.FrameParser()
        self._frames: list = []
        self._next_id = 0

    def _recv_frame(self):
        while not self._frames:
            data = self.sock.recv(1 << 16)
            if not data:
                raise ConnectionError("serving connection closed")
            self._frames.extend(self._parser.feed(data))
            if self._parser.crc_errors:
                raise ConnectionError("serving stream CRC-poisoned")
        return self._frames.pop(0)

    def send_infer(self, image) -> int:
        rid = self._next_id
        self._next_id += 1
        self.sock.sendall(wire.pack_infer(rid, image))
        return rid

    def recv_result(self) -> ServeResult:
        while True:
            ftype, payload = self._recv_frame()
            if ftype == wire.RESULT:
                rid, version, tier_code, dets = wire.parse_result(payload)
                return ServeResult(rid, version, TIER_NAMES[tier_code], dets)
            if ftype == wire.ERROR:
                rid, msg = wire.parse_error(payload)
                raise ServiceError(f"request {rid}: {msg}")

    def infer(self, image) -> ServeResult:
        rid = self.send_infer(image)
        res = self.recv_result()
        if res.request_id != rid:
            raise ConnectionError(
                f"response {res.request_id} does not match request {rid}"
            )
        return res

    def status(self) -> dict:
        self.sock.sendall(wire.pack_status_request())
        while True:
            ftype, payload = self._recv_frame()
            if ftype == wire.STATUS:
                return wire.parse_status(payload)

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass

    def __enter__(self) -> "InferenceClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
