"""Open-loop detection traffic: a fixed arrival schedule, a load generator
that runs in a child process of its own, and the latency arithmetic.

The schedule is the same set of ``round(rate * seconds)`` Poisson
inter-arrival gaps for every seed (the exponential's quantiles), in an order
drawn from the seed, scaled to span the window exactly; each request goes to
one of ``connections`` camera connections and carries one image of a seeded
pool. Each camera has a sender of its own that sends its requests when they
are due, whether or not earlier ones were answered (a camera blocked by the
service's socket does not hold the others back), and every request is timed
from when it was due to when its RESULT arrived. A request answered with an ERROR frame, or not
answered before the drain ends, has no latency: it counts as infinite.

The child process speaks the service's own wire framing: it loads the
program's ``repro/core/transport/wire.py`` by its path, since that module
imports no JAX and its package does. It never imports JAX.
"""
from __future__ import annotations

import collections
import importlib.util
import math
import socket
import threading
import time
import zlib
from pathlib import Path

import numpy as np


def _load_wire():
    path = Path(__file__).resolve().parents[2] / "src" / "repro" / "core" / "transport" / "wire.py"
    spec = importlib.util.spec_from_file_location("bench_traffic_wire", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


wire = _load_wire()


# -- schedule and latency arithmetic -------------------------------------------

def schedule(rate: float, seconds: float, connections: int, pool: int, seed: int) -> dict:
    """Due offsets (s, ascending, within [0, seconds)), connection and image
    index of every request of the window."""
    n = max(1, round(rate * seconds))
    rng = np.random.default_rng(seed)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate  # exponential quantiles
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due *= seconds / (due[-1] + gaps[-1])
    return {"due": due, "conn": rng.integers(0, connections, n), "image": rng.integers(0, pool, n)}


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (failed requests) sort last."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.inf
    return float(v[min(len(v) - 1, max(0, math.ceil(q / 100.0 * len(v)) - 1))])


def latencies(due: np.ndarray, recv: np.ndarray, ok: np.ndarray) -> np.ndarray:
    """Seconds from due to RESULT; inf where no RESULT came."""
    return np.where(ok & np.isfinite(recv), recv - due, math.inf)


# -- the wire, client side -----------------------------------------------------

def infer_frame(rid: int, image_bytes: bytes, size: int) -> list[bytes]:
    """``wire.pack_infer(rid, image)`` as buffers, the image not copied."""
    head = bytes([wire.INFER]) + wire._INFER.pack(rid, size, size)
    crc = zlib.crc32(image_bytes, zlib.crc32(head))
    return [wire._LEN.pack(len(head) + len(image_bytes)) + wire._CRC.pack(crc), head, image_bytes]


def send_all(sock: socket.socket, bufs: list[bytes]) -> None:
    """sendmsg until every byte of ``bufs`` is on the socket."""
    views = [memoryview(b) for b in bufs]
    while views:
        n = sock.sendmsg(views)
        while views and n >= len(views[0]):
            n -= len(views[0])
            views.pop(0)
        if views and n:
            views[0] = views[0][n:]


class Reader:
    """The frames of one socket, in order, through the wire's own parser."""

    def __init__(self, sock: socket.socket):
        self.sock, self.parser, self.frames = sock, wire.FrameParser(), collections.deque()

    def next(self) -> tuple[int, bytes]:
        while not self.frames:
            data = self.sock.recv(1 << 16)
            if not data:
                raise ConnectionError("serving connection closed")
            self.frames.extend(self.parser.feed(data))
        return self.frames.popleft()


# -- the generator (child process) ---------------------------------------------

def generator(pipe, host: str, port: int, sched: dict, connections: int, pool_seed: int, pool_n: int,
              size: int, n_classes: int, warmup: int, drain_s: float) -> None:
    """Child process: warm the service, wait for the window's start time, send
    the schedule open-loop, collect every answer, send the record back."""
    from bench.traffic import scenes

    t_start = time.monotonic()
    images, _ = scenes.scenes(np.random.default_rng(pool_seed), pool_n, size, n_classes)
    blobs = [np.ascontiguousarray(im, "<f4").tobytes() for im in images]
    socks = [socket.create_connection((host, port), timeout=900.0) for _ in range(connections)]
    for s in socks:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    readers = [Reader(s) for s in socks]
    t_pool = time.monotonic()
    rid = 0
    for _ in range(warmup):  # closed-loop rounds: the first compiles the service program
        for s in socks:
            send_all(s, infer_frame(rid, blobs[rid % pool_n], size))
            rid += 1
        for r in readers:
            r.next()
    pipe.send({"pool_s": t_pool - t_start, "warmup_s": time.monotonic() - t_pool})
    t0 = pipe.recv()  # monotonic time of the window's first due request
    n = len(sched["due"])
    base = rid
    sent = np.full(n, np.nan)
    recv = np.full(n, np.nan)
    ok = np.zeros(n, bool)
    answers: dict[int, tuple] = {}
    lock = threading.Lock()

    def receive(r: Reader) -> None:
        while True:
            try:
                ftype, payload = r.next()
            except (ConnectionError, OSError, ValueError):
                return
            t = time.monotonic()
            if ftype == wire.RESULT:
                i, version, _tier, dets = wire.parse_result(payload)
                with lock:
                    recv[i - base], ok[i - base] = t, True
                    answers[i - base] = (version, dets)
            elif ftype == wire.ERROR:
                i, _ = wire.parse_error(payload)
                with lock:
                    recv[i - base] = t

    for s in socks:
        s.settimeout(None)
    threads = [threading.Thread(target=receive, args=(r,), daemon=True) for r in readers]
    for t in threads:
        t.start()

    def send(c: int) -> None:  # one camera: its own requests, each when due
        for i in np.nonzero(sched["conn"] == c)[0]:
            wait = t0 + sched["due"][i] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            sent[i] = time.monotonic()
            send_all(socks[c], infer_frame(base + int(i), blobs[sched["image"][i]], size))

    senders = [threading.Thread(target=send, args=(c,), daemon=True) for c in range(connections)]
    for t in senders:
        t.start()
    for t in senders:
        t.join()
    deadline = t0 + sched["due"][-1] + drain_s
    while time.monotonic() < deadline:
        with lock:
            if np.all(np.isfinite(recv)):
                break
        time.sleep(0.01)
    for s in socks:
        try:
            s.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        s.close()
    for t in threads:
        t.join(timeout=5.0)
    with lock:
        pipe.send({"sent": sent, "recv": recv.copy(), "ok": ok.copy(), "answers": dict(answers)})
    pipe.close()
