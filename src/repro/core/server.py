"""FL_SERVER — orchestrates federated rounds (paper component #5).

"responsible for model parameter uploading, model aggregation, and model
dispatch." The server owns the jitted fed_round, the scheduler, the object
store, and the round loop; FL_CLIENTs are the mesh slices (their control
surface is repro.core.client). Aggregation policy is resolved purely
through the :mod:`repro.core.aggregators` registry — the server never
branches on a mode name.

Scheduler-in-the-loop (DESIGN.md §8): each round the Explorer's load model
reports per-client loads, `TaskScheduler.participation` turns them into the
mask/weight (and compact-index) vectors, and those flow into the jitted
round as traced inputs — selection changes every round, the compiled
program never retraces. Per-client losses come back in the metrics and feed
the scheduler's quality EMA for the *participants only* (a skipped client's
quality signal would otherwise be fabricated).

Async mode (DESIGN.md §12): ``FedConfig.mode == "async"`` swaps the round
control plane for `core.async_engine.BufferedAsyncEngine` — `run_async`
drives one buffered flush per call on the shared `SimClock`, records
per-update staleness and the simulated wall-clock into the history the
monitor renders, and the engine feeds the same scheduler quality EMA from
its async completions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import ObjectStore
from repro.configs.base import ArchConfig
from repro.core import aggregators, async_engine, explorer, rounds, telemetry
from repro.core.async_engine import (
    AsyncRoundRecord,
    BufferedAsyncEngine,
    StreamingAsyncEngine,
    TimingModel,
    sync_round_seconds,
)
from repro.core.scheduler import SchedulerConfig, TaskScheduler
from repro.core.simclock import SimClock
from repro.optim import Optimizer

PyTree = Any


@dataclasses.dataclass
class RoundRecord:
    round_idx: int
    loss: float
    weights: list[float]
    seconds: float  # the fl.round span: scheduling to checkpoint, monotonic
    participants: list[int] = dataclasses.field(default_factory=list)
    loads: list[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class EvalRecord:
    """One `evaluate_round` outcome: global + per-client mAP@0.5."""

    round_idx: int
    map50: float
    per_client_map: list[float]


class FLServer:
    def __init__(
        self,
        cfg: ArchConfig,
        fed: rounds.FedConfig,
        optimizer: Optimizer,
        *,
        store: ObjectStore | None = None,
        scheduler: TaskScheduler | None = None,
        mesh=None,
        rules: dict | None = None,
        seed: int = 0,
        dtype=jnp.float32,
        checkpoint_every: int = 0,
        task_id: str = "task",
        load_model: explorer.ClientLoadModel | None = None,
        clock: SimClock | None = None,
        timing: TimingModel | None = None,
    ):
        if fed.mode not in ("sync", "async"):
            raise ValueError(f"unknown mode {fed.mode!r}; expected sync|async")
        self.cfg = cfg
        self.fed = fed
        self.optimizer = optimizer
        self.store = store
        self.task_id = task_id
        self.checkpoint_every = checkpoint_every
        self.scheduler = scheduler or TaskScheduler(fed.n_clients, SchedulerConfig())
        self.load_model = load_model or explorer.ClientLoadModel(fed.n_clients, seed=seed)
        # an explicitly shared clock makes sync rounds advance simulated
        # time too (wait-for-slowest), so sync and async servers interleave
        # under TaskManager.step_shared_clock; without one, sync rounds
        # keep the legacy timeless cadence
        self._shared_clock = clock is not None
        self.clock = clock or SimClock()
        self.timing = timing or TimingModel()
        # compact rounds need the scheduler to emit exactly K indices
        self._k_static = rounds.static_budget(fed) if fed.participation == "compact" else None
        # registry dispatch: validates the mode name and any mode config
        # (e.g. quant8 divisibility, trimmed_mean ratio) before any jit
        self.aggregator = rounds.make_aggregator(cfg, fed, mesh)
        self.dtype = dtype
        self.engine: BufferedAsyncEngine | None = None
        if fed.mode == "async":
            # the engine owns the flat state and the (donated) flush
            # program; the server's round surface delegates to it.
            # stream=True swaps the O(C·N) buffered flush for the ring +
            # running-accumulator discipline (DESIGN.md §13)
            engine_cls = StreamingAsyncEngine if fed.stream else BufferedAsyncEngine
            self.engine = engine_cls(
                cfg, fed, optimizer, mesh=mesh, rules=rules, seed=seed, dtype=dtype,
                clock=self.clock, load_model=self.load_model, timing=self.timing,
                scheduler=self.scheduler, aggregator=self.aggregator,
            )
            self.state = self.engine.state
            self._fed_round = None
            self._upload_s = self.engine.upload_s
        else:
            self.state = rounds.make_state(cfg, fed, optimizer, jax.random.key(seed), dtype)
            # donated jit (DESIGN.md §11): run_round consumes self.state and
            # rebinds the returned one, so XLA reuses the round buffers in place
            self._fed_round = rounds.jit_fed_round(rounds.build_fed_round(cfg, fed, optimizer, mesh, rules))
            self._upload_s = async_engine.default_upload_terms(
                self.timing, fed.n_clients, self.aggregator.ctx.spec.n_total, seed
            )
        self.history: list[RoundRecord | AsyncRoundRecord] = []
        self.eval_history: list[EvalRecord] = []
        self._evaluator = None  # (max_detections, jitted fn), built lazily

    @property
    def aggregation_modes(self) -> tuple[str, ...]:
        """Every mode this server could be configured with."""
        return aggregators.names()

    def global_params(self) -> PyTree:
        """Dispatchable global model (synced post-round; fedsgd topology
        already holds the single shared copy). Sync rounds broadcast the
        global to every row, so row 0 serves; an async state only
        guarantees *some* rows hold the fresh global — in-flight rows (row
        0 included) may carry stale dispatch versions — so this reads the
        engine's `global_packed_row()`, never a fixed row index. Each
        engine knows where its global lives: buffered keeps `global_row`
        (the last-staged row, immutable until the next flush), streaming
        the live ring slot, and the arrival engine an explicit snapshot
        (its rows mutate on every landing, so no buffer row is trustworthy
        mid-window). Async checkpoints go through here, so a checkpoint
        taken right after drops/redispatches stores the flushed global,
        not a client's half-trained row — tests/test_transport.py pins
        that. This is a pack/unpack EDGE (DESIGN.md §11): the flat round
        state unpacks to a param pytree only here — checkpoint PUT and
        model dispatch to serving — never inside the round."""
        if not self.aggregator.stacked:
            return self.state["params"]
        if self.engine is not None:
            # the engine knows which row is current (buffered: the last
            # staged client's row; streaming: the live ring slot)
            packed = self.engine.global_packed_row()[None]
            params = rounds.unpacked_params(self.cfg, self.fed, {"params": packed}, self.dtype)
            return jax.tree.map(lambda x: x[0], params)
        params = self.state["params"]
        if isinstance(params, jax.Array):  # flat layout: unpack one row only
            params = rounds.unpacked_params(
                self.cfg, self.fed, {"params": params[:1]}, self.dtype
            )
            return jax.tree.map(lambda x: x[0], params)
        return jax.tree.map(lambda x: x[0], params)

    def run_round(self, batch: PyTree) -> RoundRecord:
        if self.engine is not None:
            raise RuntimeError(
                "FedConfig(mode='async') servers run buffered flushes — call "
                "run_async(batch) (or fit(), which dispatches on the mode)"
            )
        with telemetry.span("fl.round", id=len(self.history)) as span:
            with telemetry.span("fl.schedule", id=span.id):
                if self._shared_clock:
                    # shared-clock semantics: this round's report is the load
                    # process state *now*; the round then consumes wait-for-slowest
                    # simulated time and the process evolves over that same span
                    # (stepping by 1.0 here would re-conflate process time with
                    # round count — the cadence bug the §12 Explorer fix removed)
                    loads = self.load_model.loads.copy()
                else:
                    loads = self.load_model.step()  # legacy: one tick per round
                sel = self.scheduler.participation(loads, k_static=self._k_static)
                part = rounds.participation_input(self.fed, sel["mask"], sel["weights"], sel.get("idx"))
                if self._shared_clock:
                    # the round takes as long as its slowest selected client
                    dur = sync_round_seconds(
                        self.timing, loads, self._upload_s, self.fed.local_steps,
                        mask=sel["mask"],
                    )
                    self.clock.advance(dur)
                    self.load_model.step(dur)
            with telemetry.span("fl.dispatch", id=span.id):
                self.state, metrics = self._fed_round(self.state, batch, part)
            with telemetry.span("fl.device_wait", id=span.id):
                loss = float(metrics["loss"])
            with telemetry.span("fl.report", id=span.id):
                participants = [int(c) for c in np.nonzero(sel["mask"])[0]]
                client_loss = np.asarray(metrics["client_loss"], np.float32)
                for c in participants:
                    self.scheduler.report_quality(c, float(client_loss[c]))
                rec = RoundRecord(
                    span.id,
                    loss,
                    [float(w) for w in sel["weights"]],
                    0.0,  # the fl.round span's length, set when it closes
                    participants=participants,
                    loads=[float(x) for x in loads],
                )
                self.history.append(rec)
            if self.store and self.checkpoint_every and rec.round_idx % self.checkpoint_every == 0:
                with telemetry.span("fl.checkpoint", id=span.id):
                    self.store.put_model(self.task_id, rec.round_idx, self.global_params(), {"loss": loss})
        rec.seconds = (span.end_ns - span.start_ns) / 1e9
        return rec

    def run_async(self, batch: PyTree) -> AsyncRoundRecord:
        """One buffered-aggregation flush on the simulated clock (DESIGN.md
        §12): the engine pops completion events until ``buffer_size`` updates
        stage (dropping and counting anything staler than max_staleness),
        applies the staleness-weighted donated flush, and redispatches. The
        record lands in the same history the monitor renders — per-update
        staleness and the simulated wall-clock included — and the engine has
        already fed the scheduler quality EMA from the completions."""
        if self.engine is None:
            raise RuntimeError("run_async needs FedConfig(mode='async')")
        rec = self.engine.step_round(batch)
        self.state = self.engine.state  # global_params/eval read through here
        self.history.append(rec)
        if self.store and self.checkpoint_every and rec.round_idx % self.checkpoint_every == 0:
            self.store.put_model(self.task_id, rec.round_idx, self.global_params(), {"loss": rec.loss})
        return rec

    def next_time(self) -> float:
        """Simulated completion time of this server's next round — the
        `FederatedTask.next_time` hook for TaskManager's shared-clock
        interleave (DESIGN.md §12). Async servers report their earliest
        queued completion; sync servers estimate now + wait-for-slowest
        over the clients the scheduler is likely to select: the K fastest
        under its budget (an under-budget fleet never waits for unselected
        stragglers) PLUS every client whose idle streak hit the fairness
        floor — the scheduler guarantees those join the next round, so a
        floored straggler's wait belongs in the estimate."""
        if self.engine is not None:
            t = self.engine.next_completion_time()
            return self.clock.now() if t is None else t
        per = np.array([
            self.timing.compute_seconds(l, self.fed.local_steps)
            for l in self.load_model.loads
        ]) + self._upload_s
        k = self._k_static or self.scheduler.cfg.max_participants or self.fed.n_clients
        k = min(k, self.fed.n_clients)
        dur = float(np.sort(per)[:k].max())
        floored = per[self.scheduler.idle_rounds >= self.scheduler.cfg.fairness_rounds]
        if floored.size:
            dur = max(dur, float(floored.max()))
        return self.clock.now() + dur

    def evaluate_round(
        self,
        eval_batch: PyTree,
        *,
        max_detections: int = 64,
        feed_scheduler: bool = True,
    ) -> EvalRecord:
        """Detection-quality checkpoint: global model vs each client's eval
        slice (DESIGN.md §10).

        eval_batch: {"images" (C, B, H, W, 3), "gt_boxes"/"gt_cls"/
        "gt_valid" (C, B, G, ...)} — e.g. `data.pipeline.detection_suite`'s
        holdout. One jitted call returns the pooled global mAP@0.5 and the
        per-client vector; the latter feeds the Task Scheduler's quality
        EMA (`report_eval`), so selection tracks *detection* quality, not
        just training loss — the signal the paper's load-balancing
        scheduler is supposed to maximize.
        """
        from repro.core import detection  # lazy: only detection tasks pay the import

        if self._evaluator is None or self._evaluator[0] != max_detections:
            self._evaluator = (
                max_detections,
                detection.build_evaluator(self.cfg, max_detections=max_detections),
            )
        out = self._evaluator[1](self.global_params(), jax.tree.map(jnp.asarray, eval_batch))
        per_client = [float(x) for x in np.asarray(out["per_client_map"], np.float64)]
        if feed_scheduler:
            for c, m in enumerate(per_client):
                self.scheduler.report_eval(c, m)
        rec = EvalRecord(max(len(self.history) - 1, 0), float(out["map"]), per_client)
        self.eval_history.append(rec)
        return rec

    def fit(self, batches: Iterator[PyTree], n_rounds: int, log: Callable[[str], None] = lambda m: print(m, flush=True)) -> list[RoundRecord]:
        step = self.run_async if self.engine is not None else self.run_round
        for r in range(n_rounds):
            rec = step(next(batches))
            if log and (r % max(1, n_rounds // 10) == 0 or r == n_rounds - 1):
                msg = (f"round {rec.round_idx:4d}  loss {rec.loss:.4f}  "
                       f"participants {len(rec.participants)}/{self.fed.n_clients}")
                if isinstance(rec, AsyncRoundRecord):
                    msg += (f"  sim {rec.sim_time:7.0f}s  staleness "
                            f"{np.mean(rec.staleness):.2f}  dropped {rec.dropped}")
                log(msg)
        return self.history
