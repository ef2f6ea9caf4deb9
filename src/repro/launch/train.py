"""Federated training launcher.

Runs the FedVision HFL loop (FL_SERVER + scheduler + Explorer + COS
checkpoints) for any assigned architecture at a CPU-runnable reduced size,
or emits the production-mesh launch configuration with --print-plan.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --rounds 20
  PYTHONPATH=src python -m repro.launch.train --arch granite-moe-1b-a400m \
      --agg quant8 --clients 8 --local-steps 2
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --rounds 20 \
      --participation compact --max-participants 2 --partition dirichlet
  PYTHONPATH=src python -m repro.launch.train --task detection --eval-every 1
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --rounds 20 \
      --mode async --buffer-size 2 --staleness-alpha 0.5 --max-staleness 4
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --mode async \
      --transport socket --clients 4 --buffer-size 2 --rounds 3 \
      --wire-codec quant8 --record-schedule /tmp/run.schedule.json
  PYTHONPATH=src python -m repro.launch.train --replay-schedule /tmp/run.schedule.json
  PYTHONPATH=src python -m repro.launch.train --arch grok-1-314b --print-plan

--transport socket runs a REAL multi-process federation (DESIGN.md §14):
worker processes (`repro.launch.worker`) train over TCP and the landing
loop feeds the arrival engine in wall-clock order; --rounds counts
flushes. The recorded arrival schedule replays deterministically through
the in-process SimClock engine (--replay-schedule verifies one).

--task detection runs the paper's actual workload: federated YOLOv3 over a
partitioned synthetic scene pool, with per-round global + per-client
mAP@0.5 from `server.evaluate_round` (--eval-every N) feeding the Task
Scheduler's quality EMA (DESIGN.md §10).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.checkpoint import ObjectStore
from repro.configs import get_arch
from repro.core import aggregators
from repro.core.rounds import FedConfig
from repro.core import monitor
from repro.core.scheduler import SchedulerConfig, TaskScheduler
from repro.core.server import FLServer
from repro.data import partition
from repro.data.pipeline import detection_suite, fed_batches
from repro.launch import specs
from repro.launch.cache import enable_compile_cache
from repro.optim import adamw, sgd


def print_plan(arch_name: str) -> None:
    for multi in (False, True):
        plan = specs.make_plan(arch_name, "train_4k", multi)
        print(f"== {plan.name}")
        print(f"   kind={plan.kind} aggregation={plan.aggregation}")
        if plan.fed:
            print(f"   clients={plan.fed.n_clients} client_axis={plan.fed.client_axis} "
                  f"data_axis={plan.fed.data_axis} microbatches={plan.fed.microbatches} topn={plan.fed.topn}")
        print(f"   rules={ {k: v for k, v in plan.rules.items() if v} }")


def _run_socket(args) -> None:
    """The --transport socket path: a real multi-process federation, then
    the wire summary + JSON (and optionally the recorded schedule)."""
    from repro.core.transport import harness

    meta = harness.make_meta(
        args.arch,
        reduced=not args.full_size,
        n_clients=args.clients,
        buffer_size=args.buffer_size,
        max_staleness=args.max_staleness,
        staleness_alpha=args.staleness_alpha,
        aggregation=args.agg if args.agg != "eq6" else "dense",
        local_steps=args.local_steps,
        batch=args.batch,
        seq=args.seq,
        lr=args.lr,
        wire_codec=args.wire_codec,
    )
    res = harness.wire_run(
        meta, args.rounds,
        durable_root=args.durable_dir or None,
        snapshot_every=args.snapshot_every,
        fault_plan=args.fault_plan,
        fault_seed=args.fault_seed,
    )
    if args.record_schedule:
        res.schedule.save(args.record_schedule)
    print(monitor.render_wire(args.arch, res.history, res.stats, args.clients,
                              liveness_log=res.liveness_log))
    stal = [s for r in res.history for s in r.staleness]
    print(json.dumps({
        "final_loss": res.history[-1].loss if res.history else float("nan"),
        "rounds": len(res.history),
        "mode": "async",
        "transport": "socket",
        "wire_codec": args.wire_codec,
        "landed": res.stats.landed,
        "dropped": res.dropped_total,
        "mean_staleness": (sum(stal) / len(stal)) if stal else 0.0,
        "bytes_up": res.stats.bytes_up,
        "bytes_down": res.stats.bytes_down,
        "deadline_hit": res.stats.deadline_hit,
        "recovered": res.recovered,
        "snapshots": res.stats.snapshots,
        "wal_events": res.stats.wal_events,
        "crc_errors": res.stats.crc_errors,
        "faults_injected": res.stats.faults_injected,
    }))


def _restore(path: str) -> None:
    """Recover an engine from a durable run directory (snapshot + WAL
    suffix through the jitted row update) and report what came back —
    the README's 'kill the server mid-round' quickstart verifier."""
    from repro.checkpoint.durable import DurableRun

    run = DurableRun(path)
    engine, replayed = run.recover_engine()
    print(json.dumps({
        "restored_from": str(path),
        "wal_events": run.n_events,
        "events_replayed": replayed,
        "version": engine.version,
        "flushes_recovered": len(engine.history),
        "staged_window": list(engine.staged()),
        "final_loss": engine.history[-1].loss if engine.history else float("nan"),
    }))


def _replay_schedule(path: str) -> None:
    """Replay a recorded arrival schedule (a CI artifact, say) through the
    SimClock engine; exits nonzero on the first divergent event."""
    from repro.core.transport import replay as rp

    schedule = rp.ArrivalSchedule.load(path)
    engine = rp.replay(schedule)
    print(json.dumps({
        "replayed_events": len(schedule.events),
        "flushes": len(engine.history),
        "final_loss": engine.history[-1].loss if engine.history else float("nan"),
        "dropped": engine.dropped_total,
        "deterministic": True,
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture name; optional with --task detection (defaults to fedyolov3)")
    ap.add_argument("--task", default="auto", choices=["auto", "lm", "detection"],
                    help="workload: lm (token batches) or detection (partitioned scene "
                    "pool + per-round mAP); auto picks detection for yolo-family archs")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="detection: run server.evaluate_round every N rounds "
                    "(global + per-client mAP@0.5 into the scheduler quality EMA)")
    ap.add_argument("--img-size", type=int, default=64, help="detection scene size")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=1)
    # any registered aggregator (fedsgd is a topology, not a CLI mode here)
    ap.add_argument("--agg", default="eq6", choices=[n for n in aggregators.names() if n != "fedsgd"])
    ap.add_argument("--server-lr", type=float, default=None,
                    help="fedavgm/fedadam server step (default: 1.0 for fedavgm, 0.02 for fedadam)")
    ap.add_argument("--group-size", type=int, default=0,
                    help="hier: clients per edge group (must divide --clients; "
                    "1 or --clients delegates to the flat base bit-for-bit)")
    ap.add_argument("--hier-base", default="dense",
                    help="hier: the stacked aggregator composed over group rows")
    ap.add_argument("--topn", type=int, default=0)
    ap.add_argument("--mode", default="sync", choices=["sync", "async"],
                    help="round control plane: sync (wait for every selected client) or "
                    "async (buffered staleness-weighted flushes on a simulated wall "
                    "clock, DESIGN.md §12)")
    ap.add_argument("--buffer-size", type=int, default=0,
                    help="async: flush after this many landed updates (0 -> clients, "
                    "which reproduces the sync round bit-for-bit)")
    ap.add_argument("--staleness-alpha", type=float, default=0.5,
                    help="async: polynomial staleness discount (1+s)^-alpha")
    ap.add_argument("--stream", action="store_true",
                    help="async: streaming O(buffer_size*N) flush — dispatch "
                    "ring + running accumulator instead of the (C, N) buffer "
                    "(forces --agg dense and a stateless sgd local optimizer)")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="async: drop updates staler than this many versions "
                    "(0 -> keep all; drops are counted, never silent)")
    ap.add_argument("--transport", default="inproc", choices=["inproc", "socket"],
                    help="inproc: simulated clients in this process; socket: real "
                    "worker processes over TCP (needs --mode async; --rounds "
                    "counts buffered flushes)")
    ap.add_argument("--wire-codec", default="dense",
                    choices=["dense", "quant8", "quant4", "topk"],
                    help="socket: UPDATE payload encoding — dense f32 rows, "
                    "int8 block-quantized deltas (the paper's ~4x uplink cut), "
                    "4-bit nibble-packed deltas (~8x), or sparse top-k deltas "
                    "(~18x; see transport/codec.py)")
    ap.add_argument("--record-schedule", default="",
                    help="socket: write the recorded arrival schedule (JSON) here")
    ap.add_argument("--replay-schedule", default="",
                    help="replay a recorded arrival schedule through the SimClock "
                    "engine and exit (no --arch needed; verifies determinism)")
    ap.add_argument("--durable-dir", default="",
                    help="socket: durable run directory (landing WAL + engine "
                    "snapshots; the server becomes kill -9 survivable)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="socket: full-engine snapshot every N landings "
                    "(0 = WAL only; needs --durable-dir)")
    ap.add_argument("--fault-plan", default="",
                    help="socket: deterministic fault injection spec "
                    "(transport/faults.py grammar, e.g. "
                    "'client.corrupt@2:update;kill@6'); with --durable-dir a "
                    "kill@M recovers automatically from snapshot+WAL")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="socket: seed for the fault plan's deterministic choices")
    ap.add_argument("--restore", default="",
                    help="recover an engine from a --durable-dir directory and "
                    "print the recovery report (no --arch needed; exits)")
    ap.add_argument("--participation", default="full", choices=["full", "masked", "compact"],
                    help="round body: full (everyone trains), masked (cond-gated), "
                    "compact (static-K gather; see --max-participants)")
    ap.add_argument("--max-participants", type=int, default=0,
                    help="scheduler budget per round (0 -> clients//2, min 2; "
                    "compact mode uses this as the static K)")
    ap.add_argument("--fairness-rounds", type=int, default=4,
                    help="force-include clients idle this many rounds")
    ap.add_argument("--partition", default="stream",
                    choices=["stream", *partition.SCENARIOS],
                    help="client data split: stream (per-client Markov drift) or a "
                    "data.partition scenario over a labeled pool (text archs)")
    ap.add_argument("--alpha", type=float, default=0.5, help="dirichlet label-skew concentration")
    ap.add_argument("--topk-frac", type=float, default=0.1,
                    help="topk_ef: upload fraction k/N of the packed row")
    ap.add_argument("--topk-quant", default="none", choices=["none", "quant4"],
                    help="topk_ef: quantize the selected values (composes the "
                    "sparsifier with the 4-bit codec)")
    ap.add_argument("--quant4-mode", default="stochastic",
                    choices=["stochastic", "nearest", "skip"],
                    help="quant4 aggregator rounding (skip -> dense bit-for-bit)")
    ap.add_argument("--quant4-seed", type=int, default=0,
                    help="quant4/topk_ef: per-round stochastic-rounding key seed")
    ap.add_argument("--secure-domain", default="int8", choices=["int8", "int4"],
                    help="secure: integer domain the masked sums run in")
    ap.add_argument("--no-secure-mask", action="store_true",
                    help="secure: skip the pairwise masks (the cancellation "
                    "equivalence baseline; quantized sum only)")
    ap.add_argument("--secure-session", type=int, default=0,
                    help="secure: session key the per-round pair masks derive from")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgd"])
    ap.add_argument("--full-size", action="store_true", help="use the full (non-reduced) config")
    ap.add_argument("--store", default="", help="COS object-store directory")
    ap.add_argument("--print-plan", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.replay_schedule:
        _replay_schedule(args.replay_schedule)
        return
    if args.restore:
        _restore(args.restore)
        return
    if args.snapshot_every and not args.durable_dir:
        ap.error("--snapshot-every needs --durable-dir")
    if (args.durable_dir or args.fault_plan) and args.transport != "socket":
        ap.error("--durable-dir/--fault-plan belong to --transport socket")
    if args.transport == "socket":
        if args.mode != "async":
            ap.error("--transport socket is the async control plane over a real "
                     "wire; pass --mode async")
        if args.stream or args.task == "detection":
            ap.error("--transport socket runs the buffered arrival engine "
                     "(lm workload, no --stream)")
        if args.arch is None:
            ap.error("--arch is required")
        _run_socket(args)
        return

    if args.task == "detection" and args.arch is None:
        args.arch = "fedyolov3"  # the paper's own model
    if args.arch is None:
        ap.error("--arch is required (or pass --task detection)")
    if args.print_plan:
        print_plan(args.arch)
        return

    cfg = get_arch(args.arch)
    task = args.task
    if task == "auto":
        task = "detection" if cfg.family == "yolo" else "lm"
    if task == "detection" and cfg.family != "yolo":
        ap.error(f"--task detection needs a yolo-family arch (got {args.arch})")
    if not args.full_size:
        cfg = cfg.reduced()
    if args.mode == "async" and args.participation != "full":
        ap.error("--mode async owns its own participation plane (the event queue); "
                 "drop --participation")
    if args.agg != "hier" and (args.group_size or args.hier_base != "dense"):
        ap.error("--group-size/--hier-base configure the hierarchical "
                 "aggregator; pass --agg hier")
    if args.stream:
        if args.mode != "async":
            ap.error("--stream is an async flush discipline; pass --mode async")
        if args.agg not in ("dense", "eq6"):  # eq6 is the default; coerce it
            ap.error("--stream folds aggregation into a running sum; only "
                     "--agg dense streams")
        args.agg = "dense"
        args.optimizer = "sgd"
        if args.max_staleness < 1:
            args.max_staleness = 4  # the dispatch ring needs a bound
    budget = args.max_participants or max(2, args.clients // 2)
    fed = FedConfig(
        n_clients=args.clients,
        local_steps=args.local_steps,
        aggregation=args.agg,
        topn=args.topn or specs.default_topn(cfg),
        client_axis="data",
        data_axis=None,
        # adaptive server step is ~server_lr per coordinate: fedadam needs a
        # small one out of the box (see core/aggregators/server_opt.py)
        server_lr=args.server_lr if args.server_lr is not None else (0.02 if args.agg == "fedadam" else 1.0),
        participation=args.participation,
        max_participants=budget if args.participation == "compact" else 0,
        mode=args.mode,
        buffer_size=args.buffer_size,
        staleness_alpha=args.staleness_alpha,
        max_staleness=args.max_staleness,
        stream=args.stream,
        group_size=args.group_size,
        hier_base=args.hier_base,
        topk_frac=args.topk_frac,
        topk_quant=args.topk_quant,
        quant4_mode=args.quant4_mode,
        quant4_seed=args.quant4_seed,
        secure_domain=args.secure_domain,
        secure_mask=not args.no_secure_mask,
        secure_session=args.secure_session,
    )
    if args.stream:
        optimizer = sgd(args.lr, momentum=0.0)  # stateless: the ring keeps no opt rows
    elif args.optimizer == "adamw":
        optimizer = adamw(args.lr)
    else:
        optimizer = sgd(args.lr)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    store = ObjectStore(args.store) if args.store else None
    with jax.set_mesh(mesh):
        server = FLServer(
            cfg,
            fed,
            optimizer,
            store=store,
            scheduler=TaskScheduler(fed.n_clients, SchedulerConfig(
                max_participants=budget, fairness_rounds=args.fairness_rounds)),
            mesh=mesh,
            checkpoint_every=5 if store else 0,
            task_id=args.arch,
        )
        eval_batch = None
        if task == "detection":
            # "stream" has no meaning for the pooled detection suite: the
            # IID split is the control scenario
            scenario = "iid" if args.partition == "stream" else args.partition
            gen, eval_batch, _ = detection_suite(
                cfg, fed, batch=args.batch, img_size=args.img_size,
                scenario=scenario, alpha=args.alpha,
            )
            batches = (jax.tree.map(jnp.asarray, b) for b in gen)
        else:
            batches = (
                jax.tree.map(jnp.asarray, b)
                for b in fed_batches(cfg, fed, batch=args.batch, seq=args.seq,
                                     partition_name=args.partition, alpha=args.alpha)
            )
        if eval_batch is not None and args.eval_every:
            step = server.run_async if server.engine is not None else server.run_round
            for r in range(args.rounds):
                rec = step(next(batches))
                if r % args.eval_every == 0 or r == args.rounds - 1:
                    ev = server.evaluate_round(eval_batch)
                    per = " ".join(f"{m:.3f}" for m in ev.per_client_map)
                    print(f"round {rec.round_idx:4d}  loss {rec.loss:.4f}  "
                          f"mAP@0.5 {ev.map50:.3f}  per-client [{per}]", flush=True)
            history = server.history
        else:
            history = server.fit(batches, args.rounds)
    mean_participants = sum(len(r.participants) for r in history) / len(history)
    summary = {
        "final_loss": history[-1].loss,
        "rounds": len(history),
        "participation": args.participation,
        "mean_participants": mean_participants,
    }
    if args.mode == "async":
        stal = [s for r in history for s in r.staleness]
        summary.update(
            mode="async",
            sim_seconds=history[-1].sim_time,
            mean_staleness=(sum(stal) / len(stal)) if stal else 0.0,
            dropped=server.engine.dropped_total,
        )
    if server.eval_history:
        print(monitor.render_task(args.arch, history, fed.n_clients,
                                  eval_history=server.eval_history))
        summary["final_map"] = server.eval_history[-1].map50
        summary["per_client_map"] = server.eval_history[-1].per_client_map
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
