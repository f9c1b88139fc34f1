"""Training launcher: plan-first SOLAR pipeline + jitted step + checkpointing.

    # train (the default subcommand; bare flags keep working)
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --reduced --steps 50 --loader solar --backend sharded \
        --data /tmp/tokens.bin --plan-cache /tmp/solar_plans

    # precompute / inspect plan artifacts without training
    PYTHONPATH=src python -m repro.launch.train plan --loader solar \
        --num-samples 32768 --nodes 8 --local-batch 32 --buffer 3072 \
        --epochs 6 --out /tmp/solar.plan.npz
    PYTHONPATH=src python -m repro.launch.train plan --inspect /tmp/solar.plan.npz

    # multi-process data pipeline: N rank processes, socket peer transport
    PYTHONPATH=src python -m repro.launch.train distributed --nodes 2 \
        --peer-fetch --num-samples 2048 --epochs 2 --verify

    # streaming ingestion: train over samples produced live (DESIGN.md §10)
    PYTHONPATH=src python -m repro.launch.train stream --nodes 2 \
        --num-samples 2048 --window-steps 8 --watermark 32 --verify
    PYTHONPATH=src python -m repro.launch.train stream --distributed \
        --nodes 2 --backend sharded --num-samples 2048 --verify

Trains on the default JAX device — one TPU chip, or the CPU under
``JAX_PLATFORMS=cpu`` — with no mesh: nothing here shards across chips.
``chip_smoke.py`` at the repository root is the on-chip check of this path.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.obs import log as obs_log
from repro.data import (
    STRATEGIES,
    DatasetSpec,
    LoaderSpec,
    backend_names,
    build_pipeline,
    build_store,
)
from repro.models import encdec, lm
from repro.optim.adamw import AdamWConfig
from repro.train.step import init_train_state, make_train_step
from repro.train.trainer import Trainer


def _add_pipeline_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--loader", default="solar", choices=STRATEGIES)
    ap.add_argument("--num-samples", type=int, default=2048)
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--local-batch", type=int, default=8)
    ap.add_argument("--buffer", type=int, default=512)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan-cache", default=None,
                    help="directory memoizing compiled plans by config hash")
    obs_log.add_verbosity_args(ap)


def _add_train_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--arch", required=True)
    ap.add_argument("--plan-path", default=None,
                    help="explicit plan artifact: loaded when present, "
                         "built + saved there when not")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale model (CPU-trainable)")
    _add_pipeline_args(ap)
    ap.add_argument("--backend", default="binary", choices=backend_names(),
                    help="storage backend serving --data (created on first "
                         "run in that layout)")
    ap.add_argument("--data", default=None,
                    help="dataset path (default: /tmp/solar_tokens.<backend> "
                         "— per-backend so switching --backend never reopens "
                         "another layout's bytes)")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="pipeline read-ahead in steps (0 = synchronous)")
    ap.add_argument("--num-workers", type=int, default=4,
                    help="I/O threads for schedule-driven chunk reads")
    ap.add_argument("--peer-fetch", action="store_true",
                    help="plan + execute the peer-fetch buffer tier "
                         "(solar loader only): capacity-spilled misses are "
                         "served from sibling node buffers instead of the PFS")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")


def _add_plan_args(ap: argparse.ArgumentParser) -> None:
    _add_pipeline_args(ap)
    ap.add_argument("--out", default=None,
                    help="save the compiled plan artifact here (loaded "
                         "instead when it already exists; mutually "
                         "exclusive with --plan-cache)")
    ap.add_argument("--inspect", default=None, metavar="PATH",
                    help="load an existing artifact and report on it "
                         "instead of compiling")
    ap.add_argument("--peer-fetch", action="store_true",
                    help="plan the peer-fetch tier (needs an explicit "
                         "peer cost model when no dataset is opened; a "
                         "default is derived from --sample-bytes)")
    ap.add_argument("--sample-bytes", type=int, default=4096,
                    help="sample size used to price the peer tier when "
                         "planning without a dataset; must match the "
                         "dataset's real sample size for the artifact's "
                         "config hash to line up with training")
    ap.add_argument("--capacity-factor", type=float, default=None,
                    help="padded-batch capacity factor (solar loader); 1.0 "
                         "is the zero-padding regime where the peer tier "
                         "carries traffic (DESIGN.md §6)")


def _plan_report(schedule) -> dict:
    """Stats / hash / per-node load — what the operator wants to see."""
    st = schedule.stats()
    # one walk over the plan, grouped by node — slicing a full for_node()
    # view per rank would copy the whole plan num_nodes times.
    acc = {
        r: {"node": r, "pfs_samples": 0, "misses": 0, "hits": 0,
            "peer_fetches": 0, "peer_serves": 0}
        for r in range(schedule.num_nodes)
    }
    for sp in schedule:
        for npn in sp.nodes:
            a = acc[npn.node]
            a["pfs_samples"] += npn.pfs_samples
            a["misses"] += npn.num_misses
            a["hits"] += npn.num_hits
            a["peer_fetches"] += npn.num_peer
            for f in npn.peer_fetches:
                # serving load: imbalance here is what the per-step
                # least-serving source choice keeps in check.
                acc[f.source]["peer_serves"] += 1
    per_node = [acc[r] for r in sorted(acc)]
    return {
        "strategy": schedule.strategy,
        "config_hash": schedule.config_hash,
        "artifact_digest": schedule.artifact_digest(),
        "num_nodes": schedule.num_nodes,
        "local_batch": schedule.local_batch,
        "capacity": schedule.capacity,
        "buffer_size": schedule.buffer_size,
        "num_epochs": len(schedule.epochs),
        "num_steps": schedule.num_steps,
        "stats": st.summary(),
        "per_node": per_node,
    }


def run_plan(args) -> None:
    from repro.core.costmodel import PeerCostModel, PFSCostModel
    from repro.core.plan import Schedule
    from repro.data import plan

    if args.inspect:
        schedule = Schedule.load(args.inspect)
        print(json.dumps(_plan_report(schedule), indent=1))
        return
    # Same cost-model shape make_planner derives from an open store, so a
    # precomputed artifact's config hash matches a later train run whose
    # dataset has --sample-bytes-sized samples.
    peer_cost = None
    if args.peer_fetch:
        peer_cost = PeerCostModel(
            sample_bytes=args.sample_bytes,
            pfs=PFSCostModel(sample_bytes=args.sample_bytes),
        )
    solar = None
    if args.capacity_factor is not None and args.loader == "solar":
        from repro.core.scheduler import SolarConfig

        solar = SolarConfig(
            num_nodes=args.nodes, local_batch=args.local_batch,
            buffer_size=args.buffer, seed=args.seed,
            capacity_factor=args.capacity_factor,
            enable_peer=args.peer_fetch, peer_cost=peer_cost,
        )
        peer_cost = None  # carried by the solar config now
    spec = LoaderSpec(
        loader=args.loader, num_nodes=args.nodes,
        local_batch=args.local_batch, num_epochs=args.epochs,
        buffer_size=args.buffer, seed=args.seed,
        peer_fetch=args.peer_fetch, peer_cost=peer_cost, solar=solar,
        plan_cache=args.plan_cache, plan_path=args.out,
    )
    schedule = plan(spec, num_samples=args.num_samples)
    print(json.dumps(_plan_report(schedule), indent=1))


def _add_distributed_args(ap: argparse.ArgumentParser) -> None:
    _add_pipeline_args(ap)
    ap.add_argument("--backend", default="binary", choices=backend_names(),
                    help="storage backend serving --data (created on first "
                         "run; must be path-based — every rank reopens it)")
    ap.add_argument("--data", default=None,
                    help="dataset path (default: /tmp/solar_tokens.<backend>)")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--peer-fetch", action="store_true",
                    help="plan + serve the peer tier over real sockets "
                         "(capacity_factor=1.0 so the tier carries traffic)")
    ap.add_argument("--verify", action="store_true",
                    help="also execute the plan in-process and assert every "
                         "rank's stream digest matches bit for bit (and, "
                         "under faults, that the XOR-aggregate digest of "
                         "the whole run matches despite deaths)")
    ap.add_argument("--timeout", type=float, default=300.0,
                    help="whole-run timeout in seconds")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="seeded fault-injection plan, e.g. "
                         "'seed=7,crash=1,corrupt=2,slow=1' "
                         "(see repro.runtime.faults.FaultPlan.parse; "
                         "ranks= defaults to --nodes)")
    ap.add_argument("--recovery", default="reslice",
                    choices=("reslice", "degrade"),
                    help="on rank death: re-slice its remaining plan onto "
                         "survivors (default) or degrade to PFS fallbacks")
    ap.add_argument("--prefetch-depth", type=int, default=0,
                    help="epoch-window skew: ranks barrier only every "
                         "depth+1 steps and pipeline that many steps of "
                         "chunk reads inside the window (0 = lockstep; "
                         "digests are depth-invariant)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="flight recorder (DESIGN.md §13): every rank dumps "
                         "trace-rank{N}.jsonl + a Chrome trace-event file "
                         "here; analyze with `python -m repro.obs.report`")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the coordinator's live telemetry "
                         "time-series + the final summary as one JSON file")


def run_distributed_cmd(args) -> None:
    from repro.core.scheduler import SolarConfig
    from repro.runtime import (
        FaultPlan,
        in_process_aggregate,
        in_process_digests,
        run_distributed,
    )

    faults = None
    if args.faults:
        text = args.faults
        if "ranks=" not in text:
            text = f"ranks={args.nodes},{text}"
        faults = FaultPlan.parse(text)

    if args.data is None:
        args.data = f"/tmp/solar_tokens.{args.backend}"
    solar = None
    if args.loader == "solar" and args.peer_fetch:
        # capacity_factor=1.0 is the regime where the tier carries traffic
        # (capacity-spilled hits become interconnect fetches, DESIGN.md §6).
        solar = SolarConfig(
            num_nodes=args.nodes, local_batch=args.local_batch,
            buffer_size=args.buffer, seed=args.seed,
            capacity_factor=1.0, enable_peer=True,
        )
    spec = LoaderSpec(
        loader=args.loader, backend=args.backend, path=args.data,
        num_nodes=args.nodes, local_batch=args.local_batch,
        num_epochs=args.epochs, buffer_size=args.buffer, seed=args.seed,
        collect_data=True, peer_fetch=args.peer_fetch, solar=solar,
        plan_cache=args.plan_cache, transport="socket",
        prefetch_depth=max(args.prefetch_depth, 0),
    )
    store = build_store(
        spec, create=True,
        dataset=DatasetSpec(args.num_samples, (args.seq_len + 1,), "<i4"),
        fill="random",
    )
    store.close()  # ranks reopen it themselves; the parent only creates it
    from repro.data import plan

    schedule = plan(spec)  # once: the run and the reference share one plan
    report = run_distributed(
        spec, schedule=schedule, timeout_s=args.timeout,
        faults=faults, recovery=args.recovery,
        trace_dir=args.trace_dir, metrics_out=args.metrics_out,
        verbosity=obs_log.verbosity_from(args),
    )
    out = report.summary()
    if args.verify:
        ref = in_process_digests(spec, schedule=schedule)
        mismatched = [
            r.rank for r in report.ranks
            if r.status == "ok" and not r.rejoined and r.digest != ref[r.rank]
        ]
        agg_parity = (
            report.aggregate_digest()
            == in_process_aggregate(spec, schedule=schedule)
        )
        out["verify"] = {
            "digest_parity": not mismatched and report.ok,
            "aggregate_parity": agg_parity,
            "mismatched_ranks": mismatched,
            "dead_ranks": report.dead,
        }
        print(json.dumps(out, indent=1))
        if mismatched:
            raise SystemExit(
                f"digest mismatch on ranks {mismatched}: the multi-process "
                "run trained different bytes than the in-process reference"
            )
        if not agg_parity:
            raise SystemExit(
                "aggregate digest mismatch: the run did not execute the "
                "planned global sample stream exactly once"
            )
        if report.dead and (args.recovery != "reslice" or faults is None):
            # in degrade mode a dead rank means its samples were never
            # verified at all — a green exit would let CI pass on a broken
            # runtime.  Under reslice the aggregate parity above already
            # proves survivors covered the dead rank's remaining plan, but
            # only an *injected* death is an expected outcome.
            raise SystemExit(
                f"ranks {report.dead} died during the run: digest parity "
                "could not be verified for them"
            )
        return
    print(json.dumps(out, indent=1))
    if report.dead and (args.recovery != "reslice" or faults is None):
        # a death nobody injected must not exit green, re-sliced or not:
        # wrapping scripts treat this exit code as "the run completed".
        # An *injected* crash under reslice is the scenario being tested —
        # pair it with --verify to assert aggregate parity.
        raise SystemExit(f"ranks {report.dead} died during the run")


def _add_stream_args(ap: argparse.ArgumentParser) -> None:
    from repro.stream import ADMISSION_POLICIES

    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--local-batch", type=int, default=8)
    ap.add_argument("--buffer", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--num-samples", type=int, default=2048,
                    help="id space of the stream (store rows; producers "
                         "emit each id once)")
    ap.add_argument("--backend", default="sharded",
                    choices=("memory", "sharded"),
                    help="writable backend holding the stream (distributed "
                         "runs require 'sharded': ranks read the rows the "
                         "parent's ingest writes)")
    ap.add_argument("--data", default=None,
                    help="store path (default: /tmp/solar_stream.<backend>)")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--window-steps", type=int, default=8,
                    help="training steps per plan window")
    ap.add_argument("--watermark", type=int, default=16,
                    help="fresh admissions a seal waits for before the next "
                         "window is planned")
    ap.add_argument("--admission", default="reservoir",
                    choices=ADMISSION_POLICIES,
                    help="seeded admission policy for arriving samples")
    ap.add_argument("--reservoir", type=int, default=None,
                    help="admitted-set bound for reservoir/latest policies "
                         "(default: unbounded)")
    ap.add_argument("--max-windows", type=int, default=None,
                    help="stop after this many windows (default: run until "
                         "producers finish with nothing fresh)")
    ap.add_argument("--rate", type=float, default=None,
                    help="aggregate producer arrival rate in samples/s "
                         "(default: unthrottled)")
    ap.add_argument("--producer-threads", type=int, default=2)
    ap.add_argument("--prefetch-depth", type=int, default=0,
                    help="pipeline read-ahead in steps; distributed ranks "
                         "run it as async prefetch inside their stream "
                         "windows (digests stay depth-invariant)")
    ap.add_argument("--distributed", action="store_true",
                    help="execute as --nodes rank processes: each sealed "
                         "window's plan is broadcast by content hash and "
                         "ranks cut over at the same step boundary")
    ap.add_argument("--stop-the-world", action="store_true",
                    help="plan each window synchronously at the boundary "
                         "instead of overlapping planning with training "
                         "(the baseline benchmarks/stream.py compares)")
    ap.add_argument("--verify", action="store_true",
                    help="assert the streaming determinism contract: the "
                         "concatenated window plans and the executed batch "
                         "stream match a one-shot offline replan (and, "
                         "distributed, every rank's slice digest matches "
                         "the in-process reference)")
    ap.add_argument("--timeout", type=float, default=300.0)
    obs_log.add_verbosity_args(ap)


def run_stream_cmd(args) -> None:
    import threading

    from repro.stream import (
        IngestSession,
        StreamSpec,
        run_producers,
        run_stream,
    )
    from repro.stream.distributed import run_stream_distributed

    if args.data is None:
        args.data = f"/tmp/solar_stream.{args.backend}"
    if args.distributed and args.backend != "sharded":
        raise SystemExit(
            "stream --distributed requires --backend sharded (ranks must "
            "see the parent's row writes; 'memory' stages at open)"
        )
    spec = LoaderSpec(
        loader="stream", backend=args.backend, path=args.data,
        num_nodes=args.nodes, local_batch=args.local_batch,
        buffer_size=args.buffer, seed=args.seed, collect_data=True,
        prefetch_depth=max(args.prefetch_depth, 0),
        stream=StreamSpec(
            window_steps=args.window_steps, admission=args.admission,
            watermark=args.watermark, reservoir_size=args.reservoir,
            max_windows=args.max_windows,
        ),
    )
    store = build_store(
        spec, create=True,
        dataset=DatasetSpec(
            args.num_samples, (args.seq_len + 1,), "<i4", num_shards=4
        ),
        fill="zeros",
    )
    try:
        session = IngestSession(
            store, seed=args.seed, admission=args.admission,
            reservoir_size=args.reservoir,
        )
        producer = threading.Thread(
            target=run_producers, args=(session, range(args.num_samples)),
            kwargs=dict(
                threads=args.producer_threads, data_seed=args.seed,
                rate_hz=args.rate,
            ),
            name="stream-producers", daemon=True,
        )
        producer.start()
        if args.distributed:
            report = run_stream_distributed(
                spec, session, verify=args.verify, timeout_s=args.timeout,
            )
        else:
            report = run_stream(
                spec.replace(store=store, path=None), session,
                overlap=not args.stop_the_world, verify=args.verify,
            )
        producer.join(timeout=30.0)
        print(json.dumps(report.summary(), indent=1))
        if args.distributed and report.dead:
            raise SystemExit(f"ranks {report.dead} died during the stream")
        if args.verify and not report.ok:
            raise SystemExit(
                "streaming determinism violated: the live window plans or "
                "batches diverged from the one-shot offline replan"
            )
    finally:
        store.close()


def run_train(args) -> None:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    if args.data is None:
        args.data = f"/tmp/solar_tokens.{args.backend}"
    spec = LoaderSpec(
        loader=args.loader, backend=args.backend, path=args.data,
        num_nodes=args.nodes, local_batch=args.local_batch,
        num_epochs=args.epochs, buffer_size=args.buffer, seed=args.seed,
        collect_data=True, prefetch_depth=args.prefetch_depth,
        num_workers=args.num_workers, peer_fetch=args.peer_fetch,
        plan_cache=args.plan_cache, plan_path=args.plan_path,
    )
    store = build_store(
        spec, create=True,
        dataset=DatasetSpec(args.num_samples, (args.seq_len + 1,), "<i4"),
        fill="random",
    )
    loader = build_pipeline(spec, store=store)
    capacity = getattr(loader, "capacity", args.local_batch + 4)

    key = jax.random.PRNGKey(0)
    init = encdec.init_encdec if cfg.family == "encdec" else lm.init_lm
    params = init(key, cfg)
    opt = AdamWConfig(lr=args.lr, total_steps=args.steps)
    loss_mod = encdec if cfg.family == "encdec" else lm

    def loss_fn(p, b):
        return loss_mod.train_loss(p, b, cfg)

    step = jax.jit(make_train_step(cfg, opt, loss_fn), donate_argnums=(0,))
    state = init_train_state(params, opt)
    skip = 0
    if args.resume and args.checkpoint_dir:
        state, skip = Trainer.try_restore(
            args.checkpoint_dir, state,
            plan_hash=getattr(loader, "config_hash", None),
        )
        print(f"resuming from step {skip}")

    def make_batch(sb):
        data, weights = sb.to_global(capacity)
        tokens = jnp.asarray(data[:, :-1] % cfg.vocab_size, jnp.int32)
        labels = jnp.asarray(data[:, 1:] % cfg.vocab_size, jnp.int32)
        batch = {"tokens": tokens, "labels": labels,
                 "weights": jnp.asarray(weights)}
        b = tokens.shape[0]
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros((b, cfg.num_patches, cfg.d_model),
                                         jnp.float32)
        if cfg.family == "encdec":
            batch["source"] = jnp.zeros((b, cfg.source_len, cfg.d_model),
                                        jnp.float32)
        return batch

    trainer = Trainer(
        loader=loader, step_fn=step, state=state, make_batch=make_batch,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every, skip_steps=skip,
        prefetch_depth=args.prefetch_depth, num_workers=args.num_workers,
    )
    trainer.run(max_steps=args.steps)
    for rec in trainer.metrics_history[:: max(len(trainer.metrics_history) // 10, 1)]:
        print(f"step {rec['step']:5d} loss {rec['loss']:.4f}")
    print(json.dumps(trainer.breakdown(), indent=1))


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    # Back-compat: a bare flag list is the train subcommand — but leave
    # top-level help reachable so the plan subcommand stays discoverable.
    if argv and argv[0] not in (
        "train", "plan", "distributed", "stream", "-h", "--help"
    ):
        argv = ["train"] + argv
    ap = argparse.ArgumentParser(prog="repro.launch.train")
    sub = ap.add_subparsers(dest="cmd", required=True)
    _add_train_args(sub.add_parser(
        "train", help="train a model through the plan-first pipeline"))
    _add_plan_args(sub.add_parser(
        "plan", help="precompute or inspect a plan artifact (no training)"))
    _add_distributed_args(sub.add_parser(
        "distributed",
        help="execute one plan as N rank processes over the socket peer "
             "transport (data pipeline only, no model training)"))
    _add_stream_args(sub.add_parser(
        "stream",
        help="train over a live sample stream: seeded admission, rolling "
             "window plans, deterministic vs an offline replan"))
    args = ap.parse_args(argv)
    obs_log.configure(obs_log.verbosity_from(args))
    use_compile_cache()
    if args.cmd == "plan":
        run_plan(args)
    elif args.cmd == "distributed":
        run_distributed_cmd(args)
    elif args.cmd == "stream":
        run_stream_cmd(args)
    else:
        run_train(args)


if __name__ == "__main__":
    main()
