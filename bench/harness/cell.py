"""One run of one cell: set-up, the measured window, then the check.

A cell is rank 0 of a four-rank data-parallel job. Set-up makes the data
set from the seed, writes it through the cell's storage backend, plans the
whole job with ``plan(spec)`` (the cell's fixed ``plan_seed`` shuffles, so
every run does the same work; the plan cache holds it after the first run), takes rank 0's slice with
``execute(spec, schedule.for_node(0), store=...)`` over the PFS stand-in,
and hands it to the program's ``Trainer`` with ``jax.jit(make_train_step(...))``.
One ``Trainer.run`` call then does everything: the warm-up steps (the first
compiles; in a cell that fills its buffer, the fill epoch too), and the
window, which opens when the last warm-up step completes and closes with the
first step to complete ``seconds`` or more later, that step included: the
step wrapper raises ``WindowClosed`` and ``Trainer.run`` stops there. So a
window holds whole steps only, and its length is ``seconds`` plus less than
one step. The benchmark supplies only what a user's
training script supplies: the initial weights, ``make_batch`` with its
input/target split, and that wrapper around the step.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import tempfile
import time
import types
from pathlib import Path

import numpy as np

from bench.harness import catalog, checks, data, devtrace, pfs, reference

__all__ = ["NoChip", "Run", "WindowClosed", "loader_spec", "program_config", "program_step",
           "reference_batches", "run_cell"]


class NoChip(RuntimeError):
    """JAX finds no accelerator of the kind, or fewer chips than the cell needs."""


class WindowClosed(Exception):
    """Raised from the step wrapper once the window has closed."""


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""

    setup_s: float
    t_open: float
    t_close: float
    #: one ``(t_done, real_rows, buffer_hits)`` per step completed in the window.
    steps: list
    #: the PFS stand-in's reads, ``(t_issued, t_done, nbytes)``.
    pfs_reads: list
    flops_per_sample: int
    peak_flops: float
    #: program spans ``(kind, t0, t1)`` of a traced run, else None.
    spans: list | None = None
    #: the device-trace reduction of a traced run, else None.
    device: dict | None = None

    @property
    def seconds(self) -> float:
        """The window's length."""
        return self.t_close - self.t_open

    def span_seconds(self, kind: str) -> list[float]:
        """Durations of the program's spans of ``kind`` that end in the window."""
        return [t1 - t0 for k, t0, t1 in self.spans or ()
                if k == kind and self.t_open < t1 <= self.t_close]


@dataclasses.dataclass
class _StepLog:
    epoch: int
    step: int
    ids: np.ndarray
    hits: int


class _CompileCounter:
    """Backend compiles in this process, from JAX's monitoring events."""

    def __init__(self, jax):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class _TimedStep:
    """The jitted step, blocked on and timed; opens and closes the window."""

    def __init__(self, jax, step, *, warmup: int, seconds: float, on_open):
        self._jax = jax
        self._step = step
        self._warmup = warmup
        self._seconds = seconds
        self._on_open = on_open
        self._gap = None
        self.calls = 0
        self.first_s = 0.0
        self.t_open: float | None = None
        self.t_close: float | None = None
        self.done: list[float] = []
        self.mu1 = None
        self.params3 = None

    def end_gap(self) -> None:
        if self._gap is not None:
            self._gap.__exit__(None, None, None)
            self._gap = None

    def __call__(self, state, batch):
        jax = self._jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            new_state, metrics = self._step(state, batch)
            jax.block_until_ready((new_state, metrics))
        t = time.perf_counter()
        k = self.calls
        self.calls += 1
        if k == 0:
            self.first_s = t - t0
            # AdamW's first moment after one step is (1 - b1) * its gradient
            self.mu1 = jax.device_get(new_state["opt"].mu)
        if k == 2:
            self.params3 = jax.device_get(new_state["params"])
        if self.t_open is None:
            if self.calls >= self._warmup:
                self.t_open = self._on_open()
        else:
            self.done.append(t)
            if t - self.t_open >= self._seconds:
                self.t_close = t
                raise WindowClosed
        self._gap = jax.profiler.TraceAnnotation("bench.next_batch")
        self._gap.__enter__()
        return new_state, metrics


def _frozen(value):
    return tuple(_frozen(v) for v in value) if isinstance(value, list) else value


def program_config(cfg):
    """The program's ``SurrogateConfig`` of the configuration: each of the
    dataclass's fields read from the key of its name (lists as tuples), so a
    field the program adds is read from the file as it stands."""
    from repro.configs.surrogates import SurrogateConfig

    return SurrogateConfig(**{f.name: _frozen(cfg[f.name])
                              for f in dataclasses.fields(SurrogateConfig) if f.name in cfg})


def program_step(cfg):
    """The program's jitted training step for the configuration, and its
    AdamW settings, built as a user's training script builds them."""
    import jax

    from repro.models import cnn
    from repro.optim.adamw import AdamWConfig
    from repro.train import step as train_step

    scfg = program_config(cfg)
    opt = AdamWConfig(**cfg["optimizer"])
    step_cfg = types.SimpleNamespace(grad_accum=1, grad_accum_dtype="float32")
    return jax.jit(train_step.make_train_step(
        step_cfg, opt, lambda p, b: cnn.surrogate_loss(p, b, scfg))), opt


def _split(cfg, record_rows, ids, table):
    """A batch's (x, y) from its records: the input channels and the target
    channels of a record, or the record and the seeded target table. The
    records keep their dtype; the network's forward pass transforms them."""
    cin = cfg["input_shape"][-1]
    if table is None:
        return record_rows[..., :cin], record_rows[..., cin:]
    y = np.zeros((record_rows.shape[0],) + tuple(cfg["output_shape"]), np.float32)
    y[: len(ids)] = table[ids]
    return record_rows, y


def loader_spec(cfg, wl, **fields):
    """The ``LoaderSpec`` of a cell: its loader over ``num_nodes`` ranks,
    shuffled by the cell's fixed ``plan_seed``."""
    from repro.core.scheduler import SolarConfig
    from repro.data import LoaderSpec

    solar = None
    if wl["loader"] == "solar":
        solar = SolarConfig(num_nodes=wl["num_nodes"], local_batch=cfg["local_batch"],
                            buffer_size=wl["buffer_size"],
                            capacity_factor=wl["capacity_factor"],
                            enable_peer=wl["peer_fetch"], seed=wl["plan_seed"])
    return LoaderSpec(
        loader=wl["loader"], backend=wl["backend"], num_nodes=wl["num_nodes"],
        local_batch=cfg["local_batch"], num_epochs=wl["num_epochs"],
        buffer_size=wl["buffer_size"], seed=wl["plan_seed"], peer_fetch=wl["peer_fetch"],
        solar=solar, **fields)


def reference_batches(cfg, seed: int, id_lists, table) -> list:
    """``(x, y)`` of the real rows of each step's ids, made from the seed."""
    out = []
    for ids in id_lists:
        x, y = _split(cfg, data.records(seed, ids, cfg["record_shape"], data.record_dtype(cfg)),
                      ids, table)
        out.append((x, y[: len(ids)]))
    return out


def _peak(root: Path, kind: str) -> float:
    with open(root / "bench" / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise LookupError(f"no peak for device kind {kind!r} in bench/peaks.json")
    return float(table[kind]["bf16_flops_per_s"])


def _cache_dir(root: Path, what: str) -> str:
    return str(root / "bench" / ".cache" / what)


def run_cell(root, name: str, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True) -> tuple[dict, list[str]]:
    """Run the cell once; returns the result line's object and the check lines."""
    root = Path(root)
    cell = catalog.load_cell(root, name)
    cfg, wl, net = cell.config, cell.workload, cell.network

    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU, JAX's default device is {dev.platform!r} ({dev.device_kind})")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {name} needs {cell.chips} chips, JAX finds {len(devices)}")
    peak = _peak(root, dev.device_kind)
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or _cache_dir(root, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from repro.data import create_store, execute, plan
    from repro.models import cnn
    from repro.obs import trace as obs_trace
    from repro.train import step as train_step
    from repro.train.trainer import Trainer

    compiles = _CompileCounter(jax)
    setup = {"start_s": time.perf_counter() - t_start}
    if trace:
        obs_trace.enable(capacity=1 << 18)
    scfg = program_config(cfg)
    record_shape = tuple(cfg["record_shape"])
    dtype = data.record_dtype(cfg)
    n = int(cfg["num_samples"])
    table = (data.targets(seed, n, math.prod(cfg["output_shape"]))
             if cfg["targets"] == "seeded" else None)

    with tempfile.TemporaryDirectory(prefix="bench_data_") as tmp:
        # -- data set, written through the cell's backend --------------------
        t = time.perf_counter()
        path = os.path.join(tmp, f"{cfg['name']}.{wl['backend']}")
        records = data.generate(seed, n, record_shape, dtype=dtype)
        create_store(path, wl["backend"], data=records, **wl["store_options"]).close()
        del records
        link = pfs.PfsLink(wl["pfs"]["latency_s"], wl["pfs"]["bandwidth_bytes_per_s"])
        store = pfs.open_store(path, wl["backend"], link)
        setup["data_s"] = time.perf_counter() - t

        # -- the job's plan, and rank 0's slice of it ------------------------
        t = time.perf_counter()
        spec = loader_spec(cfg, wl, collect_data=True, prefetch_depth=wl["prefetch_depth"],
                           num_workers=wl["num_workers"], plan_cache=_cache_dir(root, "plans"))
        schedule = plan(spec, store=store)
        mine = schedule.for_node(wl["rank"])
        loader = execute(spec, mine, store=store)
        setup["plan_s"] = time.perf_counter() - t
        planned = [(ep.epoch_id, sp.step, sp.nodes[0].sample_ids)
                   for ep in mine.epochs for sp in ep.steps]
        warmup = wl["warmup_epochs"] * len(mine.epochs[0].steps) + wl["warmup_steps"]

        # -- weights from the seed, on the device, and the program's step ----
        t = time.perf_counter()
        want = jax.eval_shape(lambda: cnn.init_surrogate(jax.random.PRNGKey(0), scfg))
        key = reference.jax_key(seed)
        got = jax.eval_shape(lambda: net.init_params(key, cfg))
        if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got) or [
                a.shape for a in jax.tree_util.tree_leaves(want)] != [
                a.shape for a in jax.tree_util.tree_leaves(got)]:
            raise RuntimeError("the program's parameter tree differs from the reference's")
        params = jax.jit(lambda k: net.init_params(k, cfg))(key)
        params0 = jax.device_get(params)
        step, opt = program_step(cfg)
        state = train_step.init_train_state(params, opt)
        setup["weights_s"] = time.perf_counter() - t

        log: list[_StepLog] = []
        first: list[tuple] = []
        kept: list[tuple] = []
        rng = np.random.default_rng([seed, 1])
        sample = int(wl["sampled_steps"])
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_", dir=tmp) if trace else None
        window = {}

        def on_open() -> float:
            setup["warm_s"] = time.perf_counter() - t_warm - timed.first_s
            window["compiles"] = compiles.count
            if trace:
                jax.profiler.start_trace(trace_dir)
            window["annotation"] = jax.profiler.TraceAnnotation(devtrace.WINDOW)
            window["annotation"].__enter__()
            return time.perf_counter()

        timed = _TimedStep(jax, step, warmup=warmup, seconds=seconds, on_open=on_open)
        capacity = mine.capacity

        def make_batch(sb):
            timed.end_gap()
            with jax.profiler.TraceAnnotation("bench.make_batch"):
                if len(sb.node_ids) != 1:
                    raise RuntimeError(f"rank slice yielded {len(sb.node_ids)} nodes")
                rows, weights = sb.to_global(capacity)
                ids = sb.node_ids[0]
                x, y = _split(cfg, rows, ids, table)
                i = len(log)
                log.append(_StepLog(sb.epoch, sb.step, ids, int(sb.hit_masks[0].sum())))
                # the first three steps, and a reservoir sample of the others
                if i < 3:
                    first.append((ids, x, y, weights))
                elif len(kept) < sample:
                    kept.append((ids, x, y, weights))
                else:
                    j = rng.integers(i - 2)
                    if j < sample:
                        kept[j] = (ids, x, y, weights)
                return {"x": jax.device_put(x, dev), "y": jax.device_put(y, dev),
                        "weights": jax.device_put(weights, dev)}

        trainer = Trainer(loader=loader, step_fn=timed, state=state, make_batch=make_batch)
        del state, params
        t_warm = time.perf_counter()
        plan_ended = 1
        try:
            with jax.default_matmul_precision(cfg["matmul_precision"]):
                trainer.run()
        except WindowClosed:
            plan_ended = 0
        finally:
            timed.end_gap()
            if "annotation" in window:
                window["annotation"].__exit__(None, None, None)
            if trace and timed.t_open is not None:
                jax.profiler.stop_trace()
            store.close()
        window_compiles = compiles.count - window.get("compiles", compiles.count)
        setup["compile_s"] = timed.first_s
        stats = dev.memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
        losses = [m["loss"] for m in trainer.metrics_history]
        trainer.state = None

        # -- readings of the traced run ---------------------------------------
        spans, reduced = None, None
        if trace:
            tr = obs_trace.disable()
            recs, _, _ = tr.records()
            spans = [(obs_trace.kind_name(int(r["kind"])), float(r["t0"]), float(r["t1"]))
                     for r in recs]
            if timed.t_open is not None:
                reduced = devtrace.reduce(devtrace.extract(devtrace.find_xplane(trace_dir)))

        # -- the check ---------------------------------------------------------
        t = time.perf_counter()
        ck = checks.Checks()
        ck.add("plan_ended_before_window_closed", plan_ended, 0)
        ck.add("steps_off_plan", sum(
            1 for i, s in enumerate(log) if i >= len(planned) or (s.epoch, s.step) !=
            planned[i][:2] or not np.array_equal(s.ids, planned[i][2])), 0)
        ck.add("epochs_not_covering_data_set", sum(
            1 for ep in schedule.epochs if not np.array_equal(
                np.sort(np.concatenate([nd.sample_ids for sp in ep.steps for nd in sp.nodes])),
                np.arange(n))), 0)
        bad_rows = bad_weights = 0
        for ids, x, y, w in first + kept:
            xr, yr = _split(cfg, data.records(seed, ids, record_shape, dtype), ids, table)
            k = len(ids)
            bad_rows += int(np.sum(np.any(x[:k] != xr, axis=tuple(range(1, x.ndim)))
                                   | np.any(y[:k] != yr[:k], axis=tuple(range(1, y.ndim)))))
            bad_weights += int(np.sum(w[:k] != 1.0) + np.sum(w[k:] != 0.0))
        ck.add("sampled_rows_differing_from_data_set", bad_rows, 0)
        ck.add("sampled_weights_wrong", bad_weights, 0)
        if timed.params3 is None:
            raise RuntimeError("fewer than three steps ran")
        batches = reference_batches(cfg, seed, [ids for _, _, ids in planned[:3]], table)
        with jax.default_matmul_precision("highest"):
            ref_losses, ref_g, ref_p3 = reference.train_steps(net, params0, batches, cfg,
                                                              capacity)
        b1 = cfg["optimizer"]["b1"]
        g1 = [a / (1 - b1) for a in reference.leaves(timed.mu1)]
        g1_ref = reference.leaves(ref_g)
        p0 = reference.leaves(params0)
        d3 = [a - b for a, b in zip(reference.leaves(timed.params3), p0)]
        d3_ref = [a - b for a, b in zip(reference.leaves(ref_p3), p0)]
        keep = checks.steady_leaves(g1_ref)
        last = net.last_layer(cfg)
        gg, ug = checks.leaf_gaps(g1, g1_ref), checks.leaf_gaps(d3, d3_ref)
        ck.add("loss_gap_steps_1_3", checks.loss_gap(losses[:3], ref_losses),
               cfg["limits"]["loss_gap"])
        for name, limit in cfg["limits"].items():
            if name.startswith("grad_gap_"):
                ck.add(name.replace("grad_gap_", "grad_gap_step_1_"),
                       checks.held_gap(name, gg, last_layer=last), limit)
            elif name.startswith("update_gap_"):
                ck.add(name.replace("update_gap_", "update_gap_steps_1_3_"),
                       checks.held_gap(name, ug, keep), limit)
            elif name != "loss_gap":
                raise ValueError(f"unknown limit {name!r} in the configuration")
        check_s = time.perf_counter() - t
        sizes = [int(a.size) for a in g1_ref]
        detail = {"losses": {"program": losses[:3], "reference": ref_losses},
                  "grad_worst": [int(gg.argmax()), sizes[int(gg.argmax())], float(gg.max())],
                  "update_worst": [int(np.where(keep, ug, -1).argmax()),
                                   sizes[int(np.where(keep, ug, -1).argmax())],
                                   float(ug[keep].max())],
                  "grad_leaf_gaps": gg.tolist(), "update_leaf_gaps": ug.tolist(),
                  "leaf_sizes": sizes, "leaves_kept": int(keep.sum())}

        t_close = timed.t_close if timed.t_close is not None else time.perf_counter()
        done = timed.done
        steps = [(done[j], log[warmup + j].ids.size, log[warmup + j].hits)
                 for j in range(len(done))]
        run = Run(setup_s=(timed.t_open or t_close) - t_start,
                  t_open=timed.t_open or t_close, t_close=t_close, steps=steps,
                  pfs_reads=list(link.reads), flops_per_sample=net.train_flops_per_sample(cfg),
                  peak_flops=peak, spans=spans, device=reduced)
        if len(link.reads) != store.read_calls:
            raise RuntimeError(f"the PFS stand-in charged {len(link.reads)} reads, "
                               f"the store made {store.read_calls}")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = catalog.load_metric(root, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {
        "correct": ck.correct,
        "attempted": len(steps),
        "failed": int(sum(not np.isfinite(v) for v in losses[warmup:warmup + len(steps)])),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    setup["check_s"] = check_s
    result["setup"] = setup
    result["window"] = {"steps": len(steps), "compiles": window_compiles,
                        "pfs_reads": len(link.reads)}
    result["detail"] = detail
    result["checks"] = ck.as_dict()
    return result, ck.lines()
