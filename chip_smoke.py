#!/usr/bin/env python3
"""On-chip smoke test: SOLAR-fed PtychoNN training on one TPU chip.

    python chip_smoke.py [--seed 0]

Drives the system's main path once through its library entry points:

  1. writes a fresh ``binary`` store of 16384 PtychoNN diffraction frames
     (64x64x1 float32, 256 MiB) generated from ``--seed``;
  2. trains PtychoNN at its registry size (``SURROGATES["ptychonn"]``) for
     the whole two-epoch SOLAR plan: ``LoaderSpec`` -> ``Trainer`` ->
     ``jax.jit(make_train_step(...))``, 4 simulated nodes x 64 samples per
     step, padded to a global batch of 4 x 96;
  3. checks that every loss is finite, the last below the first, the state
     on the TPU, and no compile after step 3;
  4. checks the first global batch's loss and gradient norm, with the
     initial parameters, against a float32 reference on the host CPU
     (both under ``jax.default_matmul_precision("highest")``).

It needs a TPU and exits nonzero, printing no result, when JAX's default
device is anything else.  The last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The samples/s it prints is a smoke timing, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
import types
from pathlib import Path

#: steps after which the train step must not compile again.
WARM_STEPS = 3
#: the largest relative chip-vs-CPU difference accepted at highest precision.
REF_RTOL = 1e-4


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


class _CompileCounter:
    """Counts backend compiles (persistent-cache hits included) and the
    seconds each jitted function spent in them, through JAX's monitoring
    events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds: dict[str, float] = {}
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds[fun_name] = self.seconds.get(fun_name, 0.0) + duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class _TimedStep:
    """The jitted train step, timed per call; after each call it notes the
    process's backend-compile count."""

    def __init__(self, step, counter: _CompileCounter):
        self.step = step
        self.counter = counter
        self.first_call_s = 0.0
        self.ends: list[float] = []
        self.compiles: list[int] = []

    def __call__(self, state, batch):
        import jax

        t0 = time.perf_counter()
        out = self.step(state, batch)
        jax.block_until_ready(out)
        self.ends.append(time.perf_counter())
        if len(self.ends) == 1:
            self.first_call_s = self.ends[0] - t0
        self.compiles.append(self.counter.compiles)
        return out


def smoke(dev, cfg, *, seed: int, num_samples: int, num_nodes: int,
          local_batch: int, buffer_size: int, num_epochs: int) -> None:
    """Run every phase on ``dev``; raise SystemExit on the first failure."""
    import jax
    import numpy as np

    from repro.data import DatasetSpec, LoaderSpec, create_store
    from repro.models import cnn
    from repro.optim.adamw import AdamWConfig, global_norm
    from repro.train.step import init_train_state, make_train_step
    from repro.train.trainer import Trainer

    counter = _CompileCounter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        # -- dataset ---------------------------------------------------------
        t0 = time.perf_counter()
        path = str(Path(tmp) / "ptychonn.bin")
        store = create_store(
            path, "binary",
            spec=DatasetSpec(num_samples, cfg.input_shape, "<f4"),
            fill="random", seed=seed,
        )
        nbytes = store.num_samples * store.sample_bytes
        store.close()
        print(f"dataset: binary store, {num_samples} x {cfg.input_shape} "
              f"float32 = {nbytes / 2**20:.1f} MiB from seed {seed}, "
              f"written in {time.perf_counter() - t0:.2f} s")

        # -- training --------------------------------------------------------
        spec = LoaderSpec(
            loader="solar", backend="binary", path=path,
            num_nodes=num_nodes, local_batch=local_batch,
            buffer_size=buffer_size, num_epochs=num_epochs, seed=seed,
            collect_data=True, prefetch_depth=2,
        )
        params = jax.device_put(
            cnn.init_surrogate(jax.random.PRNGKey(seed), cfg), dev
        )
        init_params = jax.device_get(params)
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree_util.tree_leaves(params))
        steps = num_epochs * num_samples // (num_nodes * local_batch)
        opt = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=steps)
        step_cfg = types.SimpleNamespace(grad_accum=1,
                                         grad_accum_dtype="float32")
        timed = _TimedStep(jax.jit(make_train_step(
            step_cfg, opt, lambda p, b: cnn.surrogate_loss(p, b, cfg)
        )), counter)
        first_batch: dict = {}

        def make_batch(sb):
            # test_system's synthetic target: the input's mean, broadcast.
            data, weights = sb.to_global(trainer.loader.capacity)
            pooled = data.reshape(data.shape[0], -1).mean(axis=1)
            y = np.broadcast_to(
                pooled.reshape((-1,) + (1,) * len(cfg.output_shape)),
                (data.shape[0],) + cfg.output_shape,
            ).astype(np.float32)
            host = {"x": data, "y": y, "weights": weights}
            if not first_batch:
                first_batch.update(host)
            return {k: jax.device_put(v, dev) for k, v in host.items()}

        trainer = Trainer(loader=spec, step_fn=timed,
                          state=jax.device_put(init_train_state(params, opt),
                                               dev),
                          make_batch=make_batch)
        try:
            trainer.run()
        finally:
            trainer.loader.store.close()
        hist = trainer.metrics_history
        losses = [m["loss"] for m in hist]
        global_batch = first_batch["x"].shape[0]
        print(f"model: {cfg.name}, {n_params} params, global batch "
              f"{global_batch} ({num_nodes} nodes x capacity "
              f"{trainer.loader.capacity})")

        _check(len(hist) >= 20, f"only {len(hist)} steps ran")
        _check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
        _check(losses[-1] < losses[0],
               f"last loss {losses[-1]} is not below the first {losses[0]}")
        leaves = jax.tree_util.tree_leaves(trainer.state)
        _check(all(x.devices() == {dev} for x in leaves),
               f"train state left {dev}")
        late = timed.compiles[-1] - timed.compiles[WARM_STEPS - 1]
        _check(late == 0, f"{late} compiles after step {WARM_STEPS}")
        print(f"train: {len(hist)} steps, loss {losses[0]:.6f} -> "
              f"{losses[-1]:.6f}, all finite; state on {dev}")
        print(f"compile: first step {timed.first_call_s:.2f} s (trace + "
              f"compile + one step), train-step backend compile "
              f"{counter.seconds.get('jit(step)', 0.0):.2f} s, persistent-"
              f"cache hits {counter.cache_hits}; no compile after step "
              f"{WARM_STEPS} (train-step cache size "
              f"{timed.step._cache_size()})")
        bd = trainer.breakdown()["loader_internal"]
        print(f"loader: numPFS={bd['numPFS']} hit_rate={bd['hit_rate']} "
              f"misses={bd['misses']}")
        tokens = sum(m["tokens"] for m in hist[WARM_STEPS:])
        span = timed.ends[-1] - timed.ends[WARM_STEPS - 1]
        print(f"smoke timing, not a benchmark: steps {WARM_STEPS + 1}.."
              f"{len(hist)}: {tokens / span:.1f} samples/s "
              f"({span / (len(hist) - WARM_STEPS) * 1e3:.2f} ms/step)")

        # -- float32 reference on the host CPU ------------------------------
        cpu = jax.devices("cpu")[0]

        @jax.jit
        def loss_and_gnorm(p, b):
            (loss, _), g = jax.value_and_grad(
                lambda q: cnn.surrogate_loss(q, b, cfg), has_aux=True
            )(p)
            return loss, global_norm(g)

        def on(d):
            out = loss_and_gnorm(jax.device_put(init_params, d),
                                 jax.device_put(first_batch, d))
            return tuple(float(v) for v in out)

        t0 = time.perf_counter()
        default_loss, _ = on(dev)
        with jax.default_matmul_precision("highest"):
            chip_loss, chip_gn = on(dev)
            cpu_loss, cpu_gn = on(cpu)
        print(f"default precision: chip loss {default_loss:.8f}, relative "
              f"deviation {_rel(default_loss, cpu_loss):.3e} from the CPU")
        print(f"reference (highest precision): loss chip {chip_loss:.8f} "
              f"cpu {cpu_loss:.8f} rel {_rel(chip_loss, cpu_loss):.3e}; "
              f"grad norm chip {chip_gn:.8f} cpu {cpu_gn:.8f} "
              f"rel {_rel(chip_gn, cpu_gn):.3e} "
              f"({time.perf_counter() - t0:.1f} s)")
        _check(np.isfinite([chip_loss, chip_gn, cpu_loss, cpu_gn]).all(),
               "non-finite reference values")
        _check(_rel(chip_loss, cpu_loss) <= REF_RTOL,
               f"loss differs from the CPU reference by more than {REF_RTOL}")
        _check(_rel(chip_gn, cpu_gn) <= REF_RTOL,
               f"gradient norm differs from the CPU reference by more than "
               f"{REF_RTOL}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the dataset and the initial weights")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX's default device is "
            f"{dev.platform!r} ({dev.device_kind})"
        )
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs.surrogates import SURROGATES
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    smoke(dev, SURROGATES["ptychonn"], seed=args.seed, num_samples=16384,
          num_nodes=4, local_batch=64, buffer_size=1024, num_epochs=2)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
