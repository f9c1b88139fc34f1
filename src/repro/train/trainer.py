"""Trainer: SOLAR input pipeline + jitted step + fault tolerance.

The trainer is loader-agnostic (any :mod:`repro.data.loaders` loader) but is
built around SOLAR's contract:

  * the loader yields uneven per-node batches; ``StepBatch.to_global`` pads
    to the fixed SPMD capacity with zero-weight rows (gradients unchanged),
  * the :class:`~repro.data.prefetch.PrefetchExecutor` keeps
    ``prefetch_depth`` step batches ready — schedule-driven parallel chunk
    reads for SOLAR, background iteration for the baselines — so PFS reads
    overlap the previous step's compute (the paper's Fig. 6 overlap),
  * the plan cursor ``(epoch, step)`` plus the next global step is part of
    every checkpoint: restart resumes the exact global-batch sequence, and
    because every strategy now executes a plan, the resume replays the
    skipped steps' buffer deltas via ``ScheduleExecutor.fast_forward`` —
    zero I/O instead of re-reading every skipped batch,
  * per-step wall times are tracked separately for load vs compute — the
    paper's Fig. 3 breakdown comes straight from these counters, read off
    the same clock reads as the ``train.*`` spans that tile each step.
"""
from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

from repro.obs import trace as obs_trace

from repro.checkpoint.checkpoint import (
    AsyncCheckpointer,
    latest_checkpoint,
    plan_cursor_extra,
    restore_checkpoint,
    resume_cursor,
)
from repro.data.loaders import StepBatch
from repro.data.pipeline import LoaderSpec, build_pipeline
from repro.data.prefetch import PrefetchExecutor

__all__ = ["Trainer"]

_NO_ANNOTATION = contextlib.nullcontext()


class Trainer:
    def __init__(
        self,
        *,
        loader,                     # a loader, PrefetchExecutor, or LoaderSpec
        step_fn,                    # jitted (state, batch) -> (state, metrics)
        state,
        make_batch,                 # StepBatch -> model batch dict (numpy)
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 0,
        prefetch_depth: int = 2,
        num_workers: int = 4,       # I/O threads for schedule-driven prefetch
        skip_steps: int = 0,        # resume: skip already-trained steps
    ):
        if isinstance(loader, LoaderSpec):
            # declarative pipelines: the spec resolves backend + loader +
            # prefetch in one validated place (repro.data.pipeline) and its
            # prefetch shape wins over the Trainer kwargs — in particular
            # prefetch_depth=0 stays fully synchronous.
            prefetch_depth = loader.prefetch_depth
            num_workers = loader.num_workers
            loader = build_pipeline(loader)
        self.loader = loader
        self.step_fn = step_fn
        self.state = state
        self.make_batch = make_batch
        self.ckpt = AsyncCheckpointer(checkpoint_dir) if checkpoint_dir else None
        self.checkpoint_every = checkpoint_every
        self.prefetch_depth = prefetch_depth
        self.num_workers = num_workers
        self.skip_steps = skip_steps
        self.metrics_history: list[dict] = []
        self.load_time_s = 0.0
        self.compute_time_s = 0.0

    # -- fault tolerance -------------------------------------------------------

    @classmethod
    def try_restore(cls, checkpoint_dir, state_template, shardings=None,
                    plan_hash: str | None = None):
        """Returns (state, resume_step) — (template, 0) when no checkpoint.

        ``resume_step`` comes from the checkpoint's plan cursor (falling back
        through the legacy ``solar_step`` key).  When both ``plan_hash`` and
        the checkpoint record one, a mismatch raises — silently resuming a
        mid-plan cursor against a *different* plan would train the wrong
        sample sequence.
        """
        path = latest_checkpoint(checkpoint_dir) if checkpoint_dir else None
        if path is None:
            return state_template, 0
        state, meta = restore_checkpoint(path, state_template, shardings=shardings)
        saved_hash = meta.get("extra", {}).get("plan_hash")
        if plan_hash and saved_hash and plan_hash != saved_hash:
            raise ValueError(
                f"checkpoint {path} was written against plan {saved_hash}, "
                f"but the current pipeline executes plan {plan_hash} — "
                "refusing to resume a cursor into a different plan"
            )
        step, _cursor = resume_cursor(meta)
        return state, step

    # -- main loop -------------------------------------------------------------

    def run(self, max_steps: int | None = None):
        if isinstance(self.loader, PrefetchExecutor):
            executor = self.loader
        elif self.prefetch_depth > 0:
            executor = PrefetchExecutor(
                self.loader,
                depth=self.prefetch_depth,
                num_workers=self.num_workers,
            )
        else:  # prefetch_depth=0: fully synchronous loading
            executor = None
        source = executor if executor is not None else self.loader
        global_step = 0
        # Plan-first resume: replay the skipped steps' buffer deltas instead
        # of re-reading their data (ScheduleExecutor.fast_forward; proxied
        # through a PrefetchExecutor).  Loaders without a plan fall back to
        # skip-by-iteration.
        fast_forward = getattr(source, "fast_forward", None)
        if self.skip_steps and fast_forward is not None:
            fast_forward(self.skip_steps)
            global_step = self.skip_steps
        tr = obs_trace.get()
        # Traced, each iteration is also a profiler step, entered right after
        # reading train.step's t0: the pair anchors the recorder's clock to
        # the device trace's (DESIGN.md §13).  Untraced, nothing is entered.
        annotate = jax.profiler.StepTraceAnnotation if tr.enabled else None
        batches = iter(source)
        try:
            # loaders without a plan skip by iteration
            while global_step < self.skip_steps and next(batches, None) is not None:
                global_step += 1
            while True:
                tr.set_step(global_step)
                t0 = time.perf_counter()
                with (annotate("train", step_num=global_step) if annotate
                      else _NO_ANNOTATION):
                    sb = next(batches, None)
                    if sb is None:
                        break
                    t1 = time.perf_counter()
                    batch = self.make_batch(sb)
                    t2 = time.perf_counter()
                    tr.rec(obs_trace.TRAIN_MAKE_BATCH, t1, t2)
                    self.state, metrics = self.step_fn(self.state, batch)
                    jax.block_until_ready(metrics["loss"])
                    t3 = time.perf_counter()
                    tr.rec(obs_trace.TRAIN_COMPUTE, t2, t3)
                    rec = {k: float(np.asarray(v)) for k, v in metrics.items()}
                    t4 = time.perf_counter()
                    tr.rec(obs_trace.TRAIN_METRICS, t3, t4)
                    rec["step"] = global_step
                    self.metrics_history.append(rec)
                    global_step += 1
                    t5 = t4
                    if (
                        self.ckpt
                        and self.checkpoint_every
                        and global_step % self.checkpoint_every == 0
                    ):
                        self.ckpt.save(
                            global_step,
                            self.state,
                            extra=plan_cursor_extra(
                                global_step, sb.epoch, sb.step,
                                plan_hash=getattr(self.loader, "config_hash", None),
                            ),
                        )
                        t5 = time.perf_counter()
                        tr.rec(obs_trace.TRAIN_CHECKPOINT, t4, t5)
                    tr.rec(obs_trace.TRAIN_STEP, t0, t5)
                # the wait for the batch and its assembly; the device step
                self.load_time_s += t2 - t0
                self.compute_time_s += t3 - t2
                if max_steps is not None and global_step >= max_steps:
                    break
        finally:
            if executor is not None:
                executor.close()
        if self.ckpt:
            self.ckpt.wait()
        return self.state

    def breakdown(self) -> dict:
        """Paper Fig. 3-style time split from the clock reads of the
        ``train.*`` spans: ``load_s`` is the wait for each next batch plus
        ``make_batch`` (reads done on the prefetch thread count only where
        the loop waited on them), ``compute_s`` the step through its
        ``block_until_ready``."""
        total = self.load_time_s + self.compute_time_s
        return {
            "load_s": round(self.load_time_s, 4),
            "compute_s": round(self.compute_time_s, 4),
            "load_frac": round(self.load_time_s / total, 4) if total else 0.0,
            "loader_internal": self.loader.report.summary(),
        }
