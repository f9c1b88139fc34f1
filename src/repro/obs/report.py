"""``python -m repro.obs.report`` — where did each ms go? (DESIGN.md §13)

Reads the per-rank ``trace-rank*.jsonl`` files a traced run dumped into
``--trace-dir`` and renders per-step time attribution across the loading
ladder: disk/PFS chunk reads, the peer tier, barrier waits, skew parking,
tenant yields/sheds, heartbeats, and the sections of ``Trainer.run``.
``--check`` turns the same pass into a validator (well-formed spans,
per-thread monotonic timestamps, barrier time accounted, nonzero chunk
reads) for CI smokes.

``--xplane PATH`` (a JAX profiler ``.xplane.pb``, or a directory holding
one) puts the spans on the profiler's clock: each ``train.step`` span pairs
with the profiler step ``Trainer.run`` entered for it, by step number, and
the median of the pairs' offsets maps the recorder's clock onto the
profiler's.  The device's idle time inside those steps is then split by the
innermost program span open on the training loop's thread.

    PYTHONPATH=src python -m repro.obs.report TRACE_DIR [--check] [--json]
        [--xplane PATH]
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import statistics
import sys
import warnings

__all__ = ["load_traces", "load_xplane", "analyze", "clock_split", "check",
           "main"]

#: the rendered breakdown: display stage -> span kinds whose time it sums.
#: ``step.*`` sections tile the rank loop; chunk/peer/serve kinds attribute
#: the same wall time at finer grain (they nest inside the sections), so
#: the coverage accounting below sums only the tiling sections.
STAGES = {
    "barrier": ("barrier.wait",),
    "peer": ("step.peer",),
    "execute": ("step.execute",),
    "prime": ("step.prime",),
    "hb": ("hb.send",),
}
DETAIL = {
    "disk_pfs": ("chunk.read",),
    "peer_wire": ("peer.fetch",),
    "skew_wait": ("serve.skew_park",),
    "tenant_yield": ("serve.tenant_yield",),
    "to_global": ("batch.to_global",),
    "read_wait": ("prefetch.read_wait",),
}
#: ``Trainer.run``: ``train.step`` and the sections that tile it when a
#: prefetch executor feeds the loop.
TRAIN_STAGES = {
    "qwait": ("prefetch.qwait",),
    "make_batch": ("train.make_batch",),
    "compute": ("train.compute",),
    "metrics": ("train.metrics",),
    "checkpoint": ("train.checkpoint",),
}
#: the training loop's spans that device idle time is attributed to; the
#: innermost open one takes it (``train.step`` alone: unspanned).
IDLE_KINDS = ("train.step", "batch.to_global") + tuple(
    k for kinds in TRAIN_STAGES.values() for k in kinds)
#: anchor offsets may spread this much before ``--check`` fails (ns).
MAX_SPREAD_NS = 1_000_000
COUNTS = {
    "sheds": ("serve.shed",),
    "retries": ("peer.retry",),
    "breaker_opens": ("peer.breaker_open",),
    # fault firings are interned per kind+site ("fault.crash:32", ...)
    "faults": ("fault", "fault."),
}


def load_traces(trace_dir: str) -> dict[int, dict]:
    """rank -> {"meta": {...}, "records": [span dicts]} from the JSONL dumps."""
    out: dict[int, dict] = {}
    for path in sorted(glob.glob(os.path.join(trace_dir, "trace-rank*.jsonl"))):
        m = re.search(r"trace-rank(\d+)\.jsonl$", path)
        if m is None:
            continue
        rank = int(m.group(1))
        meta: dict = {}
        records: list[dict] = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                obj = json.loads(line)
                if obj.get("meta"):
                    meta = obj
                else:
                    records.append(obj)
        out[rank] = {"meta": meta, "records": records, "path": path}
    return out


def _sum_by(records, kinds) -> float:
    names = set(kinds)
    return sum(r["dur"] for r in records if r["name"] in names)


def _count_by(records, kinds) -> int:
    exact = {k for k in kinds if not k.endswith(".")}
    prefixes = tuple(k for k in kinds if k.endswith("."))
    return sum(
        1 for r in records
        if r["name"] in exact
        or (prefixes and r["name"].startswith(prefixes))
    )


def analyze(trace_dir: str, xplane: str | None = None) -> dict:
    """Aggregate one traced run's dumps into per-rank + cluster attribution.

    Per rank: total/per-step milliseconds for every display stage, the
    fraction of measured step wall time the tiling sections account for
    (``coverage``), and the barrier overhead in ms/step — the number
    ``BENCH_dist.json`` previously derived from hand-inserted timers; under
    ``train``, the same for ``Trainer.run``'s loop.  With ``xplane``, also
    :func:`clock_split` of that profiler trace under ``xplane``.
    """
    traces = load_traces(trace_dir)
    if not traces:
        raise FileNotFoundError(
            f"no trace-rank*.jsonl files under {trace_dir!r}"
        )
    ranks: dict[str, dict] = {}
    cluster_steps = 0
    cluster_totals: dict[str, float] = {}
    cluster_step_ms = 0.0
    cluster_coverage_num = 0.0
    cluster_coverage_den = 0.0
    for rank, tr in sorted(traces.items()):
        recs = tr["records"]
        steps = [r for r in recs if r["name"] == "step"]
        nsteps = len(steps)
        step_ms = _sum_by(recs, ("step",)) * 1e3
        stage_ms = {
            stage: _sum_by(recs, kinds) * 1e3
            for stage, kinds in STAGES.items()
        }
        detail_ms = {
            stage: _sum_by(recs, kinds) * 1e3
            for stage, kinds in DETAIL.items()
        }
        counts = {
            name: _count_by(recs, kinds) for name, kinds in COUNTS.items()
        }
        accounted = sum(stage_ms.values())
        # per-step rows (step index -> per-stage ms) for the detailed view
        per_step: dict[int, dict[str, float]] = {}
        for r in recs:
            for stage, kinds in {**STAGES, "step": ("step",)}.items():
                if r["name"] in kinds:
                    row = per_step.setdefault(int(r["step"]), {})
                    row[stage] = row.get(stage, 0.0) + r["dur"] * 1e3
        train_ms = _sum_by(recs, ("train.step",)) * 1e3
        ntrain = _count_by(recs, ("train.step",))
        train_stage_ms = {
            stage: _sum_by(recs, kinds) * 1e3
            for stage, kinds in TRAIN_STAGES.items()
        }
        ranks[str(rank)] = {
            "steps": nsteps,
            "records": len(recs),
            "dropped": int(tr["meta"].get("dropped", 0)),
            "step_ms_total": round(step_ms, 3),
            "step_ms_mean": round(step_ms / nsteps, 3) if nsteps else 0.0,
            "stage_ms_total": {k: round(v, 3) for k, v in stage_ms.items()},
            "stage_ms_per_step": {
                k: round(v / nsteps, 3) if nsteps else 0.0
                for k, v in stage_ms.items()
            },
            "detail_ms_total": {k: round(v, 3) for k, v in detail_ms.items()},
            "counts": counts,
            "coverage": round(accounted / step_ms, 4) if step_ms else 0.0,
            "barrier_ms_per_step": (
                round(stage_ms["barrier"] / nsteps, 3) if nsteps else 0.0
            ),
            "per_step": {
                str(s): {k: round(v, 4) for k, v in sorted(row.items())}
                for s, row in sorted(per_step.items())
            },
            "train": {
                "steps": ntrain,
                "step_ms_mean": round(train_ms / ntrain, 3) if ntrain else 0.0,
                "stage_ms_per_step": {
                    k: round(v / ntrain, 3) if ntrain else 0.0
                    for k, v in train_stage_ms.items()
                },
                "coverage": (
                    round(sum(train_stage_ms.values()) / train_ms, 4)
                    if train_ms else 0.0
                ),
            },
        }
        cluster_steps += nsteps
        cluster_step_ms += step_ms
        for k, v in stage_ms.items():
            cluster_totals[k] = cluster_totals.get(k, 0.0) + v
        cluster_coverage_num += accounted
        cluster_coverage_den += step_ms
    out = {
        "trace_dir": trace_dir,
        "num_ranks": len(traces),
        "ranks": ranks,
        "cluster": {
            "steps": cluster_steps,
            "step_ms_mean": (
                round(cluster_step_ms / cluster_steps, 3)
                if cluster_steps else 0.0
            ),
            "stage_ms_per_step": {
                k: round(v / cluster_steps, 3) if cluster_steps else 0.0
                for k, v in sorted(cluster_totals.items())
            },
            "barrier_ms_per_step": (
                round(cluster_totals.get("barrier", 0.0) / cluster_steps, 3)
                if cluster_steps else 0.0
            ),
            "coverage": (
                round(cluster_coverage_num / cluster_coverage_den, 4)
                if cluster_coverage_den else 0.0
            ),
        },
    }
    if xplane is not None:
        trained = [tr for tr in traces.values()
                   if _count_by(tr["records"], ("train.step",))]
        if len(trained) != 1:
            raise ValueError(
                "a profiler trace is one process's: expected one rank with "
                f"train.step spans, found {len(trained)}"
            )
        out["xplane"] = clock_split(trained[0]["records"], load_xplane(xplane))
    return out


def load_xplane(path: str) -> dict:
    """The profiler steps ``Trainer.run`` entered (step number -> ``(start,
    end)``) and each device plane's ``XLA Ops`` intervals, in profiler ns.

    ``path`` is an ``.xplane.pb`` or a directory holding exactly one.
    """
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(
                f"expected one .xplane.pb under {path!r}, found {found}"
            )
        path = found[0]
    from jax.profiler import ProfileData  # offline: rank processes never load jax

    data = ProfileData.from_file(path)
    steps: dict[int, tuple[int, int]] = {}
    devices: dict[str, list[tuple[int, int]]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [(int(e.start_ns), int(e.end_ns))
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name != "train":
                        continue
                    # jaxlib builds the stats' type on first use, with a
                    # DeprecationWarning about its __module__
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore", DeprecationWarning)
                        num = dict(e.stats).get("step_num")
                    if num is not None:
                        steps[int(num)] = (int(e.start_ns), int(e.end_ns))
    return {"path": path, "steps": steps, "devices": devices}


def _union(intervals) -> list[list[int]]:
    """Merged, sorted intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _subtract(windows, busy) -> list[tuple[int, int]]:
    """Sorted disjoint ``windows`` minus sorted disjoint ``busy``."""
    out = []
    j = 0
    for s, e in windows:
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(busy) and busy[k][0] < e:
            if busy[k][0] > cur:
                out.append((cur, busy[k][0]))
            cur = max(cur, busy[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _innermost(marks) -> list[tuple[int, int, str]]:
    """Properly nested spans of one thread, ``(start, end, name)``, flattened
    into disjoint segments named by the innermost span open in each."""
    events = []
    for m in marks:
        s, e, _ = m
        if e <= s:
            continue
        events.append((e, 0, -s, m))   # ends first; inner (later) ends first
        events.append((s, 1, -e, m))   # outer (longer) starts first
    events.sort(key=lambda ev: ev[:3])
    out, stack, prev = [], [], None
    for t, is_start, _, m in events:
        if stack and t > prev:
            out.append((prev, t, stack[-1][2]))
        prev = t
        if is_start:
            stack.append(m)
        else:
            stack.remove(m)
    return out


def clock_split(records, xp: dict) -> dict:
    """Pair ``train.step`` spans with the profiler's steps; split the
    device's idle time inside the paired steps by program span.

    ``offset_ns`` is the median of (profiler start − span ``t0``) over the
    pairs and ``spread_ns`` their range.  ``idle_s``, averaged over the
    device planes that ran ops (empty without one, as on a CPU), names the
    innermost of :data:`IDLE_KINDS` open on the loop's thread during each
    idle part; ``train.step`` is a step's unspanned time.
    """
    spans = {int(r["step"]): r for r in records if r["name"] == "train.step"}
    pairs = sorted(set(spans) & set(xp["steps"]))
    out = {"xplane": xp["path"], "pairs": len(pairs)}
    if not pairs:
        return out
    offsets = [xp["steps"][k][0] - spans[k]["ts"] * 1e9 for k in pairs]
    offset = round(statistics.median(offsets))
    out.update(offset_ns=offset, spread_ns=round(max(offsets) - min(offsets)))
    tid = spans[pairs[0]]["tid"]
    segments = _innermost(sorted(
        (round(r["ts"] * 1e9) + offset, round((r["ts"] + r["dur"]) * 1e9) + offset,
         r["name"])
        for r in records if r["tid"] == tid and r["name"] in IDLE_KINDS
    ))
    starts = [s for s, _, _ in segments]
    windows = [xp["steps"][k] for k in pairs]
    idle: dict[str, float] = {}
    busy_ns = 0
    for ops in xp["devices"].values():
        busy = _union(ops)
        gaps = _subtract(windows, busy)
        busy_ns += sum(e - s for s, e in windows) - sum(e - s for s, e in gaps)
        for s, e in gaps:
            covered = 0.0
            for i in range(max(bisect.bisect_right(starts, s) - 1, 0), len(segments)):
                ss, se, name = segments[i]
                if ss >= e:
                    break
                part = min(e, se) - max(s, ss)
                if part > 0:
                    idle[name] = idle.get(name, 0.0) + part
                    covered += part
            if e - s > covered:
                idle["train.step"] = idle.get("train.step", 0.0) + (e - s - covered)
    n = max(len(xp["devices"]), 1)
    out.update(
        window_s=sum(e - s for s, e in windows) / 1e9,
        busy_s=busy_ns / n / 1e9,
        idle_s={k: v / n / 1e9 for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
    )
    return out


def check(trace_dir: str, *, min_coverage: float = 0.9,
          xplane: str | None = None) -> list[str]:
    """Validate a traced run's dumps; returns a list of failures (empty=OK).

    With ``xplane``, the ``train.step`` spans must also pair with its
    profiler steps, their offsets spreading at most ``MAX_SPREAD_NS``.
    """
    return _check(trace_dir, min_coverage, xplane)[0]


def _check(trace_dir, min_coverage, xplane) -> tuple[list[str], dict | None]:
    """:func:`check`'s failures and the analysis it made (None if none)."""
    failures: list[str] = []
    try:
        traces = load_traces(trace_dir)
    except OSError as exc:
        return [f"cannot read {trace_dir!r}: {exc}"], None
    if not traces:
        return [f"no trace-rank*.jsonl files under {trace_dir!r}"], None
    for rank, tr in sorted(traces.items()):
        recs = tr["records"]
        if not recs:
            failures.append(f"rank {rank}: empty trace")
            continue
        last_by_tid: dict[str, float] = {}
        for i, r in enumerate(recs):
            if not all(k in r for k in ("name", "ts", "dur", "step", "tid")):
                failures.append(f"rank {rank}: record {i} missing fields")
                break
            if r["dur"] < 0:
                failures.append(
                    f"rank {rank}: record {i} ({r['name']}) has dur < 0"
                )
            # records within one thread's ring are appended in time order;
            # the dump interleaves threads but must preserve that order.
            prev = last_by_tid.get(r["tid"])
            if prev is not None and r["ts"] < prev:
                failures.append(
                    f"rank {rank}: non-monotonic timestamps on {r['tid']}"
                )
                break
            last_by_tid[r["tid"]] = r["ts"]
        if _count_by(recs, ("chunk.read",)) == 0:
            failures.append(f"rank {rank}: no chunk.read spans recorded")
        if _count_by(recs, ("step", "train.step")) == 0:
            failures.append(f"rank {rank}: no step or train.step spans recorded")
    if len(traces) > 1:
        total_barrier = sum(
            _sum_by(tr["records"], ("barrier.wait",))
            for tr in traces.values()
        )
        if total_barrier <= 0.0:
            failures.append("multi-rank run recorded zero barrier.wait time")
    try:
        rep = analyze(trace_dir, xplane)
    except (OSError, KeyError, ValueError) as exc:
        failures.append(f"analyze failed: {exc}")
        return failures, None
    cov = rep["cluster"]["coverage"]
    if rep["cluster"]["steps"] and cov < min_coverage:
        failures.append(
            f"step coverage {cov:.3f} < {min_coverage} — the tiling "
            "sections no longer account for the rank loop"
        )
    for rank, row in sorted(rep["ranks"].items()):
        cov = row["train"]["coverage"]
        if row["train"]["steps"] and cov < min_coverage:
            failures.append(
                f"rank {rank}: train.step coverage {cov:.3f} < {min_coverage}"
                " — the train.* sections no longer tile Trainer.run"
            )
    if xplane is not None:
        xp = rep["xplane"]
        if not xp["pairs"]:
            failures.append("no train.step span pairs with a profiler step")
        elif xp["spread_ns"] > MAX_SPREAD_NS:
            failures.append(
                f"clock anchors spread {xp['spread_ns'] / 1e3:.1f} us > "
                f"{MAX_SPREAD_NS / 1e3:.0f} us"
            )
    return failures, rep


def _render(rep: dict) -> str:
    lines = [
        f"trace: {rep['trace_dir']}  ({rep['num_ranks']} rank(s), "
        f"{rep['cluster']['steps']} step spans, "
        f"coverage {rep['cluster']['coverage']:.1%})",
        "",
        f"{'rank':>4} {'steps':>6} {'ms/step':>9} "
        + "".join(f"{s:>10}" for s in STAGES)
        + f"{'coverage':>10}",
    ]
    for rank, row in sorted(rep["ranks"].items(), key=lambda kv: int(kv[0])):
        lines.append(
            f"{rank:>4} {row['steps']:>6} {row['step_ms_mean']:>9.3f} "
            + "".join(
                f"{row['stage_ms_per_step'][s]:>10.3f}" for s in STAGES
            )
            + f"{row['coverage']:>10.1%}"
        )
    lines += [
        "",
        "cluster ms/step by stage: " + ", ".join(
            f"{k}={v}" for k, v in rep["cluster"]["stage_ms_per_step"].items()
        ),
        f"barrier overhead: {rep['cluster']['barrier_ms_per_step']} ms/step",
    ]
    detail = {
        k: round(sum(
            r["detail_ms_total"][k] for r in rep["ranks"].values()
        ), 3)
        for k in DETAIL
    }
    counts = {
        k: sum(r["counts"][k] for r in rep["ranks"].values()) for k in COUNTS
    }
    lines.append(
        "detail ms total: " + ", ".join(f"{k}={v}" for k, v in detail.items())
    )
    lines.append(
        "event counts: " + ", ".join(f"{k}={v}" for k, v in counts.items())
    )
    for rank, row in sorted(rep["ranks"].items(), key=lambda kv: int(kv[0])):
        t = row["train"]
        if t["steps"]:
            lines.append(
                f"rank {rank} Trainer.run: {t['steps']} steps, "
                f"{t['step_ms_mean']} ms/step, " + ", ".join(
                    f"{k}={v}" for k, v in t["stage_ms_per_step"].items()
                ) + f", coverage {t['coverage']:.1%}"
            )
    xp = rep.get("xplane")
    if xp is not None:
        lines.append(f"xplane: {xp['xplane']}, {xp['pairs']} step anchors")
        if xp["pairs"]:
            lines.append(
                f"clock offset {xp['offset_ns']} ns, spread "
                f"{xp['spread_ns'] / 1e3:.1f} us; steps {xp['window_s']:.3f} s"
                f", device busy {xp['busy_s']:.3f} s"
            )
            lines.append("device idle s by program span: " + (", ".join(
                f"{k}={v:.3f}" for k, v in xp["idle_s"].items()
            ) or "(no device plane)"))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.obs.report",
        description="per-step time attribution from a traced run's dumps",
    )
    ap.add_argument("trace_dir", help="directory holding trace-rank*.jsonl")
    ap.add_argument("--json", action="store_true",
                    help="emit the full analysis as JSON instead of a table")
    ap.add_argument("--check", action="store_true",
                    help="validate the trace (exit 1 on any failure)")
    ap.add_argument("--min-coverage", type=float, default=0.9,
                    help="--check: minimum accounted step-time fraction")
    ap.add_argument("--xplane", default=None, metavar="PATH",
                    help="a JAX profiler .xplane.pb of the same run (or a "
                         "directory holding one): anchor the spans to its "
                         "clock and split the device's idle time by span")
    args = ap.parse_args(argv)
    if args.check:
        failures, rep = _check(args.trace_dir, args.min_coverage, args.xplane)
        if failures:
            for f in failures:
                print(f"CHECK FAIL: {f}", file=sys.stderr)
            return 1
        msg = f"trace OK: {rep['num_ranks']} rank(s)"
        if rep["cluster"]["steps"]:
            msg += (f", {rep['cluster']['steps']} steps, "
                    f"coverage {rep['cluster']['coverage']:.1%}, "
                    f"barrier {rep['cluster']['barrier_ms_per_step']} ms/step")
        train = sum(r["train"]["steps"] for r in rep["ranks"].values())
        if train:
            msg += f", {train} Trainer.run steps"
        if args.xplane is not None:
            msg += (f", {rep['xplane']['pairs']} clock anchors spread "
                    f"{rep['xplane']['spread_ns'] / 1e3:.1f} us")
        print(msg)
        return 0
    rep = analyze(args.trace_dir, args.xplane)
    print(json.dumps(rep, indent=1, sort_keys=True) if args.json
          else _render(rep))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
