"""Serving launcher: batched prefill + decode over the KV/SSM cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --batch 4 --prompt-len 16 --gen 32 [--kv-int8]

With ``--data-tier host:port`` the replica pulls its inputs through the
multi-tenant buffer tier (DESIGN.md §12) instead of synthesizing prompts:
it attaches as ``--tenant``/``--token``, reads ``--batch`` samples by id
starting at ``--first-id``, and maps the raw rows to prompts
deterministically.  Any server in the cluster works as the entry point —
misses are residency-routed to the peer holding the sample before falling
back to the PFS.  Without the flag the synthetic-prompt path is unchanged.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import encdec, lm
from repro.serve.engine import ServeEngine


def _parse_endpoint(text: str) -> tuple[str, int]:
    host, sep, port = text.rpartition(":")
    if not sep or not host:
        raise argparse.ArgumentTypeError(
            f"--data-tier wants host:port, got {text!r}"
        )
    return host, int(port)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument(
        "--data-tier", type=_parse_endpoint, default=None, metavar="HOST:PORT",
        help="pull prompts from a buffer-tier server instead of synthesizing",
    )
    ap.add_argument("--tenant", type=int, default=1,
                    help="tenant id for --data-tier attach")
    ap.add_argument("--token", default="",
                    help="tenant auth token for --data-tier attach")
    ap.add_argument("--first-id", type=int, default=0,
                    help="first sample id to read from the tier")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.kv_int8:
        cfg = cfg.replace(kv_cache_dtype="int8")

    key = jax.random.PRNGKey(0)
    init = encdec.init_encdec if cfg.family == "encdec" else lm.init_lm
    params = init(key, cfg)
    engine = ServeEngine(cfg, params, max_len=args.prompt_len + args.gen + 1)

    rng = np.random.default_rng(0)
    source = None
    if cfg.family == "encdec":
        source = rng.standard_normal(
            (args.batch, cfg.source_len, cfg.d_model)).astype(np.float32)

    if args.data_tier is not None:
        if cfg.family == "encdec":
            ap.error("--data-tier drives decoder-only prompts; "
                     "encdec archs need the synthetic source path")
        from repro.serve.datatier import DataTierClient

        client = DataTierClient(
            {0: args.data_tier}, tenant=args.tenant, token=args.token
        )
        try:
            ids = np.arange(
                args.first_id, args.first_id + args.batch, dtype=np.int64
            )
            t0 = time.perf_counter()
            out, served = engine.generate_from_tier(
                client, ids, args.gen, prompt_len=args.prompt_len
            )
            dt = time.perf_counter() - t0
            print(f"tier served {int(served.sum())}/{ids.size} samples; "
                  f"client stats: {client.stats()}")
        finally:
            client.close()
    else:
        prompts = rng.integers(
            0, cfg.vocab_size, size=(args.batch, args.prompt_len)
        ).astype(np.int32)
        t0 = time.perf_counter()
        out = engine.generate(prompts, args.gen, source=source)
        dt = time.perf_counter() - t0

    toks = out.shape[0] * args.gen
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s on {jax.default_backend()})")
    print("first sequence:", out[0][:16].tolist())


if __name__ == "__main__":
    main()
