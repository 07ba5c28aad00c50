"""Serving driver: one-shot generate, engine streaming, or load generation.

Three modes over the same model + params:

  * ``oneshot``  — :class:`repro.api.ServeSession.generate` (prefill + decode
    loop, the parity oracle)
  * ``engine``   — :class:`repro.serve.ServeEngine` with streaming events
    printed as they arrive (continuous batching visible on the console)
  * ``loadgen``  — :func:`repro.serve.run_load` closed-loop synthetic users;
    prints the req/s + latency-percentile report

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b --tokens 16
  PYTHONPATH=src python -m repro.launch.serve --mode engine --requests 8
  PYTHONPATH=src python -m repro.launch.serve --mode loadgen --requests 64
"""
from __future__ import annotations

import argparse
import json
import sys

import jax

from repro.api import ServeSession
from repro.configs import ARCHS, get_config, smoke_config
from repro.launch.compile_cache import configure_compile_cache
from repro.models.api import get_model
from repro.serve import EngineConfig, SamplingParams, run_load


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b", choices=ARCHS)
    ap.add_argument("--mode", default="oneshot",
                    choices=("oneshot", "engine", "loadgen"))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # engine / loadgen
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="loadgen: common prompt prefix length (prefix cache)")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    configure_compile_cache()

    cfg = get_config(args.arch) if args.full_config else smoke_config(args.arch)
    model = get_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params, _ = model.init_params(key=key)
    serve = ServeSession(model=model, params=params)
    sampling = SamplingParams(
        temperature=args.temperature, top_k=args.top_k, seed=args.seed,
    )

    if args.mode == "oneshot":
        B, P = args.batch, args.prompt_len
        prompt = jax.random.randint(key, (B, P), 0, cfg.vocab)
        out = serve.generate(prompt, max_new_tokens=args.tokens,
                             sampling=sampling)
        print(f"arch={cfg.name} batch={B} prompt={P} decoded={args.tokens}")
        print(f"decode throughput: {out.decode_tok_s:.1f} tok/s "
              f"({out.ms_per_step:.1f} ms/step)")
        print("sample token ids:", out.tokens[0].tolist())
        return 0

    max_len = args.max_len or (args.prompt_len + args.tokens + 8)
    engine = serve.engine(EngineConfig(max_slots=args.slots, max_len=max_len))

    if args.mode == "engine":
        import numpy as np
        rng = np.random.default_rng(args.seed)
        for _ in range(args.requests):
            prompt = rng.integers(0, cfg.vocab, size=(args.prompt_len,))
            engine.submit(prompt.tolist(), max_new_tokens=args.tokens,
                          sampling=sampling)
        while engine.has_work():
            for ev in engine.step():
                tag = f" [{ev.finish_reason}]" if ev.done else ""
                print(f"req={ev.request_id} #{ev.index} tok={ev.token}{tag}")
        stats = engine.prefix_cache_stats
        print(f"steps={engine.steps} decoded={engine.tokens_decoded} "
              f"prefix_hit_rate={stats.hit_rate:.3f}")
        return 0

    report = run_load(
        engine, n_requests=args.requests, prompt_len=args.prompt_len,
        max_new_tokens=args.tokens, shared_prefix_len=args.shared_prefix,
        seed=args.seed,
    )
    print(json.dumps(report.to_json(), indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
