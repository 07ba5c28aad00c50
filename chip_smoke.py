#!/usr/bin/env python3
"""Bring-up check on the chip: train and serve deepseek-7b at its published widths.

    python chip_smoke.py              # one TPU: train 6 steps, serve 4 requests
    python chip_smoke.py --chips 4    # four TPUs: dp=4 meshfeed vs one chip

The model is ``deepseek-7b`` with every width as published (d_model 4096,
32 heads x 128, d_ff 11008, bf16), cut to 2 layers and a quarter of the
vocabulary (25,600 of 102,400, one chip's share of a head split over 4
chips).  Weights are random, made from ``--seed``.

One chip: the training phase drives ``repro.api.Session`` (FleetSpec.demo:
one host + storage workers on the synthetic backend) at seq 2048 with adamw;
the serving phase sends 4 greedy requests (256-token prompts, 32 new
tokens) through ``ServeEngine`` with the trained weights.

``--chips 4`` runs only the multi-chip path and what it is compared with:
the same config, seed and global batch trained once over a (4, 1)
data x model mesh through the meshfeed backend and once on one chip.

Everything runs in this one process (a chip belongs to one process).  The
script exits non-zero, and prints no result line, when JAX finds no TPU or
any check fails.  Its last line on success is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import warnings
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import FleetSpec, ServeSession, Session, SessionConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.models.api import get_model  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.serve import EngineConfig  # noqa: E402
from repro.storage import DataConfig  # noqa: E402

SEQ = 2048
TRAIN_STEPS = 6
DP_STEPS = 4
N_REQUESTS = 4
PROMPT_LEN = 256
NEW_TOKENS = 32
# 1 host + 3 storage workers at 2 rows each: 8 global rows, divisible by
# 4 chips.  12 rows (1 host + 2 workers at 4) need 18.1 GiB on one v5e.
N_STORAGE_WORKERS = 3
MAX_ROWS_PER_WORKER = 2
# step 0 loss of a random-init LM sits at ln(vocab); allow 10% either way
INIT_LOSS_RTOL = 0.10
# dp=4 vs one chip: bf16 matmuls (8-bit mantissa, eps 2^-8) summed in a
# different order across 4 chips move each loss by well under 1%
DP_LOSS_RTOL = 1e-2
# greedy tokens must match the teacher-forced forward wherever its top-1
# logit leads the top-2 by more than bf16 noise between the two paths
DECISIVE_MARGIN = 0.25

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CONSTRAINT_WARNING = "sharding constraint for logical axes"

Log = Callable[[str], None]


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def chip_config() -> ModelConfig:
    """deepseek-7b at published widths, cut to 2 layers and vocab / 4."""
    full = get_config("deepseek-7b")
    return full.with_(n_layers=2, vocab=full.vocab // 4)


class CompileClock:
    """Backend compile seconds reported by JAX while the context is open."""

    def __init__(self):
        self.seconds: List[float] = []

    def __call__(self, event: str, duration: float, **_: Any) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds.append(duration)

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self)


def make_session(
    cfg: ModelConfig, *, steps: int, seq: int, storage: str = "synthetic",
    seed: int = 0,
) -> Session:
    spec = FleetSpec.demo(
        N_STORAGE_WORKERS,
        host_max_batch=MAX_ROWS_PER_WORKER, csd_max_batch=MAX_ROWS_PER_WORKER,
    )
    if storage != "synthetic":
        spec = spec.with_storage(storage)
    return Session(
        model=get_model(cfg),
        optimizer=adamw(),
        fleet=spec,
        data=DataConfig(vocab=cfg.vocab, seq_len=seq, seed=seed),
        config=SessionConfig(total_steps=steps, seed=seed),
        shards=spec.shards(private_per_worker={"csd": 256}, public=4096),
    )


def _row_probe(params) -> List[np.ndarray]:
    """One row of every parameter leaf, copied to the host."""
    return [
        np.asarray(leaf[(0,) * (leaf.ndim - 1)], np.float32)
        for leaf in jax.tree_util.tree_leaves(params)
    ]


def train_phase(
    cfg: ModelConfig, *, steps: int = TRAIN_STEPS, seq: int = SEQ,
    storage: str = "synthetic", seed: int = 0, log: Log = print,
) -> Dict[str, Any]:
    """Train through ``Session`` and check the run; returns what it saw."""
    session = make_session(cfg, steps=steps, seq=seq, storage=storage,
                           seed=seed)
    tune = session.tune()
    plan = session.shard()
    log(f"train[{storage}]: rows={plan.global_rows} "
        f"(groups {tune.schedule.group_batches}, "
        f"valid {tune.schedule.valid_rows}) seq={seq} "
        f"mesh={dict(plan.mesh.shape)} devices={plan.n_devices}")
    params, opt_state = session.init_state()
    before = _row_probe(params)
    with warnings.catch_warnings(), CompileClock() as clock:
        # a skipped activation constraint silently replicates a tensor
        warnings.filterwarnings(
            "error", message=CONSTRAINT_WARNING, category=RuntimeWarning
        )
        report = session.run(params, opt_state=opt_state, steps=steps)
    compiled = session.compile()
    losses = [h["loss"] for h in report.history]
    step_times = [h["step_time"] for h in report.history]
    log(f"train[{storage}]: backend compiles {len(clock.seconds)}, "
        f"{sum(clock.seconds):.2f} s total, largest "
        f"{max(clock.seconds, default=0.0):.2f} s")
    log(f"train[{storage}]: step seconds "
        + " ".join(f"{t:.4f}" for t in step_times))
    log(f"train[{storage}]: losses " + " ".join(f"{x:.5f}" for x in losses))
    log(f"train[{storage}]: compile_count={report.compile_count}")

    require(len(losses) == steps, f"ran {len(losses)} of {steps} steps")
    require(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    ln_v = math.log(cfg.vocab)
    require(abs(losses[0] - ln_v) <= INIT_LOSS_RTOL * ln_v,
            f"step 0 loss {losses[0]:.4f} not within "
            f"{INIT_LOSS_RTOL:.0%} of ln({cfg.vocab})={ln_v:.4f}")
    after = _row_probe(report.params)
    changed = sum(not np.array_equal(a, b) for a, b in zip(before, after))
    log(f"train[{storage}]: {changed}/{len(before)} parameter leaves changed")
    require(changed > 0, "no parameter changed")
    require(report.compile_count == 1,
            f"compile_count={report.compile_count}, expected 1")
    return {
        "losses": losses,
        "step_times": step_times,
        "compile_seconds": list(clock.seconds),
        "compile_count": report.compile_count,
        "params": report.params,
        "in_shardings": compiled.in_shardings,
        "n_devices": plan.n_devices,
    }


def serve_phase(
    cfg: ModelConfig, params, *, n_requests: int = N_REQUESTS,
    prompt_len: int = PROMPT_LEN, new_tokens: int = NEW_TOKENS,
    seed: int = 0, log: Log = print,
) -> Dict[str, Any]:
    """Greedy requests through ``ServeEngine``, checked against a forward."""
    model = get_model(cfg)
    engine = ServeSession(model=model, params=params).engine(
        EngineConfig(max_slots=n_requests, max_len=prompt_len + new_tokens)
    )
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, size=(n_requests, prompt_len))
    t0 = time.perf_counter()
    rids = [engine.submit(p.tolist(), max_new_tokens=new_tokens)
            for p in prompts]
    engine.run_to_completion()
    outs = [engine.output(r) for r in rids]
    log(f"serve: {n_requests} requests x {new_tokens} tokens in "
        f"{time.perf_counter() - t0:.3f} s over {engine.steps} engine steps "
        f"(includes compiles)")
    for o in outs:
        require(o.finish_reason == "length" and len(o.tokens) == new_tokens,
                f"request {o.request_id}: {len(o.tokens)} tokens, "
                f"finish {o.finish_reason!r}")
        require(all(0 <= t < cfg.vocab for t in o.tokens),
                f"request {o.request_id}: token outside the vocabulary")

    # reference: one teacher-forced forward over prompt + generated tokens
    gen = np.asarray([o.tokens for o in outs], np.int32)
    seqs = jnp.asarray(np.concatenate([prompts, gen], axis=1), jnp.int32)
    logits, _ = jax.jit(model.forward)(params, seqs)
    # the logit at position p predicts token p + 1
    ref = np.asarray(logits[:, prompt_len - 1:-1], np.float32)
    require(ref.shape == (n_requests, new_tokens, cfg.vocab),
            f"forward logits shape {ref.shape}")
    require(bool(np.isfinite(ref).all()), "non-finite forward logits")
    top2 = np.sort(ref, axis=-1)[..., -2:]
    decisive = (top2[..., 1] - top2[..., 0]) > DECISIVE_MARGIN
    agree = ref.argmax(-1) == gen
    log(f"serve: greedy tokens agree with the forward at "
        f"{int(agree.sum())}/{agree.size} positions, "
        f"{int(agree[decisive].sum())}/{int(decisive.sum())} decisive")
    require(bool(agree[decisive].all()),
            "greedy token differs from the forward's argmax at a position "
            f"where it leads by more than {DECISIVE_MARGIN}")
    log("serve: first tokens " + " ".join(str(o.tokens[0]) for o in outs))
    return {"tokens": gen.tolist(), "agree": int(agree.sum())}


def dp_parity_phase(cfg: ModelConfig, *, steps: int = DP_STEPS,
                    seq: int = SEQ, seed: int = 0, log: Log = print) -> None:
    """The same global batches on one chip and over a dp=4 meshfeed mesh."""
    one = train_phase(cfg, steps=steps, seq=seq, storage="synthetic",
                      seed=seed, log=log)
    one_losses = one["losses"]
    del one
    dp = train_phase(cfg, steps=steps, seq=seq, storage="meshfeed",
                     seed=seed, log=log)
    tokens_sharding = dp["in_shardings"][2]["tokens"]
    log(f"dp: batch sharding {tokens_sharding.spec} over "
        f"{len(tokens_sharding.device_set)} devices")
    require(dp["n_devices"] == 4 and len(tokens_sharding.device_set) == 4,
            f"dp step spans {len(tokens_sharding.device_set)} devices, not 4")
    rel = [abs(a - b) / abs(b) for a, b in zip(dp["losses"], one_losses)]
    log("dp: relative loss gap per step "
        + " ".join(f"{r:.2e}" for r in rel))
    require(max(rel) <= DP_LOSS_RTOL,
            f"dp=4 losses {dp['losses']} differ from one chip "
            f"{one_losses} by more than {DP_LOSS_RTOL:.0e}")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    cache = configure_compile_cache()
    cfg = chip_config()
    full = get_config("deepseek-7b")
    print(f"device: {devices[0].device_kind} x {len(devices)}; "
          f"compile cache {cache}")
    print(f"model: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}x"
          f"{cfg.head_dim} kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"dtype={jnp.dtype(cfg.dtype).name}")
    print(f"cut: n_layers {full.n_layers} -> {cfg.n_layers}, vocab "
          f"{full.vocab} -> {cfg.vocab}; params {cfg.param_count():,}")

    if args.chips == 4:
        dp_parity_phase(cfg, seed=args.seed)
    else:
        trained = train_phase(cfg, seed=args.seed)
        params = trained.pop("params")
        del trained
        serve_phase(cfg, params, seed=args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
