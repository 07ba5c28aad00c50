"""End-to-end training driver (CPU-runnable; production flags mirror the pods).

Runs the full Stannis pipeline through the staged Session API: Algorithm-1
tune (analytic or measured), Eq.-1 epoch plan, privacy placement, then real
training steps with checkpointing — the same code path the pods run, sized
for this host.

  PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-moe-30b-a3b \\
      --steps 20 --csds 4 --measured-tune

Cluster mode launches N worker PROCESSES feeding one global mesh (see
:mod:`repro.launch.cluster`); each provisions only its own dp-groups'
storage devices and feeds only its addressable mesh slice:

  PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \\
      --steps 20 --csds 3 --cluster-processes 2 --cluster-local-devices 4
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp

from repro.api import FleetSpec, Session, SessionConfig
from repro.configs import ARCHS, get_config, smoke_config
from repro.core.tuner import measured_benchmark
from repro.launch.compile_cache import configure_compile_cache
from repro.storage import DataConfig
from repro.models.api import get_model
from repro.optim import adamw, sgd_momentum


def train_session_factory(
    *,
    arch: str = "deepseek-7b",
    steps: int = 30,
    seq: int = 64,
    csds: int = 2,
    full_config: bool = False,
    optimizer: str = "adamw",
    checkpoint_dir=None,
    seed: int = 0,
    cluster_processes: int = 1,
) -> Session:
    """The driver's session, importable by name from cluster workers."""
    cfg = get_config(arch) if full_config else smoke_config(arch)
    spec = FleetSpec.demo(
        csds, host_tput=80.0, csd_tput=10.0,
        host_max_batch=64, csd_max_batch=8,
        host_idle=100.0, csd_idle=1.5,
    )
    if cluster_processes > 1:
        spec = spec.with_cluster(processes=cluster_processes)
    return Session(
        model=get_model(cfg),
        optimizer=adamw() if optimizer == "adamw" else sgd_momentum(),
        fleet=spec,
        data=DataConfig(vocab=cfg.vocab, seq_len=seq, seed=seed),
        config=SessionConfig(
            total_steps=steps,
            checkpoint_dir=checkpoint_dir,
            seed=seed,
        ),
        shards=spec.shards(private_per_worker={"csd": 256}, public=65536),
    )


def _run_cluster(args) -> int:
    from repro.core.topology import ClusterSpec
    from repro.launch.cluster import run_cluster

    result = run_cluster(
        ClusterSpec(
            processes=args.cluster_processes,
            local_devices=args.cluster_local_devices,
        ),
        "repro.launch.train:train_session_factory",
        {
            "arch": args.arch, "steps": args.steps, "seq": args.seq,
            "csds": args.csds, "full_config": args.full_config,
            "optimizer": args.optimizer,
            "checkpoint_dir": args.checkpoint_dir, "seed": args.seed,
            "cluster_processes": args.cluster_processes,
        },
    )
    for rec in result.records:
        print(
            f"[proc {rec['process']}/{rec['n_processes']} {rec['mode']}] "
            f"workers={rec['local_workers']} "
            f"devices={rec['receipt']['devices'] if rec['receipt'] else '-'} "
            f"local_rows={rec['receipt']['rows_local'] if rec['receipt'] else '-'}"
            f"/{rec['global_rows']} compiles={rec['compile_count']}"
        )
        if rec["losses"]:
            print(f"  loss {rec['losses'][0]:.4f} -> {rec['losses'][-1]:.4f} "
                  f"addressable_only={rec['addressable_only']}")
    if not result.ok:
        print(f"cluster failed: returncodes={result.returncodes} "
              f"(worker logs under {result.run_dir})", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b", choices=ARCHS)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--csds", type=int, default=2)
    ap.add_argument("--full-config", action="store_true",
                    help="use the published dims (default: reduced smoke dims)")
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgd"])
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--measured-tune", action="store_true",
                    help="tune with real step timings instead of the analytic model")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cluster-processes", type=int, default=1,
                    help="launch N worker processes feeding one global mesh")
    ap.add_argument("--cluster-local-devices", type=int, default=0,
                    help="force this many (fake CPU) devices per process")
    args = ap.parse_args(argv)
    configure_compile_cache()

    if args.cluster_processes > 1:
        return _run_cluster(args)

    cfg = get_config(args.arch) if args.full_config else smoke_config(args.arch)
    model = get_model(cfg)
    spec = FleetSpec.demo(
        args.csds, host_tput=80.0, csd_tput=10.0,
        host_max_batch=64, csd_max_batch=8,
        host_idle=100.0, csd_idle=1.5,
    )
    fleet = spec.build()
    shards = spec.shards(private_per_worker={"csd": 256}, public=65536)

    benchmark = None
    if args.measured_tune:
        # time the real jitted step at each candidate batch; throughput ratios
        # between classes come from the analytic fleet (single-host stand-in)
        params, _ = model.init_params(key=jax.random.PRNGKey(args.seed))
        opt = adamw() if args.optimizer == "adamw" else sgd_momentum()
        from repro.train.steps import make_train_step

        step = jax.jit(make_train_step(model, opt, lambda s: 1e-3))

        def run_at(batch: int):
            toks = jnp.zeros((batch, args.seq), jnp.int32)
            b = {
                "tokens": toks, "labels": toks,
                "loss_mask": jnp.ones((batch, args.seq), jnp.float32),
            }
            st = opt.init(params)
            out = step(params, st, b)
            jax.block_until_ready(out[2]["loss"])

        bench_core = measured_benchmark({"host": run_at, "csd": run_at})

        def benchmark(name: str, batch: int) -> float:
            t = bench_core("host", batch)
            # model CSD-class slowness relative to the host measurement
            rel = fleet.by_name("host").peak_throughput / fleet.by_name(name).peak_throughput
            return t * rel

    session = Session(
        model=model,
        optimizer=adamw() if args.optimizer == "adamw" else sgd_momentum(),
        fleet=fleet,
        data=DataConfig(vocab=cfg.vocab, seq_len=args.seq, seed=args.seed),
        config=SessionConfig(
            total_steps=args.steps,
            checkpoint_dir=args.checkpoint_dir,
            seed=args.seed,
        ),
        shards=shards,
        benchmark=benchmark,
    )

    tune_plan = session.tune()
    epoch = session.plan()
    shard_plan = session.shard()
    print(f"arch={cfg.name} params={cfg.param_count():,}")
    print(f"tuned batches: {tune_plan.batches} "
          f"(margin {tune_plan.result.margin:.0%}, "
          f"ref={tune_plan.result.reference_class})")
    print(f"schedule: groups={tune_plan.schedule.group_batches} "
          f"pad={tune_plan.schedule.pad_fraction:.1%}")
    print(f"epoch: {epoch.steps_per_epoch} steps, "
          f"imbalance {epoch.imbalance_steps()} steps")
    print(f"sharding: {shard_plan.describe()} "
          f"batch={shard_plan.batch['tokens'].spec}")

    session.callbacks.on_step(
        lambda i, m: print(
            f"  step {i:4d} loss {m['loss']:.4f} lr {m['lr']:.2e} "
            f"gnorm {m['grad_norm']:.2f} ({m['step_time']*1e3:.0f} ms)"
        ) if i % 5 == 0 else None
    )
    report = session.run()
    hist = report.history
    print(f"{report.steps_run} steps in {report.wall_time:.1f}s; "
          f"loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
