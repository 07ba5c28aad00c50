"""Equivalence of the shard_map group-local EP dispatch vs the dense path.

Guards the §Perf headline optimization: the group-local dispatch
(models/moe.py::_moe_mlp_local) must match the GSPMD-auto dense reference
bit-near-exactly — forward AND gradients — on a real (data, model) mesh.
Runs in a subprocess with 4 fake devices (the main process stays 1-device).
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, n: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_local_dispatch_matches_dense_forward_and_grad():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh
        from repro.configs import smoke_config
        from repro.models import moe
        from repro.models.api import get_model
        from repro.optim import adamw
        from repro.train.steps import make_train_step

        # capacity_factor high enough that no token drops: paths must agree
        cfg = smoke_config('qwen3-moe-30b-a3b').with_(capacity_factor=8.0)
        m = get_model(cfg)
        key = jax.random.PRNGKey(0)
        params, _ = m.init_params(key=key)
        batch = {
            'tokens': jax.random.randint(key, (4, 16), 0, cfg.vocab),
            'labels': jax.random.randint(key, (4, 16), 0, cfg.vocab),
            'loss_mask': jnp.ones((4, 16), jnp.float32),
        }
        opt = adamw()
        step = make_train_step(m, opt, lambda s: 1e-3)

        moe.MOE_IMPL = 'dense'
        ref, aux_ref = jax.jit(lambda p, t: m.forward(p, t))(params, batch['tokens'])
        _, _, m1 = jax.jit(step)(params, opt.init(params), batch)

        mesh = make_mesh((2, 2), ('data', 'model'))
        moe.MOE_IMPL = 'auto'
        with jax.set_mesh(mesh):
            out, aux = jax.jit(lambda p, t: m.forward(p, t))(params, batch['tokens'])
            _, _, m2 = jax.jit(step)(params, opt.init(params), batch)

        ferr = float(jnp.max(jnp.abs(out - ref)))
        aerr = float(jnp.abs(aux - aux_ref))
        lerr = abs(float(m1['loss']) - float(m2['loss']))
        gerr = abs(float(m1['grad_norm']) - float(m2['grad_norm']))
        print('ERRS', ferr, aerr, lerr, gerr)
        assert ferr < 5e-4, ferr   # scatter-add ordering tolerance
        assert aerr < 1e-6, aerr
        assert lerr < 1e-5, lerr
        assert gerr < 1e-2, gerr
    """)
    assert "ERRS" in out


def test_local_dispatch_matches_dense_under_capacity_overflow():
    """Tokens ARE dropped: with capacity_factor=0.01 the per-expert capacity
    floors at 8 slots for 512 token-copies.  On a pure model-parallel mesh
    (n_groups == 1) the local path's per-group capacity equals the dense C,
    so which copies drop — and hence the output — must match exactly."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh
        from repro.configs import smoke_config
        from repro.models import moe
        from repro.models.api import get_model

        cfg = smoke_config('qwen3-moe-30b-a3b').with_(capacity_factor=0.01)
        assert moe.expert_capacity(256, cfg.n_experts, cfg.experts_per_token,
                                   cfg.capacity_factor) == 8
        m = get_model(cfg)
        key = jax.random.PRNGKey(3)
        params, _ = m.init_params(key=key)
        tokens = jax.random.randint(key, (4, 64), 0, cfg.vocab)

        moe.MOE_IMPL = 'dense'
        ref, aux_ref = jax.jit(lambda p, t: m.forward(p, t))(params, tokens)
        # sanity: drops really happen — uncapped output must differ
        big = smoke_config('qwen3-moe-30b-a3b').with_(capacity_factor=8.0)
        ref_big, _ = jax.jit(
            lambda p, t: get_model(big).forward(p, t))(params, tokens)
        assert float(jnp.max(jnp.abs(ref - ref_big))) > 1e-3

        mesh = make_mesh((1, 4), ('data', 'model'))
        moe.MOE_IMPL = 'auto'
        with jax.set_mesh(mesh):
            out, aux = jax.jit(lambda p, t: m.forward(p, t))(params, tokens)
        ferr = float(jnp.max(jnp.abs(out - ref)))
        aerr = float(jnp.abs(aux - aux_ref))
        print('ERRS', ferr, aerr)
        assert ferr < 5e-4, ferr
        assert aerr < 1e-6, aerr
    """)
    assert "ERRS" in out


def test_local_dispatch_over_model_batch_layout():
    """The DP-attention layout (batch sharded over model too): the explicit
    all-gather + psum_scatter path must also match."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh
        from repro.configs import smoke_config
        from repro.distributed.sharding import make_rules, set_rules
        from repro.models import moe
        from repro.models.api import get_model

        cfg = smoke_config('qwen3-moe-30b-a3b').with_(capacity_factor=8.0)
        m = get_model(cfg)
        key = jax.random.PRNGKey(1)
        params, _ = m.init_params(key=key)
        tokens = jax.random.randint(key, (4, 16), 0, cfg.vocab)

        moe.MOE_IMPL = 'dense'
        ref, _ = jax.jit(lambda p, t: m.forward(p, t))(params, tokens)

        mesh = make_mesh((2, 2), ('data', 'model'))
        rules = make_rules(extra={'batch': ('pod', 'data', 'model')})
        set_rules(rules)
        moe.MOE_IMPL = 'auto'
        with jax.set_mesh(mesh):
            out, _ = jax.jit(lambda p, t: m.forward(p, t))(params, tokens)
        set_rules(make_rules())
        err = float(jnp.max(jnp.abs(out - ref)))
        print('ERR', err)
        assert err < 5e-4, err
    """)
    assert "ERR" in out
