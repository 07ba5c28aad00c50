"""The sparse-expert reference (``bench/reference/moe.py``) and the
``qwen3moe-train-share`` cell, at a small size on the CPU.

A tiny copy of the cell's configuration keeps its layout (QK-norm, a router
over more experts than are held here, top-k, dropless) at a few thousand
parameters and 64-token rows.  In float32 the program's first steps through
``Session.run`` agree with the reference to rounding, with the published
aux weight and with one large enough to move the router.  The int8 control
fails the cell's ``grad_gap`` and ``change_gap``; half of the batch left out
of the timed step fails ``grad_gap``.  ``bench/flops.py`` counts the
configuration as the hand count does."""
import copy
import time

import jax
import jax.numpy as jnp
import pytest

from bench import flops, harness

WORKLOAD = "qwen3moe-train-share"
SEED = 2**33 + 17
# float32 on both sides: only the order of summation differs
LOSS_RTOL = 1e-5
NORM_GAP = 1e-4
TINY_MOE = dict(hidden_size=64, intermediate_size=32, moe_intermediate_size=32,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                vocab_size=256, num_experts=4, router_outputs=16,
                num_experts_per_tok=4, num_hidden_layers=2)


def _tiny(dtype="float32", **sizes):
    cell = harness.find_cell(WORKLOAD)
    cfg = dict(cell.config, **TINY_MOE)
    cfg.update(sizes, torch_dtype=dtype)
    cfg["program"] = dict(
        cfg["program"], n_experts=cfg["router_outputs"],
        experts_held=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        router_aux_weight=cfg["router_aux_loss_coef"])
    cell.config = cfg
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic["seq_len"] = 64
    return cell


def _program(cell, seed=SEED):
    _, _, _, _, rd = harness.setup_program(cell, seed, jax.devices()[:1],
                                           None)
    return rd


@pytest.mark.parametrize("coef", [0.001, 1.0])
def test_reference_follows_session_run(coef):
    cell = _tiny(router_aux_loss_coef=coef)
    rd = _program(cell)
    ref = harness.reference_module("moe")
    out = ref.train_readings(cell.config, cell.traffic, SEED, steps=3)
    assert harness.feed_mismatches(cell, ref, SEED, rd.batches) == 0
    g = harness.gaps(rd.losses, rd.grad_norms, rd.change_norms, out)
    assert all(g[f"loss_gap_{t}"] <= LOSS_RTOL for t in (1, 2, 3)), g
    assert g["grad_gap"] <= NORM_GAP and g["change_gap"] <= NORM_GAP, g


def test_reference_routes_over_every_router_output():
    """The routing counts span all of the router's outputs, not only the
    experts held here: k copies of every token, per layer."""
    cell = _tiny()
    ref = harness.reference_module("moe")
    params = ref.init_params(cell.config, ref.seed_key(SEED))
    toks = jnp.asarray(ref.step_rows(cell.config, cell.traffic, SEED, 0))
    s = ref.dims(cell.config)
    counts = ref.route_counts(cell.config, "f32", params, toks[:, :-1],
                              jnp.ones(toks.shape[0]))
    assert counts.shape == (s["L"], s["E"])
    assert float(counts.sum()) == s["L"] * toks.shape[0] * 64 * s["k"]


def test_control_fails_grad_and_change_limits():
    """In bfloat16, with a vocabulary of 4096 rows: as in the cell, most
    rows of the embedding and head get gradients far under the tensor's
    largest, which one int8 scale per tensor rounds away."""
    cell = _tiny("bfloat16", vocab_size=4096)
    ref = harness.reference_module("moe")
    base = ref.train_readings(cell.config, cell.traffic, SEED)
    ctl = ref.train_readings(cell.config, cell.traffic, SEED,
                             precision="int8")
    rd = _program(cell)
    program = harness.gaps(rd.losses, rd.grad_norms, rd.change_norms, base)
    control = harness.gaps(ctl["losses"], ctl["grad_norms"],
                           ctl["change_norms"], base)
    limits = cell.traffic["limits"]
    for name in ("grad_gap", "change_gap"):
        assert control[name] > limits[name], (name, control)
        assert control[name] >= 10 * program[name], (program, control)


def _half_batch(session):
    import dataclasses

    compiled = session.compile()
    inner = compiled.step_fn

    def step(params, opt_state, batch):
        mask = batch["loss_mask"]
        keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
        return inner(params, opt_state,
                     dict(batch, loss_mask=mask * keep[:, None]))

    session.override("compile", dataclasses.replace(compiled, step_fn=step))


@pytest.mark.parametrize("plant,correct", [(None, True),
                                           (_half_batch, False)])
def test_run_is_correct_unless_half_the_batch_is_left_out(plant, correct):
    cell = _tiny()
    out = harness.run_cell(cell, SEED + 1, 0.3, False,
                           t_start=time.perf_counter(),
                           devices=jax.devices()[:1], plant=plant,
                           log=lambda s: None)
    assert out["correct"] is correct
    c = out["checks"]["grad_gap"]
    assert (c["value"] > c["limit"]) is not correct


# hand count for qwen3-moe-30b-a3b-4l-16e at seq 4096: per layer
# 2048*128*(32+8) + 32*128*2048 = 18,874,368 attention and 3*2048*768 =
# 4,718,592 for the expert width one token runs here; 4 layers
# 94,371,840 + 18,992*2048 (head) = 133,267,456 matmul params;
# 6 * 133,267,456 + 6 * 4 * 4096 * 32 * 128 = 1,202,257,920
QWEN3_SHARE_FLOPS = 1_202_257_920


def test_qwen3_share_matches_the_hand_count():
    cfg = harness._load_json(
        harness.ROOT / "bench/configs/qwen3-moe-30b-a3b-4l-16e.json")
    assert flops.matmul_params(cfg) == 133_267_456
    assert flops.train_flops_per_token(cfg, 4096) == QWEN3_SHARE_FLOPS
