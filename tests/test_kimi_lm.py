"""The hybrid stack's later kinds (models/hybrid.py: ``K`` gated delta
rule, ``L`` latent attention, ``D`` dense gated feed-forward, ``E`` in its
SiLU-gated form) against the benchmark's plain reference
(``reference_kimi_linear``: token-by-token recurrence, dense masked
experts, plain softmax) at tiny widths: seeded random weights, float32,
``highest``."""

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import (
    kimi_linear_train_cell as cell, reference_kimi_linear as ref,
    weights_kimi_linear as weights,
)
from distributed_tensorflow_tpu.models import hybrid
from distributed_tensorflow_tpu.ops import moe
from distributed_tensorflow_tpu.ops.pallas_attention import (
    REMAT_SAVE_NAMES, flash_attention,
)
from distributed_tensorflow_tpu.ops.ring_attention import dense_attention
from distributed_tensorflow_tpu.train import LMTrainer

TINY = {
    "hidden_size": 32, "vocab_size": 64, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "intermediate_size": 48,
    "linear_attn_config": {
        "kda_layers": [1, 3], "full_attn_layers": [2], "head_dim": 8,
        "num_heads": 4, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "kv_lora_rank": 12, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "num_experts": 4,
    "num_experts_per_token": 3, "moe_intermediate_size": 24,
    "num_shared_experts": 1, "routed_scaling_factor": 2.446,
    "rms_norm_eps": 1e-5, "published": {"num_hidden_layers": 27},
    "deployment": {"router_width": 16, "experts_held": [4, 4],
                   "pattern": "KDLEKE"},
}


@pytest.fixture(autouse=True)
def highest(monkeypatch):
    monkeypatch.setattr(weights, "GATE_RANK", 6)
    # chunks of 8: a row of 24 tokens carries its state across two boundaries
    monkeypatch.setattr(hybrid, "kda_chunked", functools.partial(
        hybrid.kda_chunked, chunk=8))
    with jax.default_matmul_precision("highest"):
        yield


def tiny(kda=(1, 3), full=(2,), dense=1, **over):
    cfg = copy.deepcopy(TINY)
    n = len(kda) + len(full)
    cfg.update(num_hidden_layers=n, first_k_dense_replace=dense)
    cfg["linear_attn_config"].update(
        kda_layers=list(kda), full_attn_layers=list(full))
    cfg.update(over)
    cfg["deployment"]["pattern"] = weights.pattern_of(cfg)
    return cfg


def build(cfg, seed=3, **traffic):
    model = cell.build_model(cfg, traffic)
    model.compute_dtype = jnp.float32
    tree = weights.make(cfg, seed)
    # Random norm weights: ones hide a swap. A decay that forgets within a
    # few tokens on some channels and never on others.
    key = jax.random.key(seed + 100)
    for group, leaf in (("kda", "norm"), ("kda", "out_norm"), ("mla", "norm"),
                        ("mla", "kv_norm"), ("dense", "norm"), ("moe", "norm")):
        key, k = jax.random.split(key)
        tree[group][leaf] = tree[group][leaf] + 0.3 * jax.random.normal(
            k, tree[group][leaf].shape)
    key, k = jax.random.split(key)
    tree["kda"]["dt_bias"] = 3.0 * jax.random.normal(
        k, tree["kda"]["dt_bias"].shape)
    return model, tree


def tokens_for(cfg, rows=2, length=24, seed=0):
    return jax.random.randint(
        jax.random.key(seed), (rows, length), 0, cfg["vocab_size"])


def close(a, b, tol):
    a, b = np.asarray(a), np.asarray(b)
    scale = max(float(np.abs(b).max()), 1e-30)
    assert float(np.abs(a - b).max()) <= tol * scale, (
        float(np.abs(a - b).max()), scale)


def grads_equal(model, tree, cfg, toks, balance=None, tol=2e-4):
    z = weights.dims(cfg)
    got = cell._as_dict(jax.grad(model.loss)(cell.to_program_params(tree), toks))
    want = jax.grad(lambda w: ref.loss(w, toks, z, balance=balance))(tree)
    for name in ("embed", "norm_f", "head"):
        close(got[name], want[name], tol)
    for group in weights.GROUPS:
        for leaf, g in got.get(group, {}).items():  # none for an absent kind
            if leaf == "router_bias":  # a buffer: no gradient reaches it
                assert not np.asarray(g).any()
                continue
            try:
                close(g, want[group][leaf], tol)
            except AssertionError as e:
                raise AssertionError(f"{group}.{leaf}: {e}") from None


# one kind of mixer at a time beside each feed-forward, then the cut's own
# order of kinds; with and without the balancing
@pytest.mark.parametrize("kda,full,dense,balance", [
    ((1,), (), 1, None), ((), (1,), 1, None), ((1,), (), 0, None),
    ((1, 3), (2,), 1, None), ((1,), (), 0, 0), ((1, 3), (2,), 1, 2)])
def test_logits_and_loss_gradients_equal_the_plain_reference(
        kda, full, dense, balance):
    cfg = tiny(kda, full, dense)
    model, tree = build(cfg, balance_rounds=balance)
    # Seed 1: under seed 0 one token's third and fourth expert tie to the
    # last bit after the auction, and the program's bisection and the
    # reference's sort break the tie differently (a choice, not an error).
    toks = tokens_for(cfg, seed=1)
    close(model.apply(cell.to_program_params(tree), toks),
          ref.logits(tree, toks, weights.dims(cfg), balance=balance), 2e-5)
    grads_equal(model, tree, cfg, toks, balance)


@pytest.mark.parametrize("remat", [True, "selective"])
def test_remat_changes_no_gradient(remat):
    cfg = tiny()
    model, tree = build(cfg, balance_rounds=2)
    params, toks = cell.to_program_params(tree), tokens_for(cfg)
    plain = jax.grad(model.loss)(params, toks)
    model.remat = remat
    again = jax.grad(model.loss)(params, toks)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(plain)):
        close(a, b, 1e-5)


def test_a_length_that_is_no_multiple_of_the_chunk():
    cfg = tiny((1,), (), 1)
    model, tree = build(cfg)
    toks = tokens_for(cfg, length=21)
    close(model.apply(cell.to_program_params(tree), toks),
          ref.logits(tree, toks, weights.dims(cfg)), 2e-5)


# -- latent attention through the flash kernels -----------------------------------


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
@pytest.mark.parametrize("save_names", [None, REMAT_SAVE_NAMES],
                         ids=["plain", "named"])
def test_flash_at_two_head_sizes_equals_dense(fused, save_names):
    """q and k 192 wide, v 128: the interpreter here, the chip's compiler
    in tests/test_chip_compile.py."""
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (1, 256, 2, 192))
    k = jax.random.normal(ks[1], (1, 256, 2, 192))
    v = jax.random.normal(ks[2], (1, 256, 2, 128))
    w = jax.random.normal(ks[3], (1, 256, 2, 128))
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=128, block_k=128, fused=fused,
        save_names=save_names)
    dense = lambda q, k, v: dense_attention(q, k, v, causal=True)  # noqa: E731
    assert flash(q, k, v).shape == (1, 256, 2, 128)
    close(flash(q, k, v), dense(q, k, v), 1e-5)
    loss = lambda f: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(loss(flash), loss(dense)):
        assert got.shape == want.shape
        close(got, want, 2e-5)


def test_flash_refuses_values_of_another_length():
    q = jnp.zeros((1, 128, 2, 192))
    with pytest.raises(ValueError, match="k/v must match"):
        flash_attention(q, q, jnp.zeros((1, 64, 2, 128)), causal=True)


def test_latent_attention_trains_through_the_flash_kernels():
    cfg = tiny((), (1,), 1)
    dense_model, tree = build(cfg)
    flash_model, _ = build(cfg, attention_impl="flash", remat="selective")
    flash_model.flash_min_len = 128
    params, toks = cell.to_program_params(tree), tokens_for(cfg, length=128)
    close(flash_model.apply(params, toks), dense_model.apply(params, toks), 2e-5)
    for a, b in zip(jax.tree.leaves(jax.grad(flash_model.loss)(params, toks)),
                    jax.tree.leaves(jax.grad(dense_model.loss)(params, toks))):
        close(a, b, 2e-4)


# -- the gated experts --------------------------------------------------------------


def _layer(tokens=40, d=16, experts=32, width=12, seed=2):
    k = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(k[0], (tokens, d)),
            0.5 * jax.random.normal(k[1], (d, experts)),
            0.3 * jax.random.normal(k[2], (experts, d, width)),
            0.3 * jax.random.normal(k[3], (experts, d, width)),
            0.3 * jax.random.normal(k[4], (experts, width, d)))


def _reference_layer(x, router, bias, w_gate, w_up, w_down, held=(0, 32)):
    """The routed part of the reference's expert layer (its shared expert
    is zero here) over rows ``x`` [T, D] of unit mean square, which the
    layer's own norm (weight 1) leaves as they are, holding ``held`` of the
    32 experts -> (out [T, D], load [32])."""
    d = x.shape[-1]
    z = {"eps": 0.0, "experts": 32, "top_k": 3, "routed_scale": 2.446,
         "held": held}
    first, count = held
    p = {"norm": jnp.ones((d,)), "router": router, "router_bias": bias,
         "w_gate": w_gate[first:first + count], "w_up": w_up[first:first + count],
         "w_down": w_down[first:first + count],
         "shared_gate": jnp.zeros((d, 4)), "shared_up": jnp.zeros((d, 4)),
         "shared_down": jnp.zeros((4, d))}
    out, load = ref._experts(x[None], p, z, "float32", None, None)
    return out[0], load


def _unit_rows(x):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))


@pytest.mark.parametrize("block_rows", [None, 16])
def test_the_gated_shares_add_up_to_the_uncut_layer(block_rows):
    """The routed parts that all 32 shares of 1 expert give, plus the
    shared expert counted once, equal the reference's uncut layer."""
    x, router, w_gate, w_up, w_down = _layer()
    x = _unit_rows(x)
    bias = 0.1 * jax.random.normal(jax.random.key(4), (32,))
    whole, load_ref = _reference_layer(x, router, bias, w_gate, w_up, w_down)
    total, landed = 0.0, 0
    for share in range(32):
        out, load = moe.moe_ffn_held(
            x, router, bias, w_up[share:share + 1], w_down[share:share + 1],
            w_gate=w_gate[share:share + 1], first=share, k=3, scale=2.446,
            compute_dtype=jnp.float32, block_rows=block_rows)
        assert int(load.sum()) == 40 * 3  # the load is over all experts
        np.testing.assert_array_equal(np.asarray(load), np.asarray(load_ref))
        total, landed = total + out, landed + int(load[share])
    assert landed == 40 * 3  # every (token, choice) pair landed on one share
    close(total, whole, 2e-5)


def test_the_shared_expert_is_counted_once_in_the_model():
    """Two holders of half the experts each: their layers' outputs, less
    one copy of what both compute alike (the shared expert), add up to
    the layer that holds all."""
    cfg = tiny((1,), (), 0)
    outs = {}
    for held in ((0, 16), (0, 8), (8, 8)):
        c = copy.deepcopy(cfg)
        c["deployment"]["experts_held"] = list(held)
        c["num_experts"] = held[1]
        model, tree = build(c)
        full = weights.make({**c, "num_experts": 16, "deployment": {
            **c["deployment"], "experts_held": [0, 16]}}, 3)
        lo, hi = held[0], held[0] + held[1]
        for leaf in ("w_gate", "w_up", "w_down"):
            tree["moe"][leaf] = full["moe"][leaf][:, lo:hi]
        for leaf in ("router", "shared_gate", "shared_up", "shared_down", "norm"):
            tree["moe"][leaf] = full["moe"][leaf]
        p = hybrid._layers_of(cell.to_program_params(tree).moe)[0]
        h = jax.random.normal(jax.random.key(7), (2, 12, 32))
        outs[held], _ = model._experts(p, h)
        if held == (0, 16):
            u = hybrid.rmsnorm(h, p.norm, 1e-5).reshape(-1, 32)
            shared = model._gated_ffn(
                u, p.shared_gate, p.shared_up, p.shared_down).reshape(h.shape)
    close(outs[(0, 8)] + outs[(8, 8)] - shared, outs[(0, 16)], 2e-5)


@pytest.mark.parametrize("block_rows", [None, 16, 64])
def test_no_token_is_dropped_when_every_token_picks_the_same_expert(block_rows):
    x, router, w_gate, w_up, w_down = _layer()
    x = _unit_rows(x)
    bias = jnp.zeros((32,)).at[9].set(50.0)  # every token's first choice
    held = slice(8, 12)
    out, load = moe.moe_ffn_held(
        x, router, bias, w_up[held], w_down[held], w_gate=w_gate[held],
        first=8, k=3, scale=2.446, compute_dtype=jnp.float32,
        block_rows=block_rows)
    assert int(load[9]) == 40  # all 40 tokens landed on expert 9
    only, _ = _reference_layer(x, router, bias, w_gate, w_up, w_down, (8, 4))
    close(out, only, 2e-5)
    grad = lambda f: jax.grad(lambda w: f(w).sum())(w_gate[held])  # noqa: E731
    got = grad(lambda w: moe.moe_ffn_held(
        x, router, bias, w_up[held], w_down[held], w_gate=w, first=8, k=3,
        scale=2.446, compute_dtype=jnp.float32, block_rows=block_rows)[0])
    want = grad(lambda w: _reference_layer(
        x, router, bias, w_gate.at[held].set(w), w_up, w_down, (8, 4))[0])
    close(got, want, 1e-4)


# -- through the trainer -------------------------------------------------------------


def _trainer(cfg, rows, **kw):
    traffic = {"batch_per_chip": {"1": 4}, "mesh": {"1": None},
               "optimizer": "adamw", "learning_rate": 3e-3, **kw}
    return cell.build_trainer(cfg, traffic, 1, jax.devices()[:1], rows)


def test_run_epoch_lowers_the_loss_and_sets_the_gauges():
    from distributed_tensorflow_tpu.utils.logging import StepLogger

    cfg = tiny()
    rows = np.asarray(jax.random.randint(jax.random.key(1), (12, 16), 0, 64))
    trainer = _trainer(cfg, rows)
    assert isinstance(trainer.state.params, hybrid.StackLMParams)
    logger = StepLogger(freq=10 ** 9, print_fn=lambda *a: None)
    first = None
    for epoch in range(6):
        trainer.run_epoch(epoch, logger)
        first = first if first is not None else float(trainer._epoch_costs[0])
    assert float(trainer._epoch_costs[-1]) < first - 0.05
    gauges = {g.name: g.value for g in trainer.metrics if g.name.startswith("moe_")}
    assert set(gauges) == {
        "moe_rows_per_step", "moe_expert_rows_max", "moe_expert_rows_mean"}
    assert 0 < gauges["moe_rows_per_step"] <= 2 * 4 * 16 * 3
    assert gauges["moe_expert_rows_max"] >= gauges["moe_expert_rows_mean"] > 0


def test_the_scanned_dispatch_equals_eager_steps():
    from distributed_tensorflow_tpu.utils.logging import StepLogger

    cfg = tiny()
    rows = np.asarray(jax.random.randint(jax.random.key(2), (12, 16), 0, 64))
    logger = StepLogger(freq=10 ** 9, print_fn=lambda *a: None)
    scanned = _trainer(cfg, rows)
    scanned.run_epoch(0, logger)
    eager = _trainer(cfg, rows)
    eager._scan = False
    eager.run_epoch(0, logger)
    assert isinstance(scanned, LMTrainer) and scanned.config.scan_epoch
    # two programs' float32 rounding through three Adam steps at 3e-3
    for a, b in zip(jax.tree.leaves(scanned.state.params),
                    jax.tree.leaves(eager.state.params)):
        close(a, b, 2e-5)
    close(scanned.last_cost, eager.last_cost, 1e-5)


def test_the_tree_follows_the_pattern():
    """A pattern over the three first kinds keeps the tree it always had;
    one with a later kind has a stack for each kind it uses."""
    old = hybrid.HybridLM(
        64, 32, "EM*", ssm_heads=4, ssm_head_dim=8, ssm_state=16,
        num_experts=8, experts_per_token=2, expert_dim=8, shared_dim=8,
        num_heads=4, head_dim=8)
    assert type(old.init(1)) is hybrid.HybridLMParams
    new = cell.build_model(tiny(), {})
    tree = new.init(1)
    assert type(tree) is hybrid.StackLMParams
    assert type(tree.moe) is hybrid.GatedExpertParams
    assert tree.kda.in_proj.shape == (2, 32, 3 * 32 + 2 * 6 + 4)
    assert tree.kda.dt_bias.shape == (2, 32) and tree.kda.a_log.shape == (2, 4)
    assert tree.mla.wq.shape == (1, 32, 4 * 12)
    assert tree.mla.w_ukv.shape == (1, 12, 4 * 16)
    assert tree.dense.w_gate.shape == (1, 32, 48)
    assert tree.mamba is None and tree.attn is None
    toks = tokens_for(tiny())
    assert np.isfinite(float(new.loss(tree, toks)))


def test_the_constructor_refuses_what_it_cannot_build():
    make = hybrid.HybridLM
    with pytest.raises(ValueError, match="K layer"):
        make(64, 32, "K")
    with pytest.raises(ValueError, match="L layer"):
        make(64, 32, "L", num_heads=4)
    with pytest.raises(ValueError, match="D layer"):
        make(64, 32, "D")
    with pytest.raises(ValueError, match="expert_form"):
        make(64, 32, "D", dense_dim=8, expert_form="gelu")
