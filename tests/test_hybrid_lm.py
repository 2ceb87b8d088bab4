"""The hybrid stack (models/hybrid.py, ops/ssd.py, ops/moe.moe_ffn_held)
against the benchmark's plain reference (token-by-token recurrence, dense
masked experts, plain softmax) at tiny widths: seeded random weights,
float32, ``highest``."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import (
    hybrid_train_cell as cell, reference_nemotron_h as ref,
    weights_nemotron_h as weights,
)
from distributed_tensorflow_tpu.ops import moe
from distributed_tensorflow_tpu.ops.ssd import ssd_chunked, ssd_sequential
from distributed_tensorflow_tpu.train import LMTrainer

TINY = {
    "hidden_size": 32, "vocab_size": 64, "num_hidden_layers": 9,
    "hybrid_override_pattern": "EMEMEMEM*",
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "n_routed_experts": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "routed_scaling_factor": 2.5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "norm_eps": 1e-5,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "published": {"num_hidden_layers": 52},
    "deployment": {"router_width": 16, "experts_held": [4, 4]},
}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def tiny(pattern="EMEMEMEM*", **over):
    cfg = copy.deepcopy(TINY)
    cfg.update(hybrid_override_pattern=pattern, num_hidden_layers=len(pattern))
    cfg.update(over)
    return cfg


def build(cfg, seed=3, **traffic):
    model = cell.build_model(cfg, traffic)
    model.compute_dtype = jnp.float32
    tree = weights.make(cfg, seed)
    # Random norm weights, biases and skips: ones and zeros hide a swap.
    key = jax.random.key(seed + 100)
    for group, leaf in (("mamba", "norm"), ("mamba", "gate_norm"),
                        ("mamba", "d_skip"), ("mamba", "conv_b"),
                        ("moe", "norm"), ("attn", "norm")):
        key, k = jax.random.split(key)
        tree[group][leaf] = tree[group][leaf] + 0.3 * jax.random.normal(
            k, tree[group][leaf].shape)
    return model, tree


def tokens_for(cfg, rows=2, length=24, seed=0):
    return jax.random.randint(
        jax.random.key(seed), (rows, length), 0, cfg["vocab_size"])


def close(a, b, tol):
    a, b = np.asarray(a), np.asarray(b)
    scale = max(float(np.abs(b).max()), 1e-30)
    assert float(np.abs(a - b).max()) <= tol * scale, (
        float(np.abs(a - b).max()), scale)


@pytest.mark.parametrize("pattern,balance", [
    ("M", None), ("E", None), ("*", None), ("EMEMEMEM*", None),
    ("E", 0), ("E", 2), ("EMEMEMEM*", 2)])
def test_logits_and_loss_gradients_equal_the_plain_reference(pattern, balance):
    cfg = tiny(pattern)
    model, tree = build(cfg, balance_rounds=balance)
    toks = tokens_for(cfg)
    z = weights.dims(cfg)
    close(model.apply(cell.to_program_params(tree), toks),
          ref.logits(tree, toks, z, balance=balance), 2e-5)
    got = jax.grad(lambda t: model.loss(cell.to_program_params(t), toks))(tree)
    want = jax.grad(lambda t: ref.loss(t, toks, z, balance=balance))(tree)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        if w.size and float(jnp.abs(w).max()) > 0:
            close(g, w, 5e-4), path


@pytest.mark.parametrize("remat", [True, "selective"])
def test_remat_changes_no_gradient(remat):
    cfg = tiny("EM*")
    model, tree = build(cfg)
    toks = tokens_for(cfg)
    loss = lambda m: jax.grad(  # noqa: E731
        lambda t: m.loss(cell.to_program_params(t), toks))(tree)
    plain = loss(model)
    model.remat = remat
    for a, b in zip(jax.tree.leaves(loss(model)), jax.tree.leaves(plain)):
        close(a, b, 1e-5)


@pytest.mark.parametrize("remat", [True, "selective"])
def test_remat_true_keeps_no_named_value(remat, capsys):
    """This stack's ``remat=True`` is the checkpoint that keeps nothing
    (its cell fills its chip); GPTLM's keeps the flash kernel's output.
    "selective" keeps it here too, and shows the probe sees a name."""
    from jax.ad_checkpoint import print_saved_residuals

    cfg = tiny("*")
    model, tree = build(cfg, attention_impl="flash", remat=remat)
    model.flash_min_len = 0
    toks = tokens_for(cfg, length=32)
    print_saved_residuals(
        lambda t: model.loss(cell.to_program_params(t), toks), tree)
    # One line a residual, each with the function that made it; the
    # kernel's two outputs are made (and named) in this one.
    kept = capsys.readouterr().out.count("(flash_attention_with_lse)")
    assert kept == (0 if remat is True else 2)


def _scan_inputs(length, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    b, h, p, g, n = 2, 4, 8, 2, 16
    return (jax.random.normal(k[0], (b, length, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, length, h)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (b, length, g, n)),
            jax.random.normal(k[4], (b, length, g, n)))


@pytest.mark.parametrize("length", [32, 8, 37, 5])
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_chunked_scan_equals_the_token_by_token_recurrence(length, what):
    args = _scan_inputs(length)
    if what == "forward":
        close(ssd_chunked(*args, chunk=8), ssd_sequential(*args), 2e-5)
        return
    f = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(f(lambda *a: ssd_chunked(*a, chunk=8)), f(ssd_sequential)):
        close(a, b, 1e-4)


def test_a_state_dropped_at_a_chunk_boundary_is_seen():
    """What the chunks carry matters: the same tokens scanned as separate
    sequences of one chunk each give another answer."""
    x, dt, a, b, c = _scan_inputs(32)
    apart = lambda t: t.reshape(8, 8, *t.shape[2:])  # noqa: E731
    whole = ssd_chunked(x, dt, a, b, c, chunk=8)
    cut = ssd_chunked(apart(x), apart(dt), a, apart(b), apart(c), chunk=8)
    assert float(jnp.abs(whole - cut.reshape(whole.shape))[:, 8:].max()) > 1e-2
    close(whole[:, :8], cut.reshape(whole.shape)[:, :8], 1e-6)


def _layer(seed=0, t=40, d=16, e=32, f=12):
    k = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(k[0], (t, d)), jax.random.normal(k[1], (d, e)),
            0.3 * jax.random.normal(k[2], (e, d, f)),
            0.3 * jax.random.normal(k[3], (e, f, d)))


def _dense_layer(x, router, bias, w_up, w_down, k, scale):
    """Every expert on every token, weighted where chosen."""
    s = jax.nn.sigmoid(x @ router)
    _, idx = jax.lax.top_k(s + bias, k)
    chosen = jnp.take_along_axis(s, idx, -1)
    w = scale * chosen / chosen.sum(-1, keepdims=True)
    dense = jnp.einsum("tke,tk->te", jax.nn.one_hot(idx, router.shape[1]), w)
    y = jnp.einsum("etf,efd->etd",
                   jnp.square(jax.nn.relu(jnp.einsum("td,edf->etf", x, w_up))),
                   w_down)
    return jnp.einsum("te,etd->td", dense, y), idx


def test_the_choice_is_by_score_plus_bias_and_the_weight_by_score():
    x, router, _, _ = _layer()
    bias = jnp.zeros((32,)).at[5].set(10.0).at[7].set(-10.0)
    idx, w = moe.route_sigmoid_topk(x, router, bias, 3, 2.5)
    assert bool(jnp.all(jnp.any(idx == 5, axis=-1)))
    assert not bool(jnp.any(idx == 7))
    s = jax.nn.sigmoid(x @ router)
    chosen = jnp.take_along_axis(s, idx, -1)  # the scores, not score + bias
    close(w, 2.5 * chosen / chosen.sum(-1, keepdims=True), 1e-6)
    close(w.sum(-1), jnp.full((40,), 2.5), 1e-6)
    # and no gradient reaches the bias
    g = jax.grad(lambda b: moe.route_sigmoid_topk(x, router, b, 3, 2.5)[1].sum())(bias)
    assert float(jnp.abs(g).max()) == 0.0


@pytest.mark.parametrize("shape,j", [((2, 64, 5), 1), ((2, 64, 5), 7),
                                      ((1, 257, 3), 257), ((3, 128, 4), 40)])
def test_kth_largest_is_the_order_statistic_itself(shape, j):
    v = jax.random.normal(jax.random.key(11), shape)
    v = v.at[:, ::7].set(v[:, :1])  # ties among the values
    want = -jnp.sort(-v, axis=1)[:, j - 1]
    assert moe.kth_largest(v, j).tolist() == want.tolist()


def _per_expert(idx, experts=32, seqs=2):
    return jax.nn.one_hot(idx, experts).sum(1).reshape(seqs, -1, experts).sum(1)


@pytest.mark.parametrize("skew", [0.0, 2.0, 12.0])
def test_the_balancing_bias_deals_each_sequence_evenly(skew):
    """Two sequences of 256 tokens, 32 experts, 4 choices: whatever
    offset the router gives each expert (at 12 every token of the plain
    choice picks the same four), each expert gets its 32 tokens of each
    sequence to within a third, chosen by logit + bias and weighed by
    score; the deal does not depend on the offsets, and no
    gradient reaches the router through the bias."""
    k = jax.random.split(jax.random.key(7), 3)
    x = jnp.concatenate([jax.random.normal(k[0], (512, 16)),
                         jnp.ones((512, 1))], axis=1)
    router = jnp.concatenate(
        [0.25 * jax.random.normal(k[1], (16, 32)),
         skew * jax.random.normal(k[2], (1, 32))], axis=0)
    zeros = jnp.zeros((32,))
    idx, w = moe.route_sigmoid_topk(x, router, zeros, 4, 2.5, balance=(256, 2))
    per_seq = _per_expert(idx)
    assert float(per_seq.min()) >= 22 and float(per_seq.max()) <= 42, per_seq
    level, _ = moe.route_sigmoid_topk(
        x, router.at[-1].set(0.0), zeros, 4, 2.5, balance=(256, 2))
    # ... up to a near-tie that float32 rounds the other way with the offset on
    assert float(jnp.mean(jnp.any(idx != level, axis=-1))) <= 0.02
    plain, _ = moe.route_sigmoid_topk(x, router, zeros, 4, 2.5)
    if skew == 12.0:  # the plain choice has collapsed
        assert float(jax.nn.one_hot(plain, 32).sum((0, 1)).max()) > 400
    logits = x @ router
    # the marks alone: exactly 32 tokens of a sequence reach each expert's
    marks = -moe.balancing_bias(logits, 4, 256, 0)
    assert (logits >= marks).reshape(2, 256, 32).sum(1).tolist() == [[32] * 32] * 2
    _, want = jax.lax.top_k(logits + moe.balancing_bias(logits, 4, 256, 2), 4)
    assert idx.tolist() == want.tolist()
    score = lambda r: jnp.take_along_axis(jax.nn.sigmoid(x @ r), idx, -1)  # noqa: E731
    close(w, 2.5 * score(router) / score(router).sum(-1, keepdims=True), 1e-6)
    direct = jax.grad(lambda r: (
        2.5 * score(r) / score(r).sum(-1, keepdims=True))[:, 0].sum())(router)
    through = jax.grad(lambda r: moe.route_sigmoid_topk(
        x, r, zeros, 4, 2.5, balance=(256, 2))[1][:, 0].sum())(router)
    close(through, direct, 1e-5)


def test_the_rounds_even_what_the_marks_alone_leave_uneven():
    """Logits that move together over the tokens (one factor carries most
    of every expert's spread, at loadings that differ): the marks alone
    leave the experts with the smallest loadings several times their
    share; every round of the auction brings them nearer to it."""
    k = jax.random.split(jax.random.key(9), 4)
    factor = jax.random.normal(k[0], (512, 1))
    loading = jnp.exp(jax.random.normal(k[1], (1, 32)))
    logits = factor * loading + 0.3 * jax.random.normal(k[2], (512, 32)) + (
        3.0 * jax.random.normal(k[3], (1, 32)))
    counts = lambda rounds: _per_expert(jax.lax.top_k(  # noqa: E731
        logits + moe.balancing_bias(logits, 4, 256, rounds), 4)[1])
    widest = [float(counts(rounds).max()) for rounds in (0, 2, 8)]
    assert widest[0] > 4 * 32 and widest[1] < 2 * 32 and widest[2] < 1.25 * 32
    assert float(counts(8).min()) > 0.75 * 32


@pytest.mark.parametrize("block_rows", [None, 16])
def test_the_shares_add_up_to_the_uncut_layer(block_rows):
    """The routed parts of all 16 shares equal the whole 32-expert layer
    (the shared expert, which every share computes alike, counts once and
    is outside this function)."""
    x, router, w_up, w_down = _layer()
    bias = 0.1 * jax.random.normal(jax.random.key(4), (32,))
    whole, _ = _dense_layer(x, router, bias, w_up, w_down, 3, 2.5)
    total, landed = 0.0, 0
    for share in range(16):
        first = 2 * share
        out, load = moe.moe_ffn_held(
            x, router, bias, w_up[first:first + 2], w_down[first:first + 2],
            first=first, k=3, scale=2.5, compute_dtype=jnp.float32,
            block_rows=block_rows)
        assert int(load.sum()) == 40 * 3  # the load is over all experts
        total, landed = total + out, landed + int(load[first:first + 2].sum())
    assert landed == 40 * 3  # every (token, choice) pair landed on one share
    close(total, whole, 2e-5)


@pytest.mark.parametrize("block_rows", [None, 16, 64])
def test_no_token_is_dropped_when_every_token_picks_the_same_expert(block_rows):
    x, router, w_up, w_down = _layer()
    bias = jnp.zeros((32,)).at[9].set(50.0)  # every token's first choice
    whole, idx = _dense_layer(x, router, bias, w_up, w_down, 3, 2.5)
    held = slice(8, 12)
    out, load = moe.moe_ffn_held(
        x, router, bias, w_up[held], w_down[held], first=8, k=3, scale=2.5,
        compute_dtype=jnp.float32, block_rows=block_rows)
    rows = load[held]
    assert int(rows[1]) == 40  # all 40 tokens landed on expert 9
    # what the four held experts give, and nothing else
    mask = (idx >= 8) & (idx < 12)
    assert int(rows.sum()) == int(mask.sum())
    only, _ = _dense_layer(
        x, router, bias,
        w_up.at[:8].set(0).at[12:].set(0), w_down, 3, 2.5)
    close(out, only, 2e-5)
    grads = jax.grad(lambda w: moe.moe_ffn_held(
        x, router, bias, w, w_down[held], first=8, k=3, scale=2.5,
        compute_dtype=jnp.float32, block_rows=block_rows)[0].sum())(w_up[held])
    want = jax.grad(lambda w: _dense_layer(
        x, router, bias, jnp.zeros_like(w_up).at[held].set(w), w_down, 3,
        2.5)[0].sum())(w_up[held])
    close(grads, want, 1e-4)


def test_the_counters_count_the_rows_that_landed_on_each_held_expert():
    cfg = tiny("EME")
    model, tree = build(cfg)
    toks = tokens_for(cfg)
    _, counters = model.loss_and_counters(cell.to_program_params(tree), toks)
    rows = np.asarray(counters["moe_expert_rows"])
    assert rows.shape == (2, 4) and rows.dtype == np.int32
    # layer 0 by hand: the normed embeddings through the router
    p = {k: v[0] for k, v in tree["moe"].items()}
    u = ref._rms(tree["embed"][toks], p["norm"], 1e-5).reshape(-1, 32)
    _, idx = jax.lax.top_k(jax.nn.sigmoid(u @ p["router"]), 3)
    want = [int((idx == 4 + e).sum()) for e in range(4)]
    assert rows[0].tolist() == want


def _trainer(cfg, rows, **kw):
    traffic = {"batch_per_chip": {"1": 4}, "mesh": {"1": None},
               "optimizer": "adamw", "learning_rate": 3e-3, **kw}
    return cell.build_trainer(cfg, traffic, 1, jax.devices()[:1], rows)


def test_run_epoch_lowers_the_loss_and_sets_the_gauges():
    from distributed_tensorflow_tpu.utils.logging import StepLogger

    cfg = tiny("EM*M")
    rows = np.asarray(jax.random.randint(jax.random.key(1), (12, 16), 0, 64))
    trainer = _trainer(cfg, rows)
    logger = StepLogger(freq=10 ** 9, print_fn=lambda *a: None)
    first = None
    for epoch in range(6):
        trainer.run_epoch(epoch, logger)
        first = first if first is not None else float(trainer._epoch_costs[0])
    assert float(trainer._epoch_costs[-1]) < first - 0.05
    gauges = {g.name: g.value for g in trainer.metrics if g.name.startswith("moe_")}
    assert set(gauges) == {
        "moe_rows_per_step", "moe_expert_rows_max", "moe_expert_rows_mean"}
    # 4 rows x 16 tokens x 3 choices, of which a part landed on 4 of 16
    assert 0 < gauges["moe_rows_per_step"] <= 4 * 16 * 3
    assert gauges["moe_expert_rows_max"] >= gauges["moe_expert_rows_mean"] > 0


def test_the_load_counts_every_expert_and_the_rows_the_held_ones():
    cfg = tiny("EM*M")
    model, tree = build(cfg)
    params, toks = cell.to_program_params(tree), tokens_for(cfg)
    _, counters = model.apply_with_counters(params, toks)
    load = np.asarray(counters["moe_expert_load"])  # [1, 16]
    assert load.shape == (1, 16) and load.sum() == 2 * 24 * 3
    np.testing.assert_array_equal(
        np.asarray(counters["moe_expert_rows"]), load[:, 4:8])
    # a step hands back the held experts' rows alone
    _, stepped = model.loss_and_counters(params, toks)
    assert set(stepped) == {"moe_expert_rows"}


def test_the_scanned_dispatch_equals_eager_steps():
    from distributed_tensorflow_tpu.utils.logging import StepLogger

    cfg = tiny("EM*M")
    rows = np.asarray(jax.random.randint(jax.random.key(2), (12, 16), 0, 64))
    logger = StepLogger(freq=10 ** 9, print_fn=lambda *a: None)
    scanned = _trainer(cfg, rows)
    scanned.run_epoch(0, logger)
    eager = _trainer(cfg, rows)
    eager._scan = False
    eager.run_epoch(0, logger)
    assert isinstance(scanned, LMTrainer) and scanned.config.scan_epoch
    for a, b in zip(jax.tree.leaves(scanned.state.params),
                    jax.tree.leaves(eager.state.params)):
        close(a, b, 1e-5)
    close(scanned.last_cost, eager.last_cost, 1e-5)


def test_the_constructor_refuses_what_it_cannot_build():
    model = cell.build_model(tiny("EM"), {})
    assert model.moe_experts == 16  # what LMTrainer's tp / sp checks read
    with pytest.raises(ValueError, match="pattern"):
        type(model)(64, 32, "EMX")
    with pytest.raises(ValueError, match="experts_held"):
        type(model)(64, 32, "E", num_experts=8, experts_per_token=2,
                    expert_dim=4, shared_dim=4, experts_held=(6, 4))
