"""Each cell's code path at a tiny size on the CPU, the look for a chip
skipped: a sound run is ``correct``; the same run with the timed path
broken underneath is not, once for each fault the cell can have.

Nothing here is a measurement: the rates these runs print are the CPU's.
"""

import pytest

from benchmark.lib import harness, serve_cell, train_cell
from benchmark.tests import helpers


@pytest.fixture
def limits(monkeypatch):
    def use(table):
        monkeypatch.setattr(harness, "limits", lambda cell: table)
    return use


def _train(chips, monkeypatch, limits, break_trainer=None):
    limits(helpers.TINY_TRAIN_LIMITS)
    if break_trainer is not None:
        build = train_cell.build_trainer

        def broken(*a, **kw):
            trainer = build(*a, **kw)
            break_trainer(trainer)
            return trainer

        monkeypatch.setattr(train_cell, "build_trainer", broken)
    cell = "train-gpt2m" if chips == 1 else "train-gpt2l-tp4"
    ctx = helpers.context(cell, helpers.TINY_CFG, helpers.tiny_train_traffic(),
                          chips=chips, seconds=1.0)
    return train_cell.run(ctx)


def state_unchanged(trainer):
    """A step that computes its losses and returns its state as it was."""
    import jax
    import jax.numpy as jnp

    real = trainer._build_scanned_fn()

    def fake(state, toks, lens, idxs):
        _, losses = real(jax.tree.map(jnp.copy, state), toks, lens, idxs)
        return state, losses

    trainer._scanned_fn = fake


def half_batch(trainer):
    """Half of the batch left out, the mean taken over the rest."""
    loss = trainer.model.loss
    trainer.model.loss = lambda p, t, l=None: loss(p, t[: t.shape[0] // 2], l)


def no_exchange(trainer):
    """The feed-forward's row-split product without its sum over the
    ``model`` axis: each chip keeps only its own half of the
    contraction."""
    import jax

    model = trainer.model

    def ffn(blk, hn2, token_mask=None):
        from distributed_tensorflow_tpu.ops.moe import MoEAux

        up = jax.nn.gelu(model._dot(hn2, blk.w_up) + blk.b_up)
        k = up.shape[-1] // 2
        return model._dot(up[..., :k], blk.w_down[:k]) + blk.b_down, MoEAux.zero()

    model._ffn = ffn


@pytest.mark.parametrize("chips", [1, 4])
def test_training_cell_is_correct(chips, monkeypatch, limits):
    run = _train(chips, monkeypatch, limits)
    assert harness.judge(run.checks), run.checks
    assert set(run.checks) == set(helpers.TINY_TRAIN_LIMITS)
    assert run.failed == 0 and run.attempted >= 1
    assert run.compiles_in_window == 0
    assert run.end_to_end["train_tokens_per_s"] > 0
    assert run.window_s >= 1.0


@pytest.mark.parametrize("chips,fault,number", [
    (1, state_unchanged, "change_norm_gap"),
    (1, half_batch, "moment_norm_gap"),
    (4, half_batch, "moment_norm_gap"),
    (4, no_exchange, "moment_norm_gap"),
])
def test_training_fault_is_not_correct(chips, fault, number, monkeypatch, limits):
    run = _train(chips, monkeypatch, limits, break_trainer=fault)
    assert not harness.judge(run.checks), run.checks
    value, limit = run.checks[number]
    assert value > limit
    if fault is state_unchanged:
        assert value == pytest.approx(1.0, abs=1e-3)


def _serve(cell, mix, monkeypatch, limits, break_server=None):
    limits(helpers.TINY_SERVE_LIMITS)
    if break_server is not None:
        build = serve_cell.build_server

        def broken(*a, **kw):
            server = build(*a, **kw)
            break_server(server)
            return server

        monkeypatch.setattr(serve_cell, "build_server", broken)
    ctx = helpers.context(cell, helpers.TINY_CFG, helpers.tiny_serve_traffic(mix),
                          seconds=2.0)
    return serve_cell.run(ctx)


def token_altered(server):
    """One slot's tokens altered where the decode chunk produces them."""
    real = server._chunk_jit
    vocab = server.model.vocab_size

    def chunk(params, state):
        state, toks, valid = real(params, state)
        return state, (toks + 1) % vocab, valid

    server._chunk_jit = chunk


CELLS = [("serve-gpt2l-chat", "chat-steady"), ("serve-gpt2l-docs", "docs-batch")]


@pytest.mark.parametrize("cell,mix", CELLS)
def test_serving_cell_is_correct(cell, mix, monkeypatch, limits):
    run = _serve(cell, mix, monkeypatch, limits)
    assert harness.judge(run.checks), run.checks
    assert run.failed == 0 and run.attempted == len(run.requests) > 0
    assert run.compiles_in_window == 0
    assert run.counters["checked_tokens"] > 0
    for name in ("setup_s", "serve_tokens_per_s", "tpot_p50_ms"):
        assert run.end_to_end[name] > 0
    if mix == "docs-batch":
        assert run.counters["prefix_hit_blocks"] > 0
        assert any(r["prefix"] > 0 for r in run.requests)
    else:
        assert run.counters["prefix_hit_blocks"] == 0


@pytest.mark.parametrize("cell,mix", CELLS)
def test_altered_token_is_not_correct(cell, mix, monkeypatch, limits):
    run = _serve(cell, mix, monkeypatch, limits, break_server=token_altered)
    assert not harness.judge(run.checks), run.checks


# -- the control: the reference in int8, put in the program's place ------------

CONTROL_CFG = dict(helpers.TINY_CFG, vocab_size=8192, n_embd=128, n_layer=4,
                   n_positions=128)
# At this size sound runs read 0 (CPU) and the int8 control 0.002-0.009
# over the positions of eight requests; the real cells' readings and
# limits are in PERF.md section 2.
CONTROL_LIMIT = {"max_logit_gap": 1e-3}


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_serving_control_is_not_correct(seed, monkeypatch, limits):
    limits(CONTROL_LIMIT)
    mix = helpers.tiny_serve_traffic("chat-steady")
    mix["prompt"].update(min=16, max=48, median=32)
    mix["answer"].update(min=24, max=48, median=32)
    mix["server"].update(chunk=8, kv_blocks=256)
    mix["check_requests"] = 8
    ctx = helpers.context("serve-gpt2l-chat", CONTROL_CFG, mix, seed=seed, seconds=3.0)
    journal = serve_cell.Collector()
    server = serve_cell.build_server(ctx.cfg, mix, seed, journal)
    run, sample = serve_cell.serve_window(ctx, server, journal)
    sound = serve_cell.widest_gap(serve_cell.reference_gaps(ctx.cfg, seed, sample))
    control = serve_cell.widest_gap(
        serve_cell.reference_gaps(ctx.cfg, seed, sample, precision="int8"))
    limit = CONTROL_LIMIT["max_logit_gap"]
    assert harness.judge({"max_logit_gap": (sound, limit)})
    assert not harness.judge({"max_logit_gap": (control, limit)}), control


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_training_control_is_not_correct(seed):
    """The reference in int8, forward and backward, put in the program's
    place, at a size where the gap between bfloat16 and int8 shows as it
    does on the chip: the moment's relative error over the whole tree
    reads 0.019, a sound run of the program 0.005; the limit here lies
    between. (The real cells' readings and limits are in PERF.md
    section 2.)"""
    import jax

    from benchmark.lib import traffic as traffic_lib

    cfg = dict(helpers.TINY_CFG, n_embd=128, n_layer=4, vocab_size=2048)
    mix = helpers.tiny_train_traffic()
    rows = traffic_lib.train_rows(mix, cfg["vocab_size"], seed, 12)
    devices = jax.devices()[:1]
    low = train_cell.reference_readings(
        cfg, mix, seed, rows, devices, 3, precision="int8", keep_moment=True)
    ref = train_cell.reference_readings(
        cfg, mix, seed, rows, devices, 3, against={"program": low["moment_tree"]})
    numbers = train_cell.compare(low, ref)
    limits = dict(helpers.TINY_TRAIN_LIMITS, moment_rel_err_all=0.01)
    checks = {k: (v, limits[k]) for k, v in numbers.items() if k in limits}
    assert not harness.judge(checks), checks
    assert numbers["moment_rel_err_all"] > 0.01


def test_tokens_in_window_counts_the_work_inside():
    """Two requests; chunk 4. Request 0 (prompt 10, answer 6) is admitted
    before the window and gets its last token inside; request 1 (prompt
    20, answer 9) is admitted inside and is still running at the close."""
    ev = lambda t, kind, **kw: dict(t=t, kind=kind, **kw)  # noqa: E731
    chunk_span = lambda t, rids: ev(t, "span", name="decode_chunk", args={"rids": rids})  # noqa: E731
    events = [
        ev(1.0, "admission", rid=0, prompt_len=10),
        chunk_span(2.0, [0]),            # 4 tokens, before the window
        ev(3.5, "admission", rid=1, prompt_len=20),   # inside: 20 + 1
        chunk_span(4.0, [0, 1]),         # 1 (request 0's last) + 4
        chunk_span(5.0, [1]),            # 4
        chunk_span(6.5, [1]),            # after the close
    ]
    total = serve_cell.tokens_in_window(events, {0: 6, 1: 9}, 4, 3.0, 6.0)
    assert total == 21 + 5 + 4
