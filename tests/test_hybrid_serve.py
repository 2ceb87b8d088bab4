"""``HybridLM`` served by ``TextServer``'s paged engine: full-attention
layers in the block pool, window layers in a ring of blocks per slot,
experts on the decode path (models/hybrid.py, ops/paged_attention.py,
serve.py).

Held against the benchmark's plain reference
(``benchmark/lib/reference_laguna.py``: one float32 forward over the
whole sequence, no cache, dense softmax, experts one at a time) at a tiny
size on the CPU, with a window of 8 and blocks of 4, so that every ring
(3 blocks a slot) wraps many times over contexts of 5 to 9 windows; full
and window layers with different query-head counts. Single-device.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import reference_laguna, weights_laguna
from benchmark.lib.serve_cell import Collector
from distributed_tensorflow_tpu import serve
from distributed_tensorflow_tpu.models.hybrid import HybridLM
from distributed_tensorflow_tpu.ops import rotary
from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

WINDOW, BLOCK, MAX_LEN = 8, 4, 96
CFG = {  # a tiny laguna configuration, read by the reference's dims
    "hidden_size": 32, "vocab_size": 97, "intermediate_size": 48,
    "num_hidden_layers": 5, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 8, "shared_expert_intermediate_size": 8,
    "sliding_window": WINDOW, "moe_routed_scaling_factor": 2.5,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "factor": 64,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_theta": 10000, "partial_rotary_factor": 1}},
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "published": {"num_hidden_layers": 1},
    "deployment": {"pattern": "GDWEWEWEGE"},
}
Z = weights_laguna.dims(CFG)


def _model(**kw):
    return HybridLM(
        Z["vocab"], Z["d"], Z["pattern"], full_heads=Z["full_heads"],
        window_heads=Z["window_heads"], num_kv_heads=Z["kv_heads"],
        head_dim=Z["head_dim"], window=Z["window"],
        full_rotary=rotary.Rotary(**Z["full_rope"]),
        window_rotary=rotary.Rotary(**Z["window_rope"]),
        dense_dim=Z["dense_dim"], expert_form="silu_gated",
        num_experts=Z["experts"], experts_per_token=Z["top_k"],
        expert_dim=Z["expert_dim"], shared_dim=Z["shared_dim"],
        routed_scale=Z["routed_scale"], norm_eps=Z["eps"], max_len=MAX_LEN,
        **kw)


@pytest.fixture(scope="module")
def model():
    # float32 products: the served logits and the reference's then differ
    # by the order of float32 sums alone
    return _model(compute_dtype=jnp.float32, init_std=0.2)


@pytest.fixture(scope="module")
def params(model):
    return model.init(3)


def _tree(params) -> dict:
    """The program's tree as the reference reads it."""
    tree = {g: getattr(params, g)._asdict()
            for g in ("full", "window", "dense", "moe")}
    tree.update(embed=params.embed, head=params.head, norm_f=params.norm_f)
    return tree


def _reference_logits(params, seq) -> np.ndarray:
    """[len(seq), vocab]: the reference's logits at every position."""
    tokens = np.zeros(MAX_LEN, np.int32)
    tokens[: len(seq)] = seq
    h = reference_laguna.final_hidden(_tree(params), tokens, len(seq), Z)
    return np.asarray(jnp.matmul(h, params.head.astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST))[: len(seq)]


def _server(model, params, **kw):
    kw.setdefault("slots", 3)
    return TextServer(model, params, paged=True, chunk=4, block_size=BLOCK,
                      buckets=(16, 32, 64), prefix_caching=False, **kw)


PROMPTS = (5, 40, 23, 61, 9)  # contexts to 85 positions: over 10 windows


def test_prefill_then_decode_logits_match_the_reference_as_rings_wrap(
        model, params):
    """``extend_paged`` then ``decode_paged`` step by step, two slots at
    once, against the reference's logits at every generated position.
    Tolerance 2e-4: float32 on both sides, the sums in another order
    (logits here are O(10))."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, Z["vocab"], n).astype(np.int32) for n in (40, 13)]
    cache = model.empty_paged_cache(2, 2 * MAX_LEN // BLOCK, BLOCK)
    nb = MAX_LEN // BLOCK
    tables = np.arange(2 * nb, dtype=np.int32).reshape(2, nb)
    cache = cache._replace(block_tables=jnp.asarray(tables))
    toks = np.zeros((2, 64), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : p.size] = p
    lens = jnp.asarray([p.size for p in prompts], jnp.int32)
    logits, cache = jax.jit(model.extend_paged)(
        params, cache, jnp.asarray(toks), lens, jnp.zeros(2, jnp.int32),
        jnp.ones(2, bool))
    cache = cache._replace(lengths=lens)
    seqs = [list(p) for p in prompts]
    got = [[np.asarray(logits[i])] for i in range(2)]
    step = jax.jit(model.decode_paged)
    for _ in range(30):
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        for i in range(2):
            seqs[i].append(int(nxt[i]))
        logits, cache = step(params, nxt, cache)
        for i in range(2):
            got[i].append(np.asarray(logits[i]))
    for i in range(2):
        want = _reference_logits(params, np.asarray(seqs[i]))
        n0 = prompts[i].size
        np.testing.assert_allclose(
            np.stack(got[i]), want[n0 - 1: n0 + 30], atol=2e-4, rtol=0)


def test_served_streams_are_the_references_greedy_tokens(model, params):
    """Through the whole server (continuous batching, slots refilled,
    rings wrapping): every served token is the reference's argmax at its
    position."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, Z["vocab"], n).astype(np.int32) for n in PROMPTS]
    journal = Collector()
    srv = _server(model, params, journal=journal)
    outs = srv.generate(prompts, GenerationConfig(max_new=20))
    for p, o in zip(prompts, outs):
        want = _reference_logits(params, np.concatenate([p, o]))
        picked = want[p.size - 1: p.size - 1 + o.size]
        assert (np.argmax(picked, -1) == o).all()
    spans = [e for e in journal.events
             if e["kind"] == "span" and e["name"] == "decode_chunk"]
    touched = [sum(s["args"]["experts_touched"]) for s in spans]
    # per step and expert layer: at least the k of one token, at most all
    assert all(4 * 4 <= t <= 16 * 4 * 4 for t in touched), touched


def test_partial_yarn_rotary_matches_the_reference_past_the_original_length():
    """The full layers' rotation (64 of 128 dims turned, YaRN at factor 64
    over 4,096 original positions) and the window layers' plain one, at
    positions to 4x the original length."""
    cfg = weights_laguna.dims({**CFG, "head_dim": 128, "rope_parameters": {
        "full_attention": {**CFG["rope_parameters"]["full_attention"],
                           "original_max_position_embeddings": 4096},
        "sliding_attention": CFG["rope_parameters"]["sliding_attention"]}})
    for rope in (cfg["full_rope"], cfg["window_rope"]):
        r = rotary.Rotary(**rope)
        np.testing.assert_allclose(
            rotary.inv_freq(r), reference_laguna.inv_freq(rope), rtol=1e-6)
        x = jax.random.normal(jax.random.key(0), (1, 64, 2, 128), jnp.float32)
        pos = jnp.arange(16384 - 64, 16384)
        got = rotary.apply(x, pos[None], r)[0]
        want = reference_laguna._rotate(
            jnp.concatenate([jnp.zeros((16384 - 64, 2, 128)), x[0]]), rope)[-64:]
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=0)
    # YaRN's ramp moves the slow pairs and keeps the fast ones
    r = rotary.Rotary(**cfg["full_rope"])
    base = rotary.inv_freq(r._replace(factor=1.0))
    assert np.isclose(rotary.inv_freq(r)[0], base[0])
    assert np.isclose(rotary.inv_freq(r)[-1], base[-1] / 64)


def test_a_freed_and_refilled_slot_reads_no_stale_ring_rows(model, params):
    """One slot: a long request wraps its ring many times and leaves; a
    short one takes the slot and must generate what it generates on a
    fresh server."""
    rng = np.random.default_rng(2)
    long_p = rng.integers(0, Z["vocab"], 61).astype(np.int32)
    short_p = rng.integers(0, Z["vocab"], 6).astype(np.int32)
    used = _server(model, params, slots=1)
    used.generate([long_p], GenerationConfig(max_new=24))
    again = used.generate([short_p], GenerationConfig(max_new=16))[0]
    fresh = _server(model, params, slots=1).generate(
        [short_p], GenerationConfig(max_new=16))[0]
    np.testing.assert_array_equal(again, fresh)


def test_admission_counts_the_full_layers_blocks_alone(model, params):
    """The pool holds the two full layers; a request reserves
    ceil((prompt + max_new) / bs) blocks of it, its ring is no block of
    the pool, and a position costs the full layers' K and V alone."""
    srv = _server(model, params, slots=2, kv_blocks=15)
    assert srv.kv_position_bytes == 2 * 2 * Z["kv_heads"] * Z["head_dim"] * 4
    assert srv.ring_blocks == 3
    assert srv.metrics.gauge("kv_ring_blocks_total").value == 6
    prompt = np.arange(40, dtype=np.int32) % Z["vocab"]
    rid = srv.submit(prompt, GenerationConfig(max_new=20))  # 60 positions
    srv.submit(prompt, GenerationConfig(max_new=20))
    srv.step()
    # 15 blocks hold one request of 60 positions: the second waits
    assert srv.metrics.gauge("kv_blocks_used").value == 15
    assert srv.metrics.gauge("kv_ring_blocks_used").value == 3
    assert srv.metrics.gauge("queue_depth").value == 1
    while srv.step():
        pass
    assert srv.result(rid).size == 20


def test_the_chat_shapes_prefill_rows_are_unchanged():
    """The whole bank where slots x bucket is at most 4,096 positions (the
    chat cell: 8 slots, buckets to 512); else the rows admitted, rounded
    up to 1 or 4 within that many positions."""
    for bucket in (16, 32, 64, 128, 256, 512):
        for admitted in range(1, 9):
            assert serve.prefill_rows(8, bucket, admitted) == 8
    assert [serve.prefill_rows(32, 256, a) for a in (1, 2, 5, 17, 32)] == [
        1, 4, 4, 4, 4]
    assert [serve.prefill_rows(32, 1024, a) for a in (1, 3, 9)] == [1, 4, 4]
    assert serve.prefill_rows(32, 16384, 5) == 1
    assert serve.prefill_rows(8, 1023, 1) == 1


def test_a_dispatch_of_fewer_rows_serves_the_same_streams(
        model, params, monkeypatch):
    """Prefill dispatches of 1 or 4 rows (the bank's limit lowered so that
    this size reaches it) serve, token for token, what whole-bank
    dispatches serve: for this model (its rings by ``slot_of_row``) and
    for ``GPTLM`` (its tables alone)."""
    from distributed_tensorflow_tpu.models.gpt import GPTLM

    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, Z["vocab"], n).astype(np.int32)
               for n in (5, 12, 9, 30, 14, 7)]
    gpt = GPTLM(vocab_size=Z["vocab"], max_len=MAX_LEN, model_dim=32,
                num_heads=4, num_layers=2, pos_embedding="rope",
                compute_dtype=jnp.float32)
    gp = gpt.init(4)
    bank = [
        _server(model, params, slots=5).generate(prompts, GenerationConfig(max_new=8)),
        TextServer(gpt, gp, paged=True, slots=5, chunk=4, block_size=BLOCK,
                   buckets=(16, 32)).generate(prompts, GenerationConfig(max_new=8)),
    ]
    monkeypatch.setattr(serve, "PREFILL_BANK_POSITIONS", 64)
    assert serve.prefill_rows(5, 16, 3) == 4 and serve.prefill_rows(5, 32, 2) == 1
    rows = [
        _server(model, params, slots=5).generate(prompts, GenerationConfig(max_new=8)),
        TextServer(gpt, gp, paged=True, slots=5, chunk=4, block_size=BLOCK,
                   buckets=(16, 32)).generate(prompts, GenerationConfig(max_new=8)),
    ]
    for a, b in zip(bank, rows):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("kw", [
    {"prefix_caching": True}, {"spec_draft": 2}, {"kv_dtype": "int8"},
    {"kv_dtype": "fp8"}, {"paged": False},
], ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()))
def test_each_refused_option_raises(model, params, kw):
    base = dict(paged=True, block_size=BLOCK, buckets=(16,), prefix_caching=False)
    with pytest.raises(ValueError, match="not served with window layers"):
        TextServer(model, params, **{**base, **kw})


def test_migration_and_unserved_kinds_raise(model, params):
    srv = _server(model, params)
    with pytest.raises(ValueError, match="KV migration is not served"):
        srv.submit(np.arange(5, dtype=np.int32), GenerationConfig(max_new=2),
                   prefill_only=True)
    with pytest.raises(ValueError, match="KV migration is not served"):
        srv.warm_import()
    for pattern, missing in (("GEME", "R-state"), ("GDKE", "R-state"),
                             ("GDLE", "R-latent")):
        m = HybridLM(
            64, 32, pattern, full_heads=4, num_kv_heads=2, head_dim=8,
            full_rotary=rotary.Rotary(1e4, 8), num_heads=4, kv_lora_rank=8,
            qk_nope_dim=8, v_head_dim=8, kda_heads=2, kda_head_dim=8,
            ssm_heads=2, ssm_head_dim=8, ssm_state=4, dense_dim=16,
            num_experts=4, experts_per_token=2, expert_dim=8, shared_dim=8,
            max_len=32)
        with pytest.raises(NotImplementedError, match=missing):
            TextServer(m, None, paged=True, block_size=4, buckets=(8,),
                       prefix_caching=False)
