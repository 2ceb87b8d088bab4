"""``HybridLM`` with gated delta-rule layers that decay per head (``N``),
gated full attention with q/k norms and a gate per element (``G``) and
softmax-routed experts with a gated shared expert (``E``), served by
``TextServer``'s paged engine: each slot's recurrent and convolution
states beside the full layers' block pool (models/hybrid.py, ops/kda.py,
ops/moe.py, serve.py).

Held against the benchmark's plain reference
(``benchmark/lib/reference_qwen3next.py``: one float32 forward over the
whole sequence, the delta rule token by token, dense softmax, every held
expert on every row) at a tiny size on the CPU, over contexts that span
several 64-token chunks of the prefill's scan. Single-device.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import reference_qwen3next, weights_qwen3next
from distributed_tensorflow_tpu.models.hybrid import HybridLM
from distributed_tensorflow_tpu.ops import kda, rotary
from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

BLOCK, MAX_LEN = 8, 256
CFG = {  # a tiny qwen3_next configuration, read by the reference's dims
    "hidden_size": 32, "vocab_size": 97, "num_hidden_layers": 4,
    "full_attention_interval": 4, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "linear_num_key_heads": 2,
    "linear_key_head_dim": 8, "linear_num_value_heads": 4,
    "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
    "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 8,
    "shared_expert_intermediate_size": 8, "rms_norm_eps": 1e-6,
    "published": {"num_hidden_layers": 4, "num_experts": 16},
    "deployment": {"pattern": "NENENEGE"},
}
Z = weights_qwen3next.dims(CFG)


def _model(held=None, **kw):
    return HybridLM(
        Z["vocab"], Z["d"], Z["pattern"], full_heads=Z["heads"],
        num_kv_heads=Z["kv_heads"], head_dim=Z["head_dim"],
        full_rotary=rotary.Rotary(**Z["rope"]), full_gate="element",
        full_qk_norm=True, gdn_key_heads=Z["key_heads"],
        gdn_key_dim=Z["key_dim"], gdn_value_heads=Z["value_heads"],
        gdn_value_dim=Z["value_dim"], conv_kernel=Z["conv"],
        expert_form="silu_gated", moe_router="softmax",
        num_experts=Z["experts"], experts_held=held,
        experts_per_token=Z["top_k"], expert_dim=Z["expert_dim"],
        shared_dim=Z["shared_dim"], norm_offset=1.0, norm_eps=Z["eps"],
        max_len=MAX_LEN, compute_dtype=jnp.float32, init_std=0.2, **kw)


@pytest.fixture(scope="module")
def model():
    # float32 products: the served logits and the reference's then differ
    # by the order of float32 sums alone
    return _model()


@pytest.fixture(scope="module")
def params(model):
    p = model.init(3)
    # decays of 1-5% a token, so that a state carries across the chunks
    a = jnp.log(jax.random.uniform(jax.random.key(9), p.gdn.a_log.shape,
                                   minval=0.01, maxval=0.05))
    return p._replace(gdn=p.gdn._replace(a_log=a))


def _tree(params) -> dict:
    """The program's tree as the reference reads it."""
    tree = {g: getattr(params, g)._asdict() for g in ("gdn", "full", "moe")}
    tree.update(embed=params.embed, head=params.head, norm_f=params.norm_f)
    return tree


def _reference_logits(params, seq) -> np.ndarray:
    tokens = np.zeros(MAX_LEN, np.int32)
    tokens[: len(seq)] = seq
    h = reference_qwen3next.final_hidden(_tree(params), tokens, Z)
    return np.asarray(jnp.matmul(h, params.head, precision="highest"))[: len(seq)]


def _extend(model, params, prompts, slots=2, width=192):
    cache = model.empty_paged_cache(slots, slots * MAX_LEN // BLOCK, BLOCK)
    nb = MAX_LEN // BLOCK
    cache = cache._replace(block_tables=jnp.asarray(
        np.arange(slots * nb, dtype=np.int32).reshape(slots, nb)))
    toks = np.zeros((slots, width), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : p.size] = p
    lens = jnp.asarray([p.size for p in prompts], jnp.int32)
    logits, cache = jax.jit(model.extend_paged)(
        params, cache, jnp.asarray(toks), lens, jnp.zeros(slots, jnp.int32),
        jnp.ones(slots, bool))
    return logits, cache._replace(lengths=lens)


def test_prefill_then_decode_logits_match_the_reference(model, params):
    """``extend_paged`` (the chunked scan over 150 and 70 positions) then
    ``decode_paged`` step by step, two slots at once, against the
    reference's logits at every generated position. Tolerance 1e-3:
    float32 on both sides, the chunked scan's sums in another order than
    the token-by-token recurrence's (logits here are O(10))."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, Z["vocab"], n).astype(np.int32) for n in (150, 70)]
    logits, cache = _extend(model, params, prompts)
    seqs = [list(p) for p in prompts]
    got = [[np.asarray(logits[i])] for i in range(2)]
    step = jax.jit(model.decode_paged)
    for _ in range(12):
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
            np.stack(got[i]), want[n0 - 1: n0 + 12], atol=1e-3, rtol=0)


def test_right_padded_rows_leave_each_rows_state_at_its_real_length(
        model, params):
    """A row padded from 37 to 192 positions leaves the recurrent state
    and the convolution rows the row alone leaves (pads neither decay nor
    write); its neighbour's are its own."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, Z["vocab"], n).astype(np.int32) for n in (37, 130)]
    _, padded = _extend(model, params, prompts)
    _, alone = _extend(model, params, prompts[:1], slots=1, width=40)
    for name in ("gdn_state", "gdn_conv"):
        for got, want in zip(padded.slot_state[name], alone.slot_state[name]):
            np.testing.assert_allclose(got[0], want[0], atol=1e-5, rtol=1e-5)
            assert np.abs(np.asarray(got[1] - got[0])).max() > 1e-3


def _server(model, params, **kw):
    kw.setdefault("slots", 2)
    return TextServer(model, params, paged=True, chunk=4, block_size=BLOCK,
                      buckets=(160,), prefix_caching=False, **kw)


def test_a_freed_and_refilled_slot_reads_no_stale_state(model, params):
    """One slot: a long request leaves its state behind; a short one takes
    the slot, and both streams are the reference's greedy tokens (the
    short one's from a state that starts at zero). The server's gauge
    counts the slot's state while it is busy."""
    rng = np.random.default_rng(2)
    long_p = rng.integers(0, Z["vocab"], 140).astype(np.int32)
    short_p = rng.integers(0, Z["vocab"], 9).astype(np.int32)
    used = _server(model, params, slots=1)
    total = used.metrics.gauge("slot_state_bytes_total").value
    kd, vd = 2 * 8, 4 * 8
    assert total == 3 * 4 * (4 * 8 * 8 + 3 * (2 * kd + vd))  # 3 N layers, f32
    first = used.generate([long_p], GenerationConfig(max_new=10))[0]
    rid = used.submit(short_p, GenerationConfig(max_new=12))
    used.step()
    assert used.metrics.gauge("slot_state_bytes_used").value == total
    while used.step():
        pass
    again = used.result(rid)
    assert used.metrics.gauge("slot_state_bytes_used").value == 0
    for p, o in ((long_p, first), (short_p, again)):
        want = _reference_logits(params, np.concatenate([p, o]))
        assert (np.argmax(want[p.size - 1: p.size - 1 + o.size], -1) == o).all()


@pytest.mark.parametrize("decay", ["per_channel", "per_head"])
def test_the_one_token_update_and_the_chunked_final_state_agree_with_the_recurrence(
        decay):
    """``kda_step`` token by token gives ``kda_sequential``'s outputs, and
    ``kda_chunked``'s final state is the state the steps end in (a length
    that is no multiple of the chunk: the scan's own pads pass it through),
    for both forms of the decay."""
    b, l, h, width = 2, 43, 3, 8
    ks = jax.random.split(jax.random.key(5), 5)
    q, k = (jax.random.normal(key, (b, l, h, width)) for key in ks[:2])
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q, k = unit(q), unit(k)
    v = jax.random.normal(ks[2], (b, l, h, width))
    shape = (b, l, h, width) if decay == "per_channel" else (b, l, h)
    g = -jax.random.uniform(ks[3], shape, minval=0.01, maxval=0.3)
    beta = jax.random.uniform(ks[4], (b, l, h))
    full_g = g if g.ndim == 4 else jnp.broadcast_to(g[..., None], q.shape)
    hi = jax.lax.Precision.HIGHEST
    want = kda.kda_sequential(q, k, v, full_g, beta, precision=hi)
    s = jnp.zeros((b, h, width, width))
    outs = []
    step = jax.jit(kda.kda_step)
    for t in range(l):
        o, s = step(s, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), want, atol=1e-5, rtol=1e-5)
    o, state = kda.kda_chunked(q, k, v, full_g, beta, chunk=16, precision=hi,
                               final_state=True)
    np.testing.assert_allclose(o, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(state, s, atol=1e-4, rtol=1e-4)


def test_four_expert_shares_add_up_to_the_uncut_layer(params):
    """Four holders of 4 of the 16 experts each: their routed parts, with
    the gated shared expert (computed alike by all) counted once, add up to
    the reference's whole expert layer over all 16."""
    h = jax.random.normal(jax.random.key(7), (1, 24, Z["d"]))
    layer = {k: v[0] for k, v in params.moe._asdict().items()}
    whole = reference_qwen3next._experts_layer(
        layer, h[0], tuple(sorted({**Z, "held": 16, "rope": ()}.items())),
        "float32", None)
    parts = []
    for first in range(0, 16, 4):
        share = _model(held=(first, 4))
        p = jax.tree.map(lambda x: x[0], params.moe)
        p = p._replace(**{n: getattr(p, n)[first:first + 4]
                          for n in ("w_gate", "w_up", "w_down")})
        parts.append(jax.jit(share._experts)(p, h)[0][0])
    none = _model(held=(0, 1))
    p0 = jax.tree.map(lambda x: x[0], params.moe)
    p0 = p0._replace(**{n: jnp.zeros_like(getattr(p0, n)[:1])
                        for n in ("w_gate", "w_up", "w_down")})
    shared = jax.jit(none._experts)(p0, h)[0][0]  # the shared expert alone
    got = sum(parts) - 3 * shared
    np.testing.assert_allclose(h[0] + got, whole, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("what", [
    "prefix_caching", "spec_draft", "kv_dtype", "migration"])
def test_each_option_that_needs_the_pool_alone_refuses_a_recurrent_state(
        model, params, what):
    base = dict(paged=True, block_size=BLOCK, buckets=(16,),
                prefix_caching=False)
    if what == "migration":
        srv = _server(model, params)
        with pytest.raises(ValueError, match="KV migration is not served"):
            srv.submit(np.arange(5, dtype=np.int32),
                       GenerationConfig(max_new=2), prefill_only=True)
        return
    bad = {"prefix_caching": True, "spec_draft": 2, "kv_dtype": "int8"}[what]
    with pytest.raises(ValueError, match="recurrent states in the cache"):
        TextServer(model, params, **{**base, what: bad})


@pytest.mark.parametrize("pattern,missing", [
    ("GEME", "state-space"), ("GDKE", "decay per key channel"),
    ("GDLE", "R-latent")])
def test_kinds_without_a_serving_path_still_refuse(pattern, missing):
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
