"""The serving step against the plain full forward.

Every decode surface the server drives — the slab pair ``prefill_slots``
+ ``decode_slots``, the paged pair ``extend_paged`` + ``decode_paged``
(with a live-block list and flat pool rows, as ``TextServer``'s chunk
program hands them in) and the speculative verify (``extend_paged`` over
a suffix on a prefilled pool) — is held here to the logits of ONE causal
forward over the whole sequence, which knows no cache, no slot and no
block table. For learned positions with plain multi-head attention that
forward is the benchmark's reference (``benchmark/lib/reference.py``,
which imports nothing from the program); grouped-query heads, windows
and rotary positions are outside what it implements, and there the
oracle is ``GPTLM.apply`` at float32 on the whole sequence.

Weights come from ``benchmark/lib/weights.make``: the program's own
``init`` zeroes ``wo`` and ``w_down``, under which attention never
reaches the logits and a cache read wrongly would still pass.

An unquantized cache must follow the forward to float32 rounding. A
quantized one (int8, fp8 rows with a scale a head) reads within a stated
budget, and the same step with the scale rows dropped must read outside
it: a budget nothing can fail holds nothing.

Single-device and tiny: no conftest._CACHE_OPT_OUT_FIRST entry.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import reference, weights
from benchmark.lib.train_cell import to_program_params
from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.ops import paged_attention
from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

VOCAB, MAX_LEN, DIM, LAYERS = 97, 64, 32, 2
PROMPT_LENS = (8, 5, 3)
SLOTS = len(PROMPT_LENS)
STEPS = 6
# one row sits out on alternate steps, as a finished slot rides a chunk
ACTIVE = ((True, True, False), (True, False, True))
BLOCK, POOL_BLOCKS = 8, 24
# a row's prompt, the STEPS tokens it may be fed and the planted fault's
SEQ = max(PROMPT_LENS) + STEPS + 1

ATTENTION = {
    "mha": dict(num_heads=4),
    "gqa": dict(num_heads=8, num_kv_heads=2),
    "window": dict(num_heads=4, window=8),
}
# Largest |logit - forward's| allowed, on logits of standard deviation
# 0.73 reaching 4.3. Read over the whole matrix below (slab and paged
# alike to three digits): unquantized 2.1e-6 at worst, int8 0.031, fp8
# (e4m3: three mantissa bits) 0.141; with the scale rows dropped 3.77 at
# least.
BUDGET = {"bf16": 1e-4, "int8": 0.06, "fp8": 0.25}


@functools.lru_cache(maxsize=None)
def _setup(attention: str, positions: str):
    """``(model, params, seqs, want)``: ``seqs`` [SLOTS, SEQ] holds each
    row's prompt followed by the forward's own greedy continuation (what
    the decode calls are fed, so every layout and cache type of one
    model scores the same positions), ``want`` [SLOTS, SEQ, vocab] the
    forward's logits over it."""
    model = GPTLM(
        vocab_size=VOCAB, max_len=MAX_LEN, model_dim=DIM, num_layers=LAYERS,
        compute_dtype=jnp.float32, pos_embedding=positions,
        **ATTENTION[attention],
    )
    tree = weights.make(
        dict(vocab_size=VOCAB, n_positions=MAX_LEN, n_embd=DIM,
             n_layer=LAYERS, initializer_range=0.2), seed=7)
    kv_width = model.num_kv_heads * model.head_dim
    blocks = dict(tree["blocks"])
    blocks["wk"] = blocks["wk"][..., :kv_width]
    blocks["wv"] = blocks["wv"][..., :kv_width]
    tree = {**tree, "blocks": blocks}
    params = to_program_params(tree)
    plain = (
        positions == "learned" and model.window is None
        and model.num_kv_heads == model.num_heads
    )
    if plain:  # the benchmark's reference: no code shared with the program
        forward = jax.jit(
            lambda toks: reference.logits(tree, toks, model.num_heads))
    else:
        forward = jax.jit(lambda toks: model.apply(params, toks))
    seqs = np.zeros((SLOTS, SEQ), np.int32)
    rng = np.random.default_rng(0)
    for s, n in enumerate(PROMPT_LENS):
        seqs[s, :n] = rng.integers(0, VOCAB, n)
    for j in range(STEPS + 1):
        logits = np.asarray(forward(jnp.asarray(seqs)))
        for s, n in enumerate(PROMPT_LENS):
            seqs[s, n + j] = logits[s, n + j - 1].argmax()
    return model, params, seqs, np.asarray(forward(jnp.asarray(seqs)))


def _paged_cache(model, kv):
    """An empty pool whose slots hold disjoint blocks in an order that
    is not the pool's."""
    cache = model.empty_paged_cache(SLOTS, POOL_BLOCKS, BLOCK, kv)
    nb = model.paged_blocks_per_slot(BLOCK)
    order = np.random.default_rng(1).permutation(POOL_BLOCKS)
    tables = order[: SLOTS * nb].reshape(SLOTS, nb)
    return cache._replace(block_tables=jnp.asarray(tables, jnp.int32))


def _prefilled(model, params, seqs, layout, kv):
    """The prompts in the cache: ``(each row's last prompt logits, cache)``."""
    toks = jnp.asarray(seqs[:, : max(PROMPT_LENS)])
    lens = jnp.asarray(PROMPT_LENS, jnp.int32)
    admit = jnp.ones((SLOTS,), bool)
    if layout == "slab":
        return jax.jit(model.prefill_slots)(
            params, model.empty_slot_cache(SLOTS, kv), toks, lens, admit)
    logits, cache = jax.jit(model.extend_paged)(
        params, _paged_cache(model, kv), toks, lens, jnp.zeros_like(lens),
        admit)
    last = jnp.take_along_axis(logits, (lens - 1)[:, None, None], axis=1)
    return last[:, 0], cache._replace(lengths=lens)


def _decoder(model, layout, cache):
    """``(step, cache)``: the decode call as the server's chunk program
    makes it. Paged: one live-block list for all STEPS, made while
    every row may still step, and the pools with each position's row
    flat."""
    if layout == "slab":
        return jax.jit(model.decode_slots), cache
    shape = cache.k.shape
    live = paged_attention.live_block_list(
        cache.block_tables, cache.lengths, jnp.ones((SLOTS,), bool),
        STEPS + 1, shape[1], shape[2])
    flat = shape[:3] + (-1,)
    cache = cache._replace(k=cache.k.reshape(flat), v=cache.v.reshape(flat))

    @jax.jit
    def step(params, tok, cache, active):
        return model.decode_paged(params, tok, cache, active, live=live)

    return step, cache


def _rows_of(layout, cache, slot):
    """Everything the cache holds for ``slot``, as numpy arrays."""
    parts = [cache.k, cache.v] + (
        [] if cache.k_scale is None else [cache.k_scale, cache.v_scale])
    if layout == "slab":
        return [np.asarray(p[:, slot]) for p in parts]
    blocks = np.asarray(cache.block_tables[slot])
    return [np.asarray(p[:, blocks]) for p in parts]


def _gap(logits, want) -> float:
    return float(np.abs(np.asarray(logits) - want).max())


@pytest.mark.parametrize("positions", ["learned", "rope"])
@pytest.mark.parametrize("attention", list(ATTENTION))
@pytest.mark.parametrize("kv", ["bf16", "int8", "fp8"])
@pytest.mark.parametrize("layout", ["slab", "paged"])
def test_batched_decode_follows_the_full_forward(
    layout, kv, attention, positions
):
    model, params, seqs, want = _setup(attention, positions)
    first, cache = _prefilled(model, params, seqs, layout, kv)
    step, cache = _decoder(model, layout, cache)
    lens = list(PROMPT_LENS)
    gaps = [_gap(first[s], want[s, n - 1]) for s, n in enumerate(lens)]
    for i in range(STEPS):
        act = ACTIVE[i % len(ACTIVE)]
        tok = jnp.asarray([seqs[s, n] for s, n in enumerate(lens)])
        before = cache
        logits, cache = step(params, tok, cache, jnp.asarray(act))
        for s in range(SLOTS):
            if act[s]:
                gaps.append(_gap(logits[s], want[s, lens[s]]))
                lens[s] += 1
            else:  # rode along: its rows as they were
                for a, b in zip(_rows_of(layout, before, s),
                                _rows_of(layout, cache, s)):
                    np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(np.asarray(cache.lengths), lens)
    assert len(gaps) == SLOTS + 2 * STEPS  # two rows step a turn
    assert max(gaps) <= BUDGET[kv], (max(gaps), BUDGET[kv])
    if kv == "bf16":
        return
    # The planted fault: one more step, the cache's scale rows dropped.
    tok = jnp.asarray([seqs[s, n] for s, n in enumerate(lens)])
    logits, _ = step(
        params, tok,
        cache._replace(k_scale=jnp.ones_like(cache.k_scale),
                       v_scale=jnp.ones_like(cache.v_scale)),
        jnp.ones((SLOTS,), bool))
    fault = max(_gap(logits[s], want[s, n]) for s, n in enumerate(lens))
    assert fault > BUDGET[kv], (fault, BUDGET[kv])


@pytest.mark.parametrize("attention", list(ATTENTION))
@pytest.mark.parametrize("kv", ["bf16", "int8", "fp8"])
def test_verify_extend_follows_the_full_forward(kv, attention):
    """The server's verify round: ``extend_paged`` scores a 4-token
    suffix behind each slot's cached prompt; a row that is not admitted
    and the pad position of a short suffix leave the pool alone."""
    positions = "learned" if attention == "mha" else "rope"
    model, params, seqs, want = _setup(attention, positions)
    _, cache = _prefilled(model, params, seqs, "paged", kv)
    suffix = np.stack([seqs[s, n: n + 4] for s, n in enumerate(PROMPT_LENS)])
    suffix_lens = (4, 3, 4)
    admit = (True, True, False)
    logits, after = jax.jit(model.extend_paged)(
        params, cache, jnp.asarray(suffix), jnp.asarray(suffix_lens),
        cache.lengths, jnp.asarray(admit))
    gaps = [
        _gap(logits[s, j], want[s, n + j])
        for s, n in enumerate(PROMPT_LENS) if admit[s]
        for j in range(suffix_lens[s])
    ]
    assert len(gaps) == 7 and max(gaps) <= BUDGET[kv], (max(gaps), kv)
    for a, b in zip(_rows_of("paged", cache, 2), _rows_of("paged", after, 2)):
        np.testing.assert_array_equal(a, b)
    # row 1's fourth suffix position is a pad: its place keeps its bytes
    at = PROMPT_LENS[1] + 3
    blk = int(cache.block_tables[1, at // BLOCK])
    np.testing.assert_array_equal(
        np.asarray(cache.k[:, blk, at % BLOCK]),
        np.asarray(after.k[:, blk, at % BLOCK]))


@pytest.mark.parametrize(
    "value", [None, "auto", "xla", "pallas", "pallas-layer", "nonsense"])
def test_textserver_decode_engine_keyword(value):
    """``TextServer`` still takes the keyword the benchmark's traffic
    files pass: the three values that meant the one engine build a
    server whose greedy stream is ``greedy_decode``'s; the removed
    tier's names, and anything else, raise."""
    model, params, seqs, _ = _setup("mha", "learned")
    kw = dict(slots=2, chunk=4, paged=True, block_size=8, buckets=(8, 16))
    if value not in (None, "auto", "xla"):
        with pytest.raises(ValueError, match="removed"):
            TextServer(model, params, decode_engine=value, **kw)
        return
    server = TextServer(model, params, decode_engine=value, **kw)
    assert not hasattr(server, "decode_engine")
    prompt = seqs[0, : PROMPT_LENS[0]]
    (out,) = server.generate([prompt], [GenerationConfig(max_new=6)])
    want = model.greedy_decode(params, jnp.asarray(prompt[None]), 6)
    np.testing.assert_array_equal(out, np.asarray(want)[0, prompt.size:])
    np.testing.assert_array_equal(out, seqs[0, prompt.size: prompt.size + 6])
