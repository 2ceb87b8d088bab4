"""The paged decode step reads the pool through a live-block list (PR 29).

``ops/paged_attention.paged_decode_attention`` walks a compacted list of
the blocks that are resident, a tile at a time, into a per-slot running
softmax. Held here against the dense math it replaced, kept below as the
plain reference: gather every slot's WHOLE table into a contiguous view,
put the fresh row at ``lengths``, mask ``idx <= lengths`` (and the window's
band) and run one softmax over the view. Ragged residency is the point:
vacant slots, a slot that stops inside the chunk, lengths on a block's
edge and at the table's end, lists longer than a tile, a block two slots
share, a window, a quantized pool, a bfloat16 pool, six queries a KV head.
Every tile of the list holds one slot's blocks: each slot's entries are
padded up to whole tiles.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.ops import paged_attention as paged
from distributed_tensorflow_tpu.ops.quantized import dequantize_kv, quantize_kv

S, NB_SLOT, BS, NUM_BLOCKS = 4, 6, 4, 24  # 24 positions a slot
HQ, HKV, DH = 4, 2, 8
LAYERS, LAYER = 3, 1


def dense_reference(q, k_row, v_row, k_pool, v_pool, tables, lengths, act,
                    window=None):
    """The parent's step, one layer: the whole-table view with the fresh
    row selected into it (active rows only), one masked softmax. Over a
    bfloat16 pool the weights meet V narrowed to it, as the walk narrows
    them: ``exp(score − max)`` rounded to bfloat16, over the float32 sum
    (the walk's arithmetic where every slot fits one tile)."""
    def view(pool, row):
        got = pool[LAYER][tables]  # [S, NB, bs, Hkv, Dh]
        got = got.reshape(got.shape[0], -1, *got.shape[3:])
        here = (jnp.arange(got.shape[1])[None] == lengths[:, None]) & act[:, None]
        return jnp.where(here[:, :, None, None], row[:, None], got)

    ck, cv = view(k_pool, k_row), view(v_pool, v_row)
    idx = jnp.arange(ck.shape[1])[None]
    valid = idx <= lengths[:, None]
    if window is not None:
        valid &= idx > lengths[:, None] - window
    qg = q.reshape(q.shape[0], HKV, q.shape[1] // HKV, DH)
    scores = jnp.einsum("shgd,skhd->shgk", qg, ck.astype(jnp.float32))
    scores = jnp.where(valid[:, None, None], scores / np.sqrt(DH), -1e30)
    if cv.dtype != jnp.bfloat16:
        w = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("shgk,skhd->shgd", w, cv.astype(jnp.float32))
    w = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    fresh = (jnp.arange(ck.shape[1])[None] == lengths[:, None])[:, None, None]
    narrowed = jnp.where(fresh, w, w.astype(jnp.bfloat16).astype(jnp.float32))
    out = jnp.einsum("shgk,skhd->shgd", narrowed, cv.astype(jnp.float32))
    return out / w.sum(axis=-1)[..., None]


def make_pools(rng, kv_dtype=None):
    shape = (LAYERS, NUM_BLOCKS, BS, HKV, DH)
    k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    if kv_dtype is None:
        return k, v, None, None
    if kv_dtype == "bfloat16":
        return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), None, None
    (k, ks), (v, vs) = quantize_kv(k, kv_dtype), quantize_kv(v, kv_dtype)
    return k, v, ks, vs


def disjoint_tables(rng):
    return jnp.asarray(
        rng.permutation(NUM_BLOCKS).reshape(S, NB_SLOT), jnp.int32)


def attend(q, k_row, v_row, pools, tables, lengths, act, *, steps=1,
           window=None, flat=False, live=None):
    k, v, ks, vs = pools
    if live is None:
        live = paged.live_block_list(
            tables, lengths, act, steps, NUM_BLOCKS, BS)
    if flat:
        k, v = (p.reshape(p.shape[:3] + (-1,)) for p in (k, v))
    return paged.paged_decode_attention(
        q, k_row, v_row, k, v, LAYER, live, lengths, window=window,
        k_scale=ks, v_scale=vs)


CASES = {
    # name: (lengths, active, window, kv_dtype, tile positions, flat rows,
    #        query heads)
    "ragged": ([5, 17, 0, 9], [1, 1, 1, 1], None, None, 512, False, HQ),
    "one_resident": ([0, 13, 0, 0], [0, 1, 0, 0], None, None, 512, True, HQ),
    "block_edge": ([4, 8, 16, 20], [1, 1, 1, 1], None, None, 512, False, HQ),
    "table_end": ([23, 1, 23, 12], [1, 1, 1, 1], None, None, 512, True, HQ),
    "many_tiles": ([21, 17, 23, 19], [1, 1, 1, 1], None, None, 8, True, HQ),
    "tile_of_one_block": (
        [21, 3, 11, 6], [1, 0, 1, 1], None, None, 4, False, HQ),
    "window": ([5, 17, 23, 9], [1, 1, 1, 1], 6, None, 8, True, HQ),
    "int8": ([5, 17, 2, 9], [1, 1, 0, 1], None, "int8", 8, True, HQ),
    "int8_window": ([20, 17, 2, 23], [1, 1, 1, 1], 5, "int8", 512, False, HQ),
    # six queries a KV head; slot 0 spans three tiles, slot 1 one block
    "group_of_six": ([21, 2, 0, 14], [1, 1, 0, 1], None, None, 8, True, 12),
    # float32 queries against a bfloat16 pool: the scores' split product
    "bf16_pool": ([5, 17, 0, 23], [1, 1, 0, 1], None, "bfloat16", 512, False,
                  12),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_list_walk_equals_the_whole_table_softmax(case, monkeypatch):
    lengths, act, window, kv_dtype, tile_positions, flat, hq = CASES[case]
    monkeypatch.setattr(paged, "TILE_POSITIONS", tile_positions)
    rng = np.random.default_rng(sorted(CASES).index(case))
    lengths = jnp.asarray(lengths, jnp.int32)
    act = jnp.asarray(act, bool)
    pools = make_pools(rng, kv_dtype)
    tables = disjoint_tables(rng)
    q = jnp.asarray(rng.normal(size=(S, hq, DH)), jnp.float32)
    k_row = jnp.asarray(rng.normal(size=(S, HKV, DH)), jnp.float32)
    v_row = jnp.asarray(rng.normal(size=(S, HKV, DH)), jnp.float32)
    if kv_dtype == "bfloat16":  # the rows as the pool will hold them
        k_row, v_row = k_row.astype(jnp.bfloat16), v_row.astype(jnp.bfloat16)
        dense_pools = pools[:2]
    elif kv_dtype is not None:  # a quantized pool attends what it will hold
        k_row = dequantize_kv(*quantize_kv(k_row, kv_dtype))
        v_row = dequantize_kv(*quantize_kv(v_row, kv_dtype))
        dense_pools = (dequantize_kv(pools[0], pools[2]),
                       dequantize_kv(pools[1], pools[3]))
    else:
        dense_pools = pools[:2]
    got = attend(q, k_row, v_row, pools, tables, lengths, act,
                 window=window, flat=flat)
    want = dense_reference(
        q, k_row, v_row, *dense_pools, tables, lengths, act, window)
    rows = np.asarray(act)  # an inactive row's output is garbage by contract
    np.testing.assert_allclose(
        np.asarray(got)[rows], np.asarray(want)[rows], rtol=2e-5, atol=2e-6)


def test_a_block_two_slots_share_is_read_for_each(monkeypatch):
    """A cached prefix: slots 0 and 2 hold the same two blocks first, each
    its own after them. The list names the shared blocks once per slot."""
    monkeypatch.setattr(paged, "TILE_POSITIONS", 12)  # 3 blocks a tile
    rng = np.random.default_rng(11)
    pools = make_pools(rng)
    tables = np.array(disjoint_tables(rng))
    tables[2, :2] = tables[0, :2]
    tables = jnp.asarray(tables)
    lengths = jnp.asarray([13, 6, 10, 0], jnp.int32)
    act = jnp.asarray([1, 1, 1, 0], bool)
    live = paged.live_block_list(tables, lengths, act, 1, NUM_BLOCKS, BS)
    assert int(live.live) == 4 + 2 + 3
    blocks = np.asarray(live.block)
    assert sorted(blocks).count(int(tables[0, 0])) == 2
    q, k_row, v_row = (
        jnp.asarray(rng.normal(size=shape), jnp.float32)
        for shape in ((S, HQ, DH), (S, HKV, DH), (S, HKV, DH)))
    got = attend(q, k_row, v_row, pools, tables, lengths, act, live=live)
    want = dense_reference(q, k_row, v_row, *pools[:2], tables, lengths, act)
    np.testing.assert_allclose(
        np.asarray(got)[:3], np.asarray(want)[:3], rtol=2e-5, atol=2e-6)


def test_one_list_serves_a_chunk_in_which_a_slot_stops(monkeypatch):
    """The server makes the list once for ``chunk`` steps. Slot 1 stops
    after two of them (its length stands still, its row is no longer
    written); slot 3 crosses a block's edge inside the chunk; slot 2 was
    vacant from the start and owns no entry. Every step's rows are
    committed as the engine commits them."""
    monkeypatch.setattr(paged, "TILE_POSITIONS", 8)
    rng = np.random.default_rng(5)
    k, v, _, _ = make_pools(rng)
    tables = disjoint_tables(rng)
    lengths = jnp.asarray([6, 9, 0, 2], jnp.int32)
    start = jnp.asarray([1, 1, 0, 1], bool)
    chunk = 5
    live = paged.live_block_list(tables, lengths, start, chunk, NUM_BLOCKS, BS)
    # positions below lengths + chunk - 1 are all a step can read
    assert int(live.live) == 3 + 4 + 0 + 2
    assert int(live.walked) == 4 + 4 + 0 + 2  # each slot's, whole tiles
    for step in range(chunk):
        act = start & jnp.asarray([True, step < 2, False, True])
        q, k_row, v_row = (
            jnp.asarray(rng.normal(size=shape), jnp.float32)
            for shape in ((S, HQ, DH), (S, HKV, DH), (S, HKV, DH)))
        got = attend(q, k_row, v_row, (k, v, None, None), tables, lengths,
                     act, live=live)
        want = dense_reference(q, k_row, v_row, k, v, tables, lengths, act)
        rows = np.asarray(act)
        np.testing.assert_allclose(
            np.asarray(got)[rows], np.asarray(want)[rows],
            rtol=2e-5, atol=2e-6)
        every_layer = lambda r: jnp.broadcast_to(r[None], (LAYERS,) + r.shape)  # noqa: E731
        k = paged.commit_token_rows(k, every_layer(k_row), tables, lengths, act)
        v = paged.commit_token_rows(v, every_layer(v_row), tables, lengths, act)
        lengths = lengths + act.astype(jnp.int32)


def test_the_list_is_compact_ordered_and_skips_what_was_never_reserved():
    tables = np.full((3, 5), 40, np.int32)  # 40: the sentinel block
    tables[0, :3] = [7, 2, 9]
    tables[1, :1] = [4]  # reserved one block; the chunk would reach two
    tables[2, :4] = [1, 3, 5, 8]
    live = paged.live_block_list(
        jnp.asarray(tables), jnp.asarray([5, 2, 9], jnp.int32),
        jnp.asarray([True, True, False]), 4, 40, 4)
    assert int(live.live) == 2 + 1  # slot 0 reads below 8; slot 1's
    # second entry is none. A tile is a table's 5 blocks: each slot's
    # entries come first in its own tile, the sentinel after them
    block, slot, start = (
        np.asarray(a).tolist() for a in (live.block, live.slot, live.start))
    assert block[:10] == [7, 2, 40, 40, 40, 4, 40, 40, 40, 40]
    assert slot[:10] == [0] * 5 + [1] * 5
    assert start[:2] == [0, 4] and start[5] == 0
    # padding and the entries past the walk: the sentinel block,
    # starting past every length
    assert set(start[2:5] + start[6:]) == {20}
    assert set(block[10:]) == {40}
    assert live.block.shape == (15,)  # three tables' size in whole tiles
    assert int(live.walked) == 10  # two slots × one tile of 5


@pytest.mark.parametrize("tile_positions", [8, 12, 512])
def test_every_tile_holds_one_slot_and_pads_past_its_length(
        tile_positions, monkeypatch):
    """However the residents' blocks fall against the tiles, a tile's
    entries all belong to one slot, a slot's real entries come first in
    block order, and its padding names the sentinel and starts past the
    slot's length; the tiles walked are the slots' own, rounded up."""
    monkeypatch.setattr(paged, "TILE_POSITIONS", tile_positions)
    rng = np.random.default_rng(7)
    tables = disjoint_tables(rng)
    lengths = jnp.asarray([9, 0, 23, 4], jnp.int32)
    act = jnp.asarray([1, 0, 1, 1], bool)
    live = paged.live_block_list(tables, lengths, act, 2, NUM_BLOCKS, BS)
    tile = min(tile_positions // BS, NB_SLOT)
    block, slot, start = (
        np.asarray(a) for a in (live.block, live.slot, live.start))
    walked = int(live.walked)
    need = {0: 3, 2: 6, 3: 2}  # blocks below lengths + 1 (two steps)
    assert walked == sum(-(-n // tile) * tile for n in need.values())
    assert int(live.live) == sum(need.values())
    for t in range(walked // tile):
        owner = set(slot[t * tile:(t + 1) * tile].tolist())
        assert len(owner) == 1 and owner <= set(need)
    for owner, n in need.items():
        mine = slot[:walked] == owner
        assert block[:walked][mine][:n].tolist() == (
            np.asarray(tables)[owner, :n].tolist())
        assert start[:walked][mine][:n].tolist() == [
            i * BS for i in range(n)]
        # the padding: the sentinel, past what two steps can reach
        assert (block[:walked][mine][n:] == NUM_BLOCKS).all()
        assert (start[:walked][mine][n:] >= int(lengths[owner]) + 2).all()
    assert (block[walked:] == NUM_BLOCKS).all()


def _model(window=None):
    return GPTLM(vocab_size=61, max_len=32, model_dim=32, num_heads=4,
                 num_layers=2, window=window, compute_dtype=jnp.float32)


def _random_params(model, seed=3):
    params = model.init(seed)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf if leaf.ndim < 2
        else 0.5 * jax.random.normal(key, leaf.shape, leaf.dtype)
        for leaf, key in zip(leaves, keys)])


@pytest.mark.parametrize("window", [None, 7])
def test_decode_paged_follows_decode_slots_over_ragged_rows(window, monkeypatch):
    """The model's own step: three prompts of unlike lengths prefilled
    into a paged pool and into slabs, then stepped side by side, one slot
    vacant, one going inactive half way. Same logits, step for step, with
    a list made per step inside ``decode_paged`` and with one made once
    for all the steps."""
    monkeypatch.setattr(paged, "TILE_POSITIONS", 8)
    model = _model(window)
    params = _random_params(model)
    slots, bs, steps = 4, 4, 6
    rng = np.random.default_rng(2)
    plens = np.asarray([9, 3, 0, 14], np.int32)
    toks = rng.integers(0, 61, (slots, 16)).astype(np.int32)
    admit = jnp.asarray(plens > 0)
    paged_cache = model.empty_paged_cache(slots, 32, bs)
    tables = rng.permutation(32).reshape(slots, 8).astype(np.int32)
    paged_cache = paged_cache._replace(block_tables=jnp.asarray(tables))
    _, paged_cache = model.extend_paged(
        params, paged_cache, jnp.asarray(toks), jnp.asarray(plens),
        jnp.zeros((slots,), jnp.int32), admit)
    paged_cache = paged_cache._replace(lengths=jnp.asarray(plens))
    _, slab = model.prefill_slots(
        params, model.empty_slot_cache(slots), jnp.asarray(toks),
        jnp.asarray(plens), admit)
    once = paged.live_block_list(
        paged_cache.block_tables, paged_cache.lengths, admit, steps, 32, bs)
    caches = {"per_step": paged_cache, "once": paged_cache}
    tok = jnp.asarray(rng.integers(0, 61, (slots,)), jnp.int32)
    for step in range(steps):
        act = admit & jnp.asarray([True, step < 3, False, True])
        want, slab = model.decode_slots(params, tok, slab, act)
        for name, live in (("per_step", None), ("once", once)):
            got, caches[name] = model.decode_paged(
                params, tok, caches[name], act, live=live)
            rows = np.asarray(act)
            np.testing.assert_allclose(
                np.asarray(got)[rows], np.asarray(want)[rows],
                rtol=1e-4, atol=1e-4, err_msg=f"{name} step {step}")
        tok = jnp.argmax(want, axis=-1).astype(jnp.int32)


# -- the two counts a chunk sends the host, and the benchmark's reader ----------


def test_decode_chunk_spans_carry_the_list_s_counts(monkeypatch):
    """``kv_blocks_live`` / ``kv_blocks_read`` on each decode_chunk span are
    what the host can work out from the residents' lengths when the chunk
    starts; ``kv_read_share_pct.chat`` reads them, and reads nothing from a
    server whose chunk walks no list."""
    from benchmark.lib import harness
    from distributed_tensorflow_tpu.observability import names
    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

    monkeypatch.setattr(paged, "TILE_POSITIONS", 16)  # 4 blocks a tile
    model = _model()
    slots, chunk, bs = 3, 4, 4
    prompts, budgets = [5, 11, 3], [7, 10, 6]

    def serve(**layout):
        srv = TextServer(model, _random_params(model), slots=slots,
                         chunk=chunk, buckets=(8, 16), **layout)
        rng = np.random.default_rng(1)
        for n, b in zip(prompts, budgets):
            srv.submit(rng.integers(0, 61, (n,)).astype(np.int32),
                       GenerationConfig(max_new=b))
        while srv.step():
            pass
        return [s for s in srv.spans.spans
                if s["name"] == names.SPAN_DECODE_CHUNK]

    spans = serve(paged=True, block_size=bs)
    done = [1, 1, 1]  # tokens out so far: the prefill picked each one's first
    for span in spans:
        resident = [i for i in range(3) if done[i] < budgets[i]]
        assert span["args"]["active"] == len(resident)
        # the pool holds prompt + done - 1 rows; the chunk reads below
        # that + chunk - 1
        blocks = [
            -(-(prompts[i] + done[i] - 1 + chunk - 1) // bs) for i in resident]
        assert span["args"]["kv_blocks_live"] == sum(blocks)
        # one slot a tile: each slot's blocks rounded up to whole tiles
        assert span["args"]["kv_blocks_read"] == sum(
            -(-n // 4) * 4 for n in blocks)
        for i in resident:
            done[i] = min(budgets[i], done[i] + chunk)
    assert len(spans) == 3 and done == budgets

    def share(spans):
        run = harness.Run(
            "serve-gpt2l-chat", {"n_positions": model.max_len},
            {"server": {"slots": slots, "block_size": bs}}, 1, {},
            spans=[dict(s, kind="span") for s in spans])
        return harness.metric_reader("kv_read_share_pct.chat")(run)

    walked = sum(s["args"]["kv_blocks_read"] for s in spans)
    assert share(spans) == pytest.approx(100 * walked / (3 * slots * 8))
    slab = serve()
    assert slab and "kv_blocks_read" not in slab[0]["args"]
    assert share(slab) is None and share([]) is None
