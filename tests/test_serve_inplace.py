"""The paged decode step leaves the KV pool where it lies (PR 27).

Two halves of one mechanism, each held here on the CPU: the programs that
return the server's state take it DONATED (so the compiled program aliases
the pools to its outputs, and the buffers a dispatch was given are gone
after it), and the chunk program's loop reads the stacked pool through
``(layer, block_tables)`` and commits the fresh rows by one indexed
update, without slicing a layer out of the pool or stacking layers back
into one. What the chip's compiler makes of that loop is
``tests/test_chip_compile.py``'s to ask; the times are PERF.md's.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

VOCAB = 61
KV = ["bf16", "int8"]


def _model():
    return GPTLM(
        vocab_size=VOCAB, max_len=64, model_dim=32, num_heads=4, num_layers=3
    )


def _params(model, seed=7):
    """Weights with every block doing work (``init`` zeroes the residual
    projections, which makes each block the identity)."""
    params = model.init(seed)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf if leaf.ndim < 2
        else 0.6 * jax.random.normal(key, leaf.shape, leaf.dtype)
        for leaf, key in zip(leaves, keys)
    ])


def _server(kv_dtype, paged=True):
    model = _model()
    layout = dict(paged=True, block_size=4) if paged else {}
    return TextServer(
        model, _params(model), slots=3, chunk=4, buckets=(8, 16),
        kv_dtype=kv_dtype, **layout,
    )


def _pools(state):
    return {
        name: getattr(state, name)
        for name in ("k", "v", "k_scale", "v_scale")
        if getattr(state, name) is not None
    }


# nothing shared: a cached prefix would put two of them in different waves
PROMPTS = [
    np.arange(start, start + n, dtype=np.int32) % VOCAB
    for start, n in ((3, 5), (20, 9), (40, 14))
]
CONFIGS = [
    GenerationConfig(max_new=14),
    GenerationConfig(max_new=11, greedy=False, seed=3, top_p=0.9),
    GenerationConfig(max_new=9),
]


@pytest.mark.parametrize("kv_dtype", KV)
def test_chunk_program_aliases_the_pools_to_its_outputs(kv_dtype):
    srv = _server(kv_dtype)
    text = srv._chunk_jit.lower(srv.params, srv._state).compile().as_text()
    alias = {
        int(out): int(param) for out, param in re.findall(
            r"\{(\d+)\}: \((\d+), \{\}", text.split("entry_computation_layout")[0])
    }
    param_of = {
        name: int(number) for number, name in re.findall(
            r'parameter\((\d+)\)[^\n]*op_name="st\.(\w+)"', text)
    }
    # the state is the program's first result, leaf for leaf
    fields = [f for f in srv._state._fields if getattr(srv._state, f) is not None]
    pools = _pools(srv._state)
    assert set(pools) == ({"k", "v"} if kv_dtype == "bf16"
                          else {"k", "v", "k_scale", "v_scale"})
    for name in pools:
        assert alias.get(fields.index(name)) == param_of[name], (name, alias)


def _loop_body(hlo: str) -> str:
    """The body of the chunk's loop in a lowered (not yet optimized) module:
    the one ``while`` of the entry computation."""
    entry = hlo[hlo.index("ENTRY "):]
    body = re.search(r"while\([^\n]*body=%?([\w.\-]+)", entry).group(1)
    start = hlo.index(f"{body} ")
    return hlo[start: hlo.index("\n}\n", start)]


@pytest.mark.parametrize("kv_dtype", KV)
def test_chunk_loop_neither_slices_nor_stacks_the_pool(kv_dtype):
    srv = _server(kv_dtype)
    hlo = srv._chunk_jit.lower(srv.params, srv._state).as_text(dialect="hlo")
    called = dict(re.findall(r"\n%?([\w.\-]+) [^\n]*\{\n(.*?)\n\}\n", hlo, re.S))
    seen, todo, ops = set(), [_loop_body(hlo)], []
    while todo:  # the body and every computation it calls
        text = todo.pop()
        ops += re.findall(r"= \w+\[([\d,]*)\]\S* ([\w\-]+)\(", text)
        for name in re.findall(r"(?:to_apply|calls|body|condition)=%?([\w.\-]+)", text):
            if name in called and name not in seen:
                seen.add(name)
                todo.append(called[name])
    pools = _pools(srv._state)
    whole = {p.shape[:3] + (int(np.prod(p.shape[3:])),) for p in pools.values()}
    whole |= {p.shape for p in pools.values()}
    banned = whole | {s[1:] for s in whole} | {(1,) + s[1:] for s in whole}
    moved = [
        (op, dims) for dims, op in ops
        if op in ("concatenate", "copy", "slice", "dynamic-slice")
        and tuple(int(d) for d in dims.split(",") if d) in banned
    ]
    assert not moved, moved
    # and what does touch the pool is there: a gather per layer and pool,
    # one scatter per pool
    scatters = [dims for dims, op in ops if op == "scatter"
                and tuple(int(d) for d in dims.split(",") if d) in whole]
    assert len(scatters) == len(pools), scatters
    assert sum(o == "gather" for _, o in ops) >= srv.model.num_layers * len(pools)


@pytest.mark.parametrize("kv_dtype", KV)
def test_step_gives_the_state_away_and_serves_on(kv_dtype):
    srv = _server(kv_dtype)
    want = _server(kv_dtype).generate(PROMPTS, CONFIGS)
    rids = [srv.submit(p, c) for p, c in zip(PROMPTS, CONFIGS)]
    before = srv._state
    assert srv.step()  # one admission round, one chunk
    for name, pool in _pools(before).items():
        assert pool.is_deleted(), name
    assert not any(p.is_deleted() for p in _pools(srv._state).values())
    while srv.step():
        pass
    for got, ref in zip([srv.result(r) for r in rids], want):
        np.testing.assert_array_equal(got, ref)
    if kv_dtype == "bf16":
        greedy = srv.model.greedy_decode(
            srv.params, jnp.asarray(PROMPTS[0])[None], CONFIGS[0].max_new)
        np.testing.assert_array_equal(
            np.asarray(greedy)[0, PROMPTS[0].size:], want[0])


class _Boom(RuntimeError):
    pass


@pytest.mark.parametrize("program,when,paged", [
    ("_chunk_jit", "before", True), ("_chunk_jit", "after", True),
    ("_prefill_jit", "after", True), ("_chunk_jit", "after", False),
])
def test_a_program_that_raises_leaves_a_usable_server(program, when, paged):
    """``before``: the program raises with the state untouched (tracing,
    compiling, an argument refused) and the server stands as it stood.
    ``after``: it fails holding the donated buffers, and the server starts
    its residents again on a vacant state. Either way the error reaches
    the caller and every request is then served, token for token. (A
    prefill that raises with the state untouched leaves its members in
    their slots unprefilled, as it always has: not this mechanism's.)"""
    # the two requests of the failing dispatch share a prefill bucket
    prompts, configs = PROMPTS[1:] + PROMPTS[:1], CONFIGS[1:] + CONFIGS[:1]
    want = _server("bf16", paged=paged).generate(prompts, configs)
    srv = _server("bf16", paged=paged)
    real = getattr(srv, program)

    def broken(*args):
        if when == "after":
            real(*args)
        raise _Boom(when)

    rids = [srv.submit(p, c) for p, c in zip(prompts[:2], configs[:2])]
    if program == "_chunk_jit":
        srv.step()  # the first two are resident, one chunk in
    held = srv._state
    setattr(srv, program, broken)
    with pytest.raises(_Boom):
        srv.step()
    setattr(srv, program, real)
    assert held.k.is_deleted() == (when == "after")
    assert not any(
        leaf.is_deleted() for leaf in jax.tree.leaves(srv._state))
    if when == "after":
        assert all(r is None for r in srv._slot_req)
        assert not paged or srv._alloc.used_blocks == 0
    rids.append(srv.submit(prompts[2], configs[2]))
    while srv.step():
        pass
    for got, ref in zip([srv.result(r) for r in rids], want):
        np.testing.assert_array_equal(got, ref)
