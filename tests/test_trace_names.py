"""The names the program promises a device trace (observability/names.py)
and the one clock its spans share with it (PR 26).

Held here, on the CPU, at a tiny size:

- a ``SpanRecorder`` span is read back from the profile's ``/host:CPU``
  plane as ``dtf:<name>`` with its scalar arguments, and brackets the run
  of the program it dispatched, in the same file on the same clock;
- every scope of the list reaches the COMPILED HLO's ``op_name`` of the
  train step, the prefill, the decode chunk and the verify program;
- the Pallas kernels carry their names; the jitted programs' names equal
  the constants, and the benchmark's traffic files name only those;
- the decode chunk's delivery accounting adds up.

Single-device throughout (not in conftest._CACHE_OPT_OUT_FIRST).
"""

import glob
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.observability.spans import SpanRecorder
from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL_SCOPES = set(names.MODEL_SCOPES)


# -- one clock ---------------------------------------------------------------


def _profile(tmp_path, body):
    """Run ``body()`` under a profiler session; the xplane's planes."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    return ProfileData.from_file(path).planes


def _events(planes, plane_name):
    return [
        ev
        for plane in planes if plane.name == plane_name
        for line in plane.lines
        for ev in line.events
    ]


def test_dispatch_span_is_a_dtf_annotation_on_the_profile_clock(tmp_path):
    @jax.jit
    def traced_program(x):
        return (x @ x).sum()

    x = jnp.ones((256, 256))
    traced_program(x).block_until_ready()  # compile outside the session
    rec = SpanRecorder()

    def body():
        with rec.dispatch(
            names.SPAN_DECODE_CHUNK, chunk=4, active=2, rids=[7, 9]
        ) as sp:
            sp.fetch(traced_program(x))
            sp.args["emitted"] = [4, 3]
        with rec.span(names.SPAN_CHECKPOINT_SAVE, step=3):
            pass

    host = _events(_profile(tmp_path, body), "/host:CPU")
    (ann,) = [e for e in host if e.name == "dtf:decode_chunk"]
    # scalar arguments ride as the annotation's own; lists stay in the span
    assert dict(ann.stats) == {"chunk": 4, "active": 2}
    # the program's run, in the same file, lies inside the annotation
    runs = [
        e for e in host
        if dict(e.stats).get("hlo_module") == "jit_traced_program"
    ]
    assert runs, sorted({e.name for e in host})[:20]
    assert ann.start_ns <= min(e.start_ns for e in runs)
    assert ann.start_ns + ann.duration_ns >= max(
        e.start_ns + e.duration_ns for e in runs
    )
    (save,) = [e for e in host if e.name == "dtf:checkpoint_save"]
    assert dict(save.stats) == {"step": 3}
    assert save.start_ns >= ann.start_ns + ann.duration_ns
    # the recorder's own record is unchanged by the session
    assert rec.spans[0]["args"] == {
        "chunk": 4, "active": 2, "rids": [7, 9], "emitted": [4, 3],
        "barrier": "d2h",
    }


def test_dispatch_span_refuses_to_close_without_fetch_in_a_session(tmp_path):
    rec = SpanRecorder()

    def body():
        with pytest.raises(RuntimeError, match="without a D2H fetch"):
            with rec.dispatch(names.SPAN_PREFILL, bucket=8):
                pass
        with pytest.raises(ValueError, match="boom"):
            with rec.dispatch(names.SPAN_PREFILL, bucket=16):
                raise ValueError("boom")

    host = _events(_profile(tmp_path, body), "/host:CPU")
    # both annotations were closed all the same: the profile holds them
    assert sorted(
        dict(e.stats)["bucket"] for e in host if e.name == "dtf:prefill"
    ) == [8, 16]
    assert [s["args"].get("error") for s in rec.spans] == [True]


# -- scopes in the compiled programs -------------------------------------------


def _scopes_by_phase(compiled) -> dict:
    """phase -> the scopes found in the compiled HLO's op_name metadata."""
    out = {phase: set() for phase in names.PHASES}
    for op_name in re.findall(r'op_name="([^"]*)"', compiled.as_text()):
        scope, phase = names.scope_of(op_name)
        if scope is not None:
            out[phase].add(scope)
    return out


def test_scope_of_reads_the_innermost_name_and_jaxs_phases():
    body = "jit(epoch)/while/body/closed_call/"
    assert names.scope_of(body + "jvp()/while/body/attn_core/dot_general") == (
        "attn_core", "forward")
    assert names.scope_of(
        body + "transpose(jvp())/while/body/checkpoint/mlp/mul"
    ) == ("mlp", "backward")
    assert names.scope_of(
        body + "transpose(jvp())/checkpoint/rematted_computation/attn_out/add"
    ) == ("attn_out", "recompute")
    assert names.scope_of(body + "transpose(jvp(loss))/mul") == (
        "loss", "backward")
    # a program named like a scope is not that scope
    assert names.scope_of("jit(loss)/jvp()/add") == (None, "forward")
    # nested: the pool's gather inside a paged prefill's attention
    assert names.scope_of(
        "jit(_paged_prefill_graph)/while/body/attn_core/kv_gather/gather"
    ) == ("kv_gather", "forward")
    assert names.scope_of("") == (None, "forward")


def _tiny_model(**kw):
    kw.setdefault("vocab_size", 61)
    kw.setdefault("max_len", 64)
    kw.setdefault("model_dim", 32)
    kw.setdefault("num_heads", 2)
    kw.setdefault("num_layers", 2)
    kw.setdefault("compute_dtype", jnp.float32)
    return GPTLM(**kw)


@pytest.fixture(scope="module")
def trainer():
    from distributed_tensorflow_tpu.config import TrainConfig
    from distributed_tensorflow_tpu.data.tokens import copy_corpus
    from distributed_tensorflow_tpu.train.lm_trainer import LMTrainer

    return LMTrainer(
        _tiny_model(
            max_len=128, attention_impl="flash", flash_min_len=128, remat=True
        ),
        copy_corpus(num=12, half_len=64, vocab=61, n_val=4, n_test=4, seed=0),
        TrainConfig(
            epochs=1, batch_size=2, optimizer="adam", scan_epoch=True,
            logs_path="",
        ),
        print_fn=lambda *a: None,
    )


def test_train_step_program_carries_every_scope(trainer):
    train = trainer.datasets.train
    steps = train.num_examples // trainer.config.batch_size
    fn = trainer._build_scanned_fn()
    assert fn.__name__ == names.PROGRAM_EPOCH
    compiled = fn.lower(
        trainer.state,
        trainer._stage("train_tokens", train.tokens),
        trainer._train_lens(),
        trainer._replicated(
            trainer._epoch_indices(steps, trainer.config.batch_size)
        ),
    ).compile()
    got = _scopes_by_phase(compiled)
    assert got["forward"] >= MODEL_SCOPES | {names.LOSS, names.OPTIMIZER}
    assert got["backward"] >= MODEL_SCOPES | {names.LOSS}
    # remat replays the scanned block: its four sections, apart from the
    # first pass and from the backward
    assert got["recompute"] >= {
        names.ATTN_QKV, names.ATTN_CORE, names.ATTN_OUT, names.MLP,
    }
    assert names.OPTIMIZER not in got["backward"] | got["recompute"]


HYBRID_SCOPES = set(names.HYBRID_SCOPES)


def test_hybrid_train_step_program_carries_every_scope():
    """The stack of models/hybrid.py through the same trainer: the
    state-space and expert layers' sections in the forward pass, the
    backward pass and remat's replay of each layer."""
    from distributed_tensorflow_tpu.config import TrainConfig
    from distributed_tensorflow_tpu.data.tokens import copy_corpus
    from distributed_tensorflow_tpu.models.hybrid import HybridLM
    from distributed_tensorflow_tpu.train.lm_trainer import LMTrainer

    model = HybridLM(
        61, 32, "EM*", ssm_heads=4, ssm_head_dim=8, ssm_state=16,
        ssm_groups=2, chunk_size=32, num_experts=8, experts_per_token=2,
        expert_dim=16, shared_dim=32, routed_scale=2.5, experts_held=(2, 4),
        num_heads=4, num_kv_heads=2, head_dim=16, compute_dtype=jnp.float32,
        attention_impl="flash", flash_min_len=128, remat=True,
    )
    trainer = LMTrainer(
        model,
        copy_corpus(num=12, half_len=64, vocab=61, n_val=4, n_test=4, seed=0),
        TrainConfig(epochs=1, batch_size=2, optimizer="adam", scan_epoch=True,
                    logs_path=""),
        print_fn=lambda *a: None,
    )
    train = trainer.datasets.train
    steps = train.num_examples // trainer.config.batch_size
    fn = trainer._build_scanned_fn()
    assert fn.__name__ == names.PROGRAM_EPOCH
    compiled = fn.lower(
        trainer.state,
        trainer._stage("train_tokens", train.tokens),
        trainer._train_lens(),
        trainer._replicated(
            trainer._epoch_indices(steps, trainer.config.batch_size)
        ),
    ).compile()
    got = _scopes_by_phase(compiled)
    attention = {names.ATTN_QKV, names.ATTN_CORE, names.ATTN_OUT}
    ends = {names.EMBED, names.LM_HEAD, names.LOSS}
    assert got["forward"] >= HYBRID_SCOPES | attention | ends | {
        names.OPTIMIZER}, (HYBRID_SCOPES | attention | ends) - got["forward"]
    assert got["backward"] >= HYBRID_SCOPES | attention | ends, (
        (HYBRID_SCOPES | attention | ends) - got["backward"])
    # remat replays each layer up to what its backward reads: not the
    # attention's out-projection, whose output only joins the residual
    replayed = HYBRID_SCOPES | {names.ATTN_QKV, names.ATTN_CORE}
    assert got["recompute"] >= replayed, replayed - got["recompute"]
    assert names.MLP not in got["forward"]  # no attention + FFN pair here


KDA_SCOPES = set(names.KDA_SCOPES)


def test_delta_rule_train_step_program_carries_every_scope():
    """The later kinds of models/hybrid.py through the same trainer: a
    delta-rule layer's four sections, latent attention under the attention
    scopes, the dense gated feed-forward under ``mlp``, the gated experts
    under the ``moe_*`` scopes, in the forward pass, the backward pass and
    remat's replay of each layer."""
    from distributed_tensorflow_tpu.config import TrainConfig
    from distributed_tensorflow_tpu.data.tokens import copy_corpus
    from distributed_tensorflow_tpu.models.hybrid import HybridLM
    from distributed_tensorflow_tpu.train.lm_trainer import LMTrainer

    model = HybridLM(
        61, 32, "KDLE", kda_heads=4, kda_head_dim=8, kda_gate_rank=4,
        num_heads=4, kv_lora_rank=12, qk_nope_dim=8,
        qk_shared_dim=4, v_head_dim=8, dense_dim=48, expert_form="silu_gated",
        num_experts=8, experts_per_token=2, expert_dim=16, shared_dim=16,
        routed_scale=2.446, experts_held=(2, 4), compute_dtype=jnp.float32,
        attention_impl="flash", flash_min_len=128, remat=True,
    )
    trainer = LMTrainer(
        model,
        copy_corpus(num=12, half_len=64, vocab=61, n_val=4, n_test=4, seed=0),
        TrainConfig(epochs=1, batch_size=2, optimizer="adam", scan_epoch=True,
                    logs_path=""),
        print_fn=lambda *a: None,
    )
    train = trainer.datasets.train
    steps = train.num_examples // trainer.config.batch_size
    compiled = trainer._build_scanned_fn().lower(
        trainer.state,
        trainer._stage("train_tokens", train.tokens),
        trainer._train_lens(),
        trainer._replicated(
            trainer._epoch_indices(steps, trainer.config.batch_size)
        ),
    ).compile()
    got = _scopes_by_phase(compiled)
    experts = {names.MOE_ROUTE, names.MOE_DISPATCH, names.MOE_EXPERTS,
               names.MOE_SHARED}
    attention = {names.ATTN_QKV, names.ATTN_CORE, names.ATTN_OUT}
    every = KDA_SCOPES | experts | attention | {
        names.MLP, names.EMBED, names.LM_HEAD, names.LOSS}
    assert got["forward"] >= every | {names.OPTIMIZER}, every - got["forward"]
    assert got["backward"] >= every, every - got["backward"]
    replayed = KDA_SCOPES | experts | {names.ATTN_QKV, names.ATTN_CORE,
                                       names.MLP}
    assert got["recompute"] >= replayed, replayed - got["recompute"]
    assert not {names.SSM_PROJ, names.SSM_SCAN} & got["forward"]


def _server(paged: bool, **kw):
    m = _tiny_model()
    kw.setdefault("slots", 2)
    kw.setdefault("chunk", 4)
    kw.setdefault("buckets", (8, 16))
    if paged:
        kw.setdefault("block_size", 4)
    return TextServer(m, m.init(1), paged=paged, **kw)


def _prefill_args(srv, lb=8):
    """The host arguments of one admission round, as ``_admit_*`` builds
    them (shapes are all that a lowering reads)."""
    s = srv.slots
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    tail = (
        jnp.asarray(np.array(srv._state.key)), i32(s),
        jnp.ones((s,), bool), jnp.ones((s,), jnp.float32),
        jnp.ones((s,), jnp.float32), i32(s),
    )
    if srv.paged:
        return (
            srv.params, srv._state, i32(s, lb), jnp.ones((s,), jnp.int32),
            i32(s), jnp.ones((s,), bool), jnp.asarray(srv._host_tables),
        ) + tail
    return (
        srv.params, srv._state, i32(s, lb), jnp.ones((s,), jnp.int32),
        jnp.ones((s,), bool),
    ) + tail


SERVING_SCOPES = MODEL_SCOPES | {names.PICK, names.KV_WRITE}
PROGRAM_CASES = {
    # program -> (paged, jitted attribute, scopes beyond SERVING_SCOPES)
    names.PROGRAM_PAGED_PREFILL: (True, "_prefill_jit", {names.KV_GATHER}),
    names.PROGRAM_PREFILL: (False, "_prefill_jit", set()),
    names.PROGRAM_CHUNK: (True, "_chunk_jit", {names.KV_GATHER}),
    names.PROGRAM_CHUNK + "[slab]": (
        False, "_chunk_jit", {names.KV_RESTACK}),
    names.PROGRAM_VERIFY: (True, "_verify_jit", {names.KV_GATHER}),
}


@pytest.mark.parametrize("case", sorted(PROGRAM_CASES))
def test_serving_program_carries_every_scope(case):
    paged, attr, extra = PROGRAM_CASES[case]
    program = case.split("[")[0]
    srv = _server(paged, spec_draft=3 if program == names.PROGRAM_VERIFY else 0)
    fn = getattr(srv, attr)
    assert fn.__name__ == program
    if attr == "_prefill_jit":
        args = _prefill_args(srv)
    elif attr == "_chunk_jit":
        args = (srv.params, srv._state)
    else:
        d1 = srv.spec_draft + 1
        args = (
            srv.params, srv._state, jnp.zeros((srv.slots, d1), jnp.int32),
            jnp.ones((srv.slots,), jnp.int32),
        )
    got = _scopes_by_phase(fn.lower(*args).compile())
    assert got["forward"] >= SERVING_SCOPES | extra, (
        (SERVING_SCOPES | extra) - got["forward"]
    )
    assert not got["backward"] and not got["recompute"]


def test_every_scope_and_program_is_held_by_some_case():
    held = MODEL_SCOPES | {names.LOSS, names.OPTIMIZER} | SERVING_SCOPES
    held |= HYBRID_SCOPES  # test_hybrid_train_step_program_carries_every_scope
    held |= KDA_SCOPES  # test_delta_rule_train_step_program_carries_every_scope
    for _, _, extra in PROGRAM_CASES.values():
        held |= extra
    assert held == set(names.SCOPES)
    assert {c.split("[")[0] for c in PROGRAM_CASES} | {
        names.PROGRAM_EPOCH, names.PROGRAM_RUN
    } == set(names.PROGRAMS)


def test_compiled_run_program_name(trainer):
    assert trainer._build_compiled_run_fn().__name__ == names.PROGRAM_RUN


# -- kernels --------------------------------------------------------------------


@pytest.mark.parametrize(
    "fused,kernels",
    [
        (True, {names.KERNEL_FLASH_FWD, names.KERNEL_FLASH_BWD_FUSED}),
        (False, {names.KERNEL_FLASH_FWD, names.KERNEL_FLASH_BWD_DQ,
                 names.KERNEL_FLASH_BWD_DKV}),
    ],
)
def test_flash_kernels_are_named_in_the_jaxpr(fused, kernels):
    from distributed_tensorflow_tpu.ops.pallas_attention import flash_attention

    q = jnp.ones((1, 128, 2, 16))
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, fused=fused).sum(),
        argnums=(0, 1, 2),
    ))(q, q, q))
    flash = {n for n in names.KERNELS if n.startswith("flash_")}
    assert {n for n in flash if f"name={n}\n" in jaxpr
            or f"name={n} " in jaxpr} == kernels


# -- the benchmark names only what the program promises -------------------------


def test_traffic_files_name_only_the_programs_constants():
    promised = {"jit_" + p for p in names.PROGRAMS}
    files = glob.glob(os.path.join(ROOT, "benchmark", "traffic", "*.json"))
    assert files
    for path in files:
        with open(path) as f:
            programs = json.load(f)["programs"]
        assert programs and set(programs.values()) <= promised, path


# -- the decode chunk accounted where it runs ------------------------------------


def test_decode_chunk_spans_account_for_every_token_delivered():
    srv = _server(True, slots=2, chunk=4)
    rng = np.random.default_rng(0)
    budgets = [6, 9, 3, 5]  # 6 ends at step 1 of a second chunk; 3 inside one
    rids = [
        srv.submit(
            rng.integers(0, 61, (n,)).astype(np.int32),
            GenerationConfig(max_new=b),
        )
        for n, b in zip([5, 7, 3, 6], budgets)
    ]
    while srv.step():
        pass
    outs = [srv.result(r) for r in rids]
    assert [len(o) for o in outs] == budgets
    chunks = [s for s in srv.spans.spans
              if s["name"] == names.SPAN_DECODE_CHUNK]
    prefills = [s for s in srv.spans.spans if s["name"] == names.SPAN_PREFILL]
    assert sum(s["args"]["admitted"] for s in prefills) == len(rids)
    delivered: dict = {}
    for s in chunks:
        a = s["args"]
        assert len(a["emitted"]) == len(a["rids"]) == a["active"]
        assert a["slot_steps"] == a["active"] * a["chunk"]
        assert all(0 <= n <= a["chunk"] for n in a["emitted"])
        for rid, n in zip(a["rids"], a["emitted"]):
            delivered[rid] = delivered.get(rid, 0) + n
    # the prefill picks each request's first token; the chunks the rest
    assert delivered == {r: b - 1 for r, b in zip(rids, budgets)}
    steps = srv.metrics.counter("decode_slot_steps_total").value
    assert steps == sum(s["args"]["slot_steps"] for s in chunks)
    assert sum(delivered.values()) < steps  # slots rode masked to chunk ends


def test_spec_verify_spans_carry_the_same_accounting():
    srv = _server(True, slots=2, chunk=4, spec_draft=3)
    prompt = np.tile(np.arange(4, dtype=np.int32), 3)  # a cyclic tail drafts
    rid = srv.submit(prompt, GenerationConfig(max_new=8))
    while srv.step():
        pass
    assert len(srv.result(rid)) == 8
    verifies = [s for s in srv.spans.spans
                if s["name"] == names.SPAN_SPEC_VERIFY]
    assert verifies
    for s in verifies:
        assert s["args"]["slot_steps"] == s["args"]["active"] * 4  # draft+1
        assert len(s["args"]["emitted"]) == len(s["args"]["rids"])
    assert sum(sum(s["args"]["emitted"]) for s in verifies) == 8 - 1
