"""chip_smoke.py — does the main path still start on the chip?

Drives, in ONE process that imports JAX once and never sets a platform,
the three things a user of this repo calls, through their normal entry
points:

- the reference-parity MNIST trainer (``launch.build_trainer`` →
  ``Trainer.run``, as ``examples/single.py`` does) and the ``bench.py``
  kernel path (``make_fused_epoch_fn``);
- the LM trainer (``train.LMTrainer``) at the repo's full width —
  ``gpt-xl-L2048-flash-remat`` of ``tools/lm_bench.py``: d=2048, 4
  layers, 16 heads, L=2048, vocabulary 8,192, batch 8, flash attention,
  remat — for a few steps, saving a checkpoint through its Supervisor;
- the text server (``serve.TextServer.from_checkpoint``) on that
  checkpoint, paged cache, continuous batching, its greedy streams
  against ``GPTLM.greedy_decode`` token for token;
- the hybrid stack (``models.hybrid.HybridLM``: a state-space, an expert
  and an attention layer) through the same ``LMTrainer``: its first
  step's loss against the plain reference
  (``benchmark/lib/reference_nemotron_h.py``), then a few steps;
- the same stack's later kinds (a gated delta-rule layer, a dense gated
  feed-forward, latent attention at 192-wide keys and 128-wide values,
  SiLU-gated experts: one layer of each at d = 2304), the same way against
  ``benchmark/lib/reference_kimi_linear.py``.

Each phase prints one JSON line: its name, seconds (compile apart from
run, from ``jax.monitoring``), the compile-cache traffic, and what it
checked. A failed check exits non-zero at once. The last line of stdout,
and nothing else in it, is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Run it with no arguments on one chip. ``--chips 4`` runs ONLY the
parallel phase (sync data-parallel MNIST and the tensor-parallel LM step
on a four-device mesh, each against its one-chip run) and reports
``"count": 4``. There is no option that lets it pass without a TPU: where
JAX finds none, the device phase fails and nothing after it runs.

Weights are random, made from ``--seed``; times printed here are set-up
facts (how long a cold compile is, whether the cache hits), not rates.
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import dataclasses
import gc
import importlib.metadata
import json
import re
import sys
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.config import TrainConfig
from distributed_tensorflow_tpu.data import copy_corpus, read_data_sets
from distributed_tensorflow_tpu.launch import build_trainer
from distributed_tensorflow_tpu.models import MLP
from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.models.hybrid import HybridLM
from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.ops import cross_entropy, sgd
from distributed_tensorflow_tpu.ops.pallas_mlp import (
    make_fused_epoch_fn,
    to_fused,
)
from distributed_tensorflow_tpu.ops.pallas_mode import has_compiled_kernel
from distributed_tensorflow_tpu.parallel import SingleDevice, make_mesh
from distributed_tensorflow_tpu.runtime import native
from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer
from distributed_tensorflow_tpu.train import LMTrainer
from distributed_tensorflow_tpu.train.scan import make_scanned_train_fn
from distributed_tensorflow_tpu.utils.compile_cache import (
    configure_compile_cache,
)

# The repo's widest preset (tools/lm_bench.py "gpt-xl-L2048-flash-remat").
FULL_LM = dict(
    vocab_size=8192, max_len=2048, model_dim=2048, num_heads=16,
    num_layers=4, attention_impl="flash", remat=True,
)
FULL_LM_BATCH = 8
# One layer of each kind of the hybrid stack, heads and states at the
# sizes the published models use (state-space heads of 64 with state 128,
# attention heads of 128, chunk 128), the rest small: seconds on the chip.
HYBRID_LM = dict(
    vocab_size=4096, model_dim=1024, pattern="EM*",
    ssm_heads=16, ssm_head_dim=64, ssm_state=128, ssm_groups=8,
    chunk_size=128, num_experts=16, experts_per_token=2, expert_dim=512,
    shared_dim=1024, routed_scale=2.5, experts_held=(4, 8),
    num_heads=8, num_kv_heads=2, head_dim=128,
    attention_impl="flash", remat=True, balance_rounds=2,
)
HYBRID_LEN = 1024
# One layer of each of the later kinds at the widths the benchmark's
# delta-rule configuration publishes (d = 2304; 32 delta-rule heads of 128;
# latent attention at 128 + 64 wide keys and 128 wide values over a latent
# of 512; a feed-forward of 9216; experts of 1024), few experts, a small
# vocabulary: 0.2 G parameters.
DELTA_LM = dict(
    vocab_size=4096, model_dim=2304, pattern="KDLE",
    kda_heads=32, kda_head_dim=128, num_heads=32, kv_lora_rank=512,
    qk_nope_dim=128, qk_shared_dim=64, v_head_dim=128, dense_dim=9216,
    expert_form="silu_gated", num_experts=32, experts_per_token=8,
    expert_dim=1024, shared_dim=1024, routed_scale=2.446,
    experts_held=(8, 8), depth_for_init=54,
    attention_impl="flash", remat=True, balance_rounds=2,
)
# The hybrid step's first loss against the float32 reference: bfloat16
# matmul operands move a loss near ln(vocabulary) by a few parts in 1e4.
HYBRID_LOSS_RTOL = 2e-3
# ... and its gradient over the whole tree: the norm of the difference
# over the reference's norm. bfloat16 operands read about 0.01 (PERF.md
# section 2, ``moment_rel_err_all``); int8 would read 0.03 and more.
HYBRID_GRAD_RTOL = 2.5e-2

# The fused MLP kernel's costs against the XLA scan. tests/test_pallas_mlp.py
# holds them to rtol 1e-5 in true f32 (the interpreter). On the chip the
# MXU multiplies f32 operands in bf16 passes under both compilers — the
# first step's cost is bit-equal — but Mosaic and XLA round the backward's
# operands at different points, and 550 sequential SGD steps carry the
# difference: 2.1e-4 at worst (chip run, PR 22; 1.9e-3 against an XLA scan
# forced to true-f32 multiplies). The bound allows five times that.
MLP_RTOL = 1e-3
# tests/test_parallel.py: sync data-parallel costs against one device, at
# the test's own f32 model. (At the default bf16 each shard's gradient
# matmul rounds its OUTPUT to bf16 before the all-reduce, where one device
# rounds the whole batch's once: 4.5e-4 apart over 137 steps — four-chip
# run, PR 22.)
SYNC_RTOL = 2e-4
# Tensor-parallel LM losses against one chip. The CPU test
# (tests/test_lm_trainer.py) holds f32 parameters to rtol 2e-4; here the
# compute dtype is bf16 and a column-split matmul rounds its partial
# activations to bf16 (2**-8 relative) before they are summed, so the
# per-step losses may differ by a few such roundings.
TP_RTOL = 2e-2
# A served token may differ from the reference only on a near-tie. bf16
# keeps 8 significant bits, activations are rounded to it after every
# matmul, and two programs that order their f32 accumulations differently
# can round an activation to adjacent bf16 values; a handful of such
# roundings reach the logits. Two logit rows for the same prefix must
# agree to this fraction of the largest logit, and the two tokens must be
# that close in both rows.
NEAR_TIE = 2.0 ** -6


class SmokeFailure(Exception):
    """A phase's check failed; the run exits non-zero."""


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class Meter:
    """Seconds spent tracing, lowering and compiling, and the persistent
    compile cache's hits and misses, as ``jax.monitoring`` reports them."""

    _COMPILE = frozenset({
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    })

    def __init__(self):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event in self._COMPILE:
            self.compile_s += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    @contextlib.contextmanager
    def phase(self, name: str):
        """Run one phase; on success print its JSON line from the dict
        the body filled."""
        checked: dict = {}
        c0, h0, m0 = self.compile_s, self.hits, self.misses
        t0 = time.perf_counter()
        yield checked
        wall = time.perf_counter() - t0
        # Nested traces report their durations twice; never more than wall.
        compile_s = min(self.compile_s - c0, wall)
        emit(
            phase=name, passed=True,
            seconds=dict(
                wall=round(wall, 2), compile=round(compile_s, 2),
                run=round(wall - compile_s, 2),
            ),
            compile_cache=dict(
                hits=self.hits - h0, misses=self.misses - m0
            ),
            **checked,
        )


def _quiet(*_args) -> None:
    """A ``print_fn`` that drops the trainer's lines."""


def _costs(lines: list[str]) -> list[float]:
    return [
        float(m.group(1))
        for ln in lines
        if (m := re.search(r"Cost: (\S+?),", ln)) and ln.startswith("Step:")
    ]


# -- device ----------------------------------------------------------------


def probe_block_until_ready(n: int = 4096, iters: int = 400) -> dict:
    """Time one long dispatch three ways — enqueue only, ended by
    ``block_until_ready``, ended by a value fetch — to say whether
    ``block_until_ready`` waits for the device on this machine."""
    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)

    @jax.jit
    def chain(a):
        return jax.lax.fori_loop(0, iters, lambda _, b: b @ a, a)

    chain(x).block_until_ready()  # compile
    t0 = time.perf_counter()
    y = chain(x)
    enqueue = time.perf_counter() - t0
    y.block_until_ready()
    blocked = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(chain(x)[0, 0])
    fetched = time.perf_counter() - t0
    return dict(
        enqueue_s=round(enqueue, 4), block_until_ready_s=round(blocked, 4),
        value_fetch_s=round(fetched, 4),
        waits=bool(blocked > 0.5 * fetched and blocked > 2 * enqueue),
    )


def phase_device(meter: Meter, chips: int, **probe_sizes):
    with meter.phase("device") as out:
        devices = jax.devices()
        first = devices[0]
        check(
            first.platform == "tpu",
            f"JAX found no accelerator: platform {first.platform!r} "
            f"({first.device_kind}); chip_smoke.py runs on a TPU only",
        )
        check(
            len(devices) == chips,
            f"asked for {chips} chip(s) but JAX reports {len(devices)}; "
            "run with no arguments on one chip, --chips 4 on four",
        )
        out.update(
            platform=first.platform, kind=first.device_kind,
            count=len(devices),
            versions={
                pkg: importlib.metadata.version(pkg)
                for pkg in ("jax", "jaxlib", "libtpu")
            },
            compile_cache_dir=configure_compile_cache(),
            native_runtime="native" if native.available() else "pure-python",
            block_until_ready=probe_block_until_ready(**probe_sizes),
        )
    return first


# -- mlp -------------------------------------------------------------------


def phase_mlp(
    meter: Meter, *, compiled_kernels: bool, epochs: int = 3,
    fused_steps: int = 550, seed: int = 0, datasets=None,
):
    with meter.phase("mlp") as out:
        datasets = datasets or read_data_sets()
        lines: list[str] = []
        trainer = build_trainer(
            TrainConfig(model="mlp", epochs=epochs, logs_path=""),
            datasets=datasets,
            print_fn=lambda *a: lines.append(" ".join(map(str, a))),
        )
        result = trainer.run()
        costs = _costs(lines)
        text = "\n".join(lines)
        for marker in ("Step:", "Epoch:", "Batch:", "Cost:", "AvgTime:",
                       "Test-Accuracy:", "Total Time:", "Final Cost:", "Done"):
            check(marker in text, f"no {marker!r} line from Trainer.run")
        check(
            len(costs) >= 2 and all(np.isfinite(costs))
            and costs[-1] < costs[0],
            f"MLP cost did not fall: {costs[:1]} ... {costs[-1:]}",
        )
        check(
            0.0 <= result["accuracy"] <= 1.0,
            f"accuracy not computed: {result['accuracy']}",
        )

        # bench.py's path: the whole dispatch as one kernel launch over
        # bf16-staged batches, against the XLA scan on the same batches.
        batch = 100
        rng = np.random.default_rng(seed)
        pick = rng.permutation(datasets.train.num_examples)[: fused_steps * batch]
        xs = jnp.asarray(datasets.train.images[pick], jnp.bfloat16).reshape(
            fused_steps, batch, -1
        )
        ys = jnp.asarray(datasets.train.labels[pick], jnp.bfloat16).reshape(
            fused_steps, batch, -1
        )
        model = MLP(compute_dtype=jnp.float32)
        fused = make_fused_epoch_fn(
            steps=fused_steps, batch_size=batch, learning_rate=0.001,
            stream_dtype=jnp.bfloat16,
        )
        state = to_fused(model.init(seed=1))
        kernel = has_compiled_kernel(fused.lower(state, xs, ys).as_text())
        check(
            kernel == compiled_kernels,
            f"epoch kernel compiled={kernel}, expected {compiled_kernels}",
        )
        _, costs_fused = fused(state, xs, ys)
        opt = sgd(0.001)
        scan = make_scanned_train_fn(model, cross_entropy, opt)
        _, costs_xla = scan(
            SingleDevice().init_state(model, opt, seed=1),
            xs.astype(jnp.float32), ys.astype(jnp.float32),
        )
        costs_fused, costs_xla = np.asarray(costs_fused), np.asarray(costs_xla)
        deviation = float(
            np.max(np.abs(costs_fused - costs_xla) / np.abs(costs_xla))
        )
        check(
            np.all(np.isfinite(costs_fused)) and deviation <= MLP_RTOL,
            f"fused epoch kernel costs deviate from the XLA scan by "
            f"{deviation:.3g} (allowed {MLP_RTOL})",
        )
        out.update(
            trainer=dict(
                epochs=epochs, first_cost=costs[0], last_cost=costs[-1],
                accuracy=round(result["accuracy"], 4),
            ),
            fused_epoch=dict(
                steps=fused_steps, kernel_compiled=kernel,
                max_rel_deviation_vs_xla=deviation, rtol=MLP_RTOL,
            ),
        )


# -- lm_train --------------------------------------------------------------


def _lm_config(batch: int, ckpt_dir: str | None, **kw) -> TrainConfig:
    return TrainConfig(
        batch_size=batch, epochs=1, optimizer="adam", learning_rate=3e-4,
        log_frequency=1, logs_path="", checkpoint_dir=ckpt_dir, **kw,
    )


def _lm_corpus(model: GPTLM, batch: int, steps: int, seed: int):
    return copy_corpus(
        num=(steps + 2) * batch, half_len=model.max_len // 2,
        vocab=model.vocab_size, n_val=batch, n_test=batch, seed=seed,
    )


def _scanned_epoch_program(trainer: LMTrainer, steps: int):
    """The trainer's own scanned-epoch function (built by its first
    scanned epoch), lowered on its own state and staged corpus."""
    cfg, train = trainer.config, trainer.datasets.train
    idxs = np.zeros((steps, cfg.batch_size), np.int32)
    return trainer._scanned_fn.lower(
        trainer.state, trainer._stage("train_tokens", train.tokens),
        trainer._train_lens(), trainer._replicated(idxs),
    )


def phase_lm_train(
    meter: Meter, ckpt_dir: str, *, compiled_kernels: bool,
    model_kw: dict = FULL_LM, batch: int = FULL_LM_BATCH, steps: int = 4,
    seed: int = 0,
):
    """A few steps through the scanned epoch (the chip default), then a
    few through the per-step loop on a second trainer that resumes the
    first one's checkpoint. Returns (model, optimizer, final params)."""
    with meter.phase("lm_train") as out:
        model = GPTLM(**model_kw)
        corpus = _lm_corpus(model, batch, steps, seed)
        lines: list[str] = []
        say = lambda *a: lines.append(" ".join(map(str, a)))  # noqa: E731
        scanned = LMTrainer(
            model, corpus, _lm_config(batch, ckpt_dir, scan_epoch=True),
            print_fn=say,
        )
        scanned.run(epochs=2)
        flash = has_compiled_kernel(
            _scanned_epoch_program(scanned, steps).as_text()
        )
        check(
            flash == compiled_kernels,
            f"flash kernel in the compiled LM step: {flash}, expected "
            f"{compiled_kernels} (attention gave way to another path)",
        )
        saved = scanned.supervisor.latest_step()
        check(saved == 2 * steps, f"checkpoint at step {saved}, not {2 * steps}")
        optimizer = scanned.optimizer
        del scanned
        gc.collect()

        stepped = LMTrainer(
            model, _lm_corpus(model, batch, steps, seed),
            _lm_config(batch, ckpt_dir, scan_epoch=False), print_fn=say,
        )
        check(
            stepped.start_step == saved,
            f"per-step trainer resumed at {stepped.start_step}, not {saved}",
        )
        result = stepped.run(epochs=1)
        costs = _costs(lines)
        check(
            len(costs) == 3 * steps and all(np.isfinite(costs)),
            f"expected {3 * steps} finite step costs, got {costs}",
        )
        check(
            costs[-1] < costs[0],
            f"LM loss did not fall: {costs[0]} -> {costs[-1]}",
        )
        check(
            np.isfinite(result["perplexity"]),
            f"perplexity {result['perplexity']}",
        )
        params = stepped.state.params
        out.update(
            model={k: model_kw[k] for k in ("model_dim", "num_layers",
                                            "num_heads", "max_len")},
            params=int(sum(p.size for p in jax.tree.leaves(params))),
            batch=batch, steps=dict(scanned=2 * steps, per_step=steps),
            first_loss=costs[0], last_loss=costs[-1],
            perplexity=round(float(result["perplexity"]), 2),
            flash_kernel_compiled=flash,
            checkpoint_step=stepped.supervisor.latest_step(),
            peak_bytes_in_use=(
                jax.devices()[0].memory_stats() or {}
            ).get("peak_bytes_in_use"),
        )
    return model, optimizer, params


# -- hybrid ----------------------------------------------------------------


def _reference_dims(model: HybridLM) -> dict:
    """The sizes benchmark/lib/reference_nemotron_h.py reads, from the
    model's own attributes."""
    return {
        "pattern": model.pattern, "eps": model.norm_eps,
        "ssm_heads": model.ssm_heads, "ssm_head_dim": model.ssm_head_dim,
        "ssm_groups": model.ssm_groups, "ssm_state": model.ssm_state,
        "inner": model.ssm_inner, "conv_dim": model.conv_dim,
        "conv_kernel": model.conv_kernel, "chunk": model.chunk_size,
        "experts": model.num_experts, "held": model.experts_held,
        "top_k": model.experts_per_token, "routed_scale": model.routed_scale,
        "heads": model.num_heads, "kv_heads": model.num_kv_heads,
        "head_dim": model.head_dim,
    }


def _delta_reference_dims(model: HybridLM) -> dict:
    """The sizes benchmark/lib/reference_kimi_linear.py reads, from the
    model's own attributes."""
    return {
        "pattern": model.pattern, "eps": model.norm_eps,
        "kda_heads": model.kda_heads, "kda_head_dim": model.kda_head_dim,
        "kda_inner": model.kda_inner, "gate_rank": model.kda_gate_rank,
        "conv_kernel": model.conv_kernel, "heads": model.num_heads,
        "kv_rank": model.kv_lora_rank, "nope": model.qk_nope_dim,
        "shared_k": model.qk_shared_dim, "v_dim": model.v_head_dim,
        "experts": model.num_experts, "held": model.experts_held,
        "top_k": model.experts_per_token, "routed_scale": model.routed_scale,
    }


def phase_delta_train(meter: Meter, **kw):
    """:func:`phase_hybrid_train` over the stack's later kinds (one
    delta-rule, dense, latent-attention and gated-expert layer) against
    their own plain reference."""
    from benchmark.lib import reference_kimi_linear

    kw.setdefault("model_kw", DELTA_LM)
    return phase_hybrid_train(
        meter, name="delta_train", reference=reference_kimi_linear,
        dims_of=_delta_reference_dims,
        kernels=(names.KERNEL_KDA_SCORES_FWD, names.KERNEL_KDA_SCORES_BWD),
        **kw)


def phase_hybrid_train(
    meter: Meter, *, compiled_kernels: bool, model_kw: dict = HYBRID_LM,
    seq_len: int = HYBRID_LEN, batch: int = 2, steps: int = 3, seed: int = 0,
    loss_rtol: float = HYBRID_LOSS_RTOL, grad_rtol: float = HYBRID_GRAD_RTOL,
    name: str = "hybrid_train", reference=None, dims_of=_reference_dims,
    kernels: tuple = (),
):
    """The hybrid stack through ``LMTrainer``'s scanned epoch: the loss
    falls, the flash kernel and the ``kernels`` named are in the compiled
    step and the expert layer's counters came back with the costs; then
    one step's loss and gradient on the trainer's own initial weights
    against the plain reference (``reference``:
    ``benchmark/lib/reference_nemotron_h`` unless given, its sizes from
    ``dims_of``)."""
    if reference is None:
        from benchmark.lib import reference_nemotron_h as reference

    with meter.phase(name) as out:
        model = HybridLM(**model_kw)
        corpus = copy_corpus(
            num=(steps + 2) * batch, half_len=seq_len // 2,
            vocab=model.vocab_size, n_val=batch, n_test=batch, seed=seed,
        )
        lines: list[str] = []
        trainer = LMTrainer(
            model, corpus,
            TrainConfig(
                batch_size=batch, epochs=1, optimizer="adamw",
                learning_rate=3e-4, log_frequency=1, logs_path="",
                scan_epoch=True, seed=seed + 1,
            ),
            print_fn=lambda *a: lines.append(" ".join(map(str, a))),
        )
        start = jax.tree.map(jnp.copy, trainer.state.params)
        first_batch = corpus.train.tokens[:batch]
        trainer.run(epochs=2)
        costs = _costs(lines)
        check(
            len(costs) == 2 * steps and all(np.isfinite(costs)),
            f"expected {2 * steps} finite step costs, got {costs}",
        )
        check(costs[-1] < costs[0],
              f"hybrid loss did not fall: {costs[0]} -> {costs[-1]}")
        # One step's loss and gradient on the trainer's initial weights,
        # the model's against the plain reference's.
        def as_dict(params) -> dict:
            d = params._asdict()
            return {k: v._asdict() if hasattr(v, "_asdict") else v
                    for k, v in d.items()}

        tree = as_dict(start)
        toks = jnp.asarray(corpus.train.tokens[:batch])
        got_loss, got = jax.jit(jax.value_and_grad(model.loss))(start, toks)
        dims = dims_of(model)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda t, x: reference.loss(
                t, x, dims, balance=model.balance_rounds)))(tree, toks)
        gap = abs(float(got_loss) - float(want_loss)) / abs(float(want_loss))
        check(
            gap <= loss_rtol,
            f"hybrid loss {float(got_loss)} against the reference's "
            f"{float(want_loss)}: {gap:.2e} apart, allowed {loss_rtol:.0e}",
        )
        pairs_of = list(zip(
            jax.tree.leaves(as_dict(got)), jax.tree.leaves(want)))
        grad_err = float(
            jnp.sqrt(sum(jnp.sum(jnp.square(a - b)) for a, b in pairs_of))
            / jnp.sqrt(sum(jnp.sum(jnp.square(b)) for _, b in pairs_of)))
        check(
            grad_err <= grad_rtol,
            f"hybrid gradient {grad_err:.2e} from the reference's over the "
            f"whole tree, allowed {grad_rtol:.0e}",
        )
        text = _scanned_epoch_program(trainer, steps).as_text()
        flash = has_compiled_kernel(text)
        check(
            flash == compiled_kernels,
            f"flash kernel in the compiled hybrid step: {flash}, expected "
            f"{compiled_kernels}",
        )
        named = sorted(k for k in kernels if f'kernel_name = "{k}"' in text)
        check(
            named == (sorted(kernels) if compiled_kernels else []),
            f"of the kernels {kernels} the compiled step calls {named}",
        )
        gauges = {g.name: g.value for g in trainer.metrics
                  if g.name.startswith("moe_")}
        pairs = batch * seq_len * model.experts_per_token
        check(
            0 < gauges.get("moe_rows_per_step", 0) <= pairs
            * model.counts["E"],
            f"rows landed on the held experts: {gauges}",
        )
        out.update(
            model={k: model_kw[k] for k in (
                "model_dim", "pattern", "num_experts", "experts_held")},
            params=int(sum(p.size for p in jax.tree.leaves(start))),
            batch=batch, seq_len=seq_len, steps=2 * steps,
            first_loss=costs[0], last_loss=costs[-1],
            loss=float(got_loss), reference_loss=float(want_loss),
            loss_gap=gap, gradient_rel_err=grad_err,
            flash_kernel_compiled=flash, kernels_compiled=named,
            gauges=gauges,
        )


# -- serve -----------------------------------------------------------------


def _requests(vocab: int, seed: int, greedy_lens, sampled_lens, max_new: int):
    """Prompts and configs: greedy requests at a few repeated lengths (one
    reference compile per length), sampled ones at mixed lengths."""
    rng = np.random.default_rng(seed)
    prompts, configs = [], []
    for i, n in enumerate(list(greedy_lens) + list(sampled_lens)):
        prompts.append(rng.integers(0, vocab, (n,)).astype(np.int32))
        if i < len(greedy_lens):
            configs.append(GenerationConfig(max_new=max_new))
        else:
            configs.append(GenerationConfig(
                max_new=max_new, greedy=False, temperature=0.8, top_p=0.95,
                seed=100 + i,
            ))
    # Interleave so greedy and sampled requests share every wave.
    order = rng.permutation(len(prompts))
    return [prompts[i] for i in order], [configs[i] for i in order]


def _logit_rows(model: GPTLM, params, prefix: np.ndarray):
    """Next-token logits after ``prefix`` two ways: from the prompt pass
    alone, and from one decode step on the cache of ``prefix[:-1]``."""
    toks = jnp.asarray(prefix[None])
    prefill = jax.jit(model.prefill)
    whole = prefill(params, toks)[0][0]
    _, cache = prefill(params, toks[:, :-1])
    stepped = jax.jit(model.decode_step)(params, toks[:, -1], cache)[0][0]
    return np.asarray(whole, np.float32), np.asarray(stepped, np.float32)


def _top(row: np.ndarray, k: int = 8) -> list:
    return [(int(i), float(row[i])) for i in np.argsort(row)[::-1][:k]]


def compare_streams(model: GPTLM, params, prompt, got, want) -> dict | None:
    """None when the two generated streams are equal. Otherwise the first
    diverging position with both tokens and the agreement of that
    position's two logit rows (the prompt pass's and a decode step's);
    raises unless that position is a near-tie (NEAR_TIE)."""
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"stream shapes {got.shape} {want.shape}")
    diff = np.nonzero(got != want)[0]
    if diff.size == 0:
        return None
    at = int(diff[0])
    prefix = np.concatenate([prompt, got[:at]])
    row_a, row_b = _logit_rows(model, params, prefix)
    a, b = int(got[at]), int(want[at])
    scale = max(1.0, float(np.max(np.abs(row_a))))
    rows_apart = float(np.max(np.abs(row_a - row_b))) / scale
    gap = max(abs(row_a[a] - row_a[b]), abs(row_b[a] - row_b[b])) / scale
    report = dict(
        position=at, tokens=[a, b], rows_apart=rows_apart, token_gap=float(gap),
    )
    print(
        f"divergence {report}\n  top of row[prefill]: {_top(row_a)}\n"
        f"  top of row[decode_step]: {_top(row_b)}",
        file=sys.stderr,
    )
    check(
        rows_apart <= NEAR_TIE and gap <= NEAR_TIE,
        f"streams diverge beyond a near-tie (allowed {NEAR_TIE}): {report}",
    )
    return report


def _reference_streams(model: GPTLM, params, prompts, configs) -> dict:
    """``GPTLM.greedy_decode`` for every greedy request, one compile per
    (prompt length, budget)."""
    decode = jax.jit(model.greedy_decode, static_argnums=2)
    return {
        i: np.asarray(decode(params, jnp.asarray(p[None]), c.max_new))[0, p.size:]
        for i, (p, c) in enumerate(zip(prompts, configs)) if c.greedy
    }


def phase_serve(
    meter: Meter, model: GPTLM, optimizer, live_params, ckpt_dir: str, *,
    seed: int = 0, slots: int = 8, chunk: int = 32, block_size: int = 16,
    buckets=(32, 128), greedy_lens=(12, 12, 12, 40, 40, 40),
    sampled_lens=(7, 25, 60, 100), max_new: int = 40,
):
    with meter.phase("serve") as out:
        kw = dict(
            optimizer=optimizer, paged=True, slots=slots, chunk=chunk,
            block_size=block_size, buckets=buckets,
        )
        server = TextServer.from_checkpoint(model, ckpt_dir, **kw)
        for a, b in zip(jax.tree.leaves(server.params),
                        jax.tree.leaves(live_params)):
            check(
                bool(jnp.array_equal(a, b)),
                "restored parameters differ from the trainer's",
            )
        prompts, configs = _requests(
            model.vocab_size, seed, greedy_lens, sampled_lens, max_new
        )
        check(len(prompts) > slots, "continuous batching needs > slots requests")
        outs = server.generate(prompts, configs)
        for o, c in zip(outs, configs):
            check(
                o.shape == (c.max_new,) and o.min() >= 0
                and o.max() < model.vocab_size,
                f"bad served stream {o.shape} {o.min()}..{o.max()}",
            )
        refs = _reference_streams(model, server.params, prompts, configs)
        near_ties = [
            r for i, want in refs.items()
            if (r := compare_streams(
                model, server.params, prompts[i], outs[i], want
            ))
        ]
        # Seeded sampling is reproducible: a second server restored from
        # the same checkpoint serves the sampled requests again. Its
        # programs are the first server's, so the compile cache should
        # serve them (the hits are on this phase's line).
        c0, h0 = meter.compile_s, meter.hits
        again = TextServer.from_checkpoint(model, ckpt_dir, **kw)
        sampled = [i for i, c in enumerate(configs) if not c.greedy]
        outs2 = again.generate(
            [prompts[i] for i in sampled], [configs[i] for i in sampled]
        )
        for i, o in zip(sampled, outs2):
            check(
                np.array_equal(o, outs[i]),
                f"sampled request {i} (seed {configs[i].seed}) not reproduced",
            )
        out.update(
            checkpoint_step=server.checkpoint_step,
            requests=len(prompts), slots=slots, chunk=chunk,
            greedy_equal_to_greedy_decode=len(refs) - len(near_ties),
            greedy_near_ties=near_ties,
            sampled_reproduced=len(sampled),
            second_server=dict(
                compile_s=round(meter.compile_s - c0, 2),
                cache_hits=meter.hits - h0,
            ),
        )


# -- parallel (--chips 4) ----------------------------------------------------


def _shard_report(tree, devices) -> dict:
    """Per-leaf shard sizes over ``devices``: every device must hold a
    shard of every leaf, and the shards' sizes say what is replicated."""
    leaves = jax.tree.leaves(tree)
    full = sum(a.size * a.dtype.itemsize for a in leaves)
    per_device = {d.id: 0 for d in devices}
    for a in leaves:
        held = {s.device.id for s in a.addressable_shards}
        check(
            held == set(per_device),
            f"a {a.shape} leaf lives on devices {sorted(held)} only",
        )
        for s in a.addressable_shards:
            per_device[s.device.id] += s.data.size * s.data.dtype.itemsize
    return dict(full_bytes=int(full), bytes_per_device=per_device)


def _memory_in_use(devices) -> dict:
    return {d.id: (d.memory_stats() or {}).get("bytes_in_use") for d in devices}


def phase_parallel_mlp(meter: Meter, *, epochs: int = 1, datasets=None):
    """examples/between_sync.py's path — ``build_trainer(sync=True)`` puts
    ``SyncDataParallel`` on a ``data`` mesh over every device — against
    ``SingleDevice`` on one of them with the same global batches."""
    devices = jax.devices()
    n = len(devices)
    with meter.phase("parallel_mlp") as out:
        datasets = datasets or read_data_sets()
        cfg = TrainConfig(
            sync=True, epochs=epochs, scan_epoch=True, logs_path="",
            compute_dtype="float32",
        )
        sync = build_trainer(cfg, datasets=datasets, print_fn=_quiet)
        check(
            dict(sync.strategy.mesh.shape).get("data") == n,
            f"sync mesh {dict(sync.strategy.mesh.shape)} is not {n}-way data",
        )
        sync.run()
        one = build_trainer(
            dataclasses.replace(cfg, batch_size=cfg.batch_size * n),
            datasets=datasets, strategy=SingleDevice(), print_fn=_quiet,
        )
        one.run()
        costs_n = np.asarray(sync._epoch_costs, np.float64)
        costs_1 = np.asarray(one._epoch_costs, np.float64)
        check(costs_n.shape == costs_1.shape, "step counts differ")
        deviation = float(np.max(np.abs(costs_n - costs_1) / np.abs(costs_1)))
        check(
            deviation <= SYNC_RTOL,
            f"sync costs deviate from one device by {deviation:.3g} "
            f"(allowed {SYNC_RTOL})",
        )
        shards = _shard_report(sync.state.params, devices)
        check(
            all(b == shards["full_bytes"]
                for b in shards["bytes_per_device"].values()),
            f"sync parameters are not one full copy per device: {shards}",
        )
        xs = sync._stage_cached("train_x", datasets.train.images)
        ys = sync._stage_cached("train_y", datasets.train.labels)
        idxs = sync._place_replicated(
            np.zeros((2, cfg.batch_size * n), np.int32),
            sync.strategy.replicated_sharding,
        )
        program = sync._indexed_fn.lower(sync.state, xs, ys, idxs).compile()
        check(
            "all-reduce" in program.as_text(),
            "no all-reduce in the sync data-parallel program",
        )
        out.update(
            devices=n, steps=int(costs_n.size),
            max_rel_deviation_vs_one_device=deviation, rtol=SYNC_RTOL,
            params=shards, all_reduce_in_program=True,
            bytes_in_use=_memory_in_use(devices),
        )


def phase_parallel_lm(
    meter: Meter, *, model_kw: dict = FULL_LM, batch: int = FULL_LM_BATCH,
    steps: int = 4, seed: int = 0,
):
    """The full-width LM step, tensor-parallel over ``model`` and
    data-parallel over ``data`` (``dp_mode="tp"``), against the same steps
    on one chip."""
    devices = jax.devices()
    n = len(devices)
    with meter.phase("parallel_lm") as out:
        model = GPTLM(**model_kw)
        corpus = _lm_corpus(model, batch, steps, seed)
        single = LMTrainer(
            model, copy.deepcopy(corpus),
            _lm_config(batch, None, scan_epoch=True), print_fn=_quiet,
        )
        single.run()
        losses_1 = np.asarray(single._epoch_costs, np.float64)
        del single
        gc.collect()
        mesh = make_mesh((n // 2, 2), ("data", "model"), devices=devices)
        tp = LMTrainer(
            model, corpus,
            _lm_config(batch, None, scan_epoch=True, dp_mode="tp"),
            mesh=mesh, print_fn=_quiet,
        )
        check(tp.mode == "tp", f"trainer mode {tp.mode}")
        tp.run()
        losses_n = np.asarray(tp._epoch_costs, np.float64)
        deviation = float(np.max(np.abs(losses_n - losses_1) / np.abs(losses_1)))
        check(
            np.all(np.isfinite(losses_n)) and deviation <= TP_RTOL,
            f"tensor-parallel losses {losses_n.tolist()} deviate from one "
            f"chip's {losses_1.tolist()} by {deviation:.3g} (allowed {TP_RTOL})",
        )
        params = _shard_report(tp.state.params, devices)
        slots = _shard_report(tp.state.opt_state, devices)
        wq = tp.state.params.blocks.wq
        check(
            wq.addressable_shards[0].data.shape[-1] * 2 == wq.shape[-1],
            f"wq {wq.shape} is not column-split over `model`: "
            f"{wq.sharding}",
        )
        for name, rep in (("parameters", params), ("optimizer slots", slots)):
            check(
                max(rep["bytes_per_device"].values()) < 0.75 * rep["full_bytes"],
                f"{name} are replicated, not sharded: {rep}",
            )
        # Compiled, not just lowered: the partitioner adds the collectives.
        text = _scanned_epoch_program(tp, steps).compile().as_text()
        check("all-reduce" in text, "no all-reduce in the tensor-parallel step")
        out.update(
            devices=n, mesh=dict(mesh.shape), steps=int(losses_n.size),
            losses_one_chip=losses_1.tolist(), losses_mesh=losses_n.tolist(),
            max_rel_deviation=deviation, rtol=TP_RTOL,
            params=params, optimizer_slots=slots,
            all_reduce_in_program=True,
            flash_kernel_compiled=has_compiled_kernel(text),
            bytes_in_use=_memory_in_use(devices),
        )


# -- main --------------------------------------------------------------------


def run(chips: int, seed: int) -> dict:
    meter = Meter()
    try:
        device = phase_device(meter, chips)
        if chips > 1:
            phase_parallel_mlp(meter)
            gc.collect()
            phase_parallel_lm(meter, seed=seed)
        else:
            phase_mlp(meter, compiled_kernels=True, seed=seed)
            with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
                model, optimizer, params = phase_lm_train(
                    meter, ckpt, compiled_kernels=True, seed=seed,
                )
                phase_serve(meter, model, optimizer, params, ckpt, seed=seed)
            del params
            gc.collect()
            phase_hybrid_train(meter, compiled_kernels=True, seed=seed)
            gc.collect()
            phase_delta_train(meter, compiled_kernels=True, seed=seed)
    finally:
        meter.close()
    return dict(
        platform=device.platform, kind=device.device_kind,
        count=len(jax.devices()),
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the parallel phase, on a four-chip host",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        device = run(args.chips, args.seed)
    except SmokeFailure as failure:
        print(f"chip_smoke: FAILED: {failure}", file=sys.stderr)
        return 1
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
