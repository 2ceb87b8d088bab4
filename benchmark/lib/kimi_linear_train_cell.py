"""A training cell of a ``kimi_linear`` stack (``"kind":
"kimi_linear_train"``): ``HybridLM`` over the kinds ``K`` (gated delta
rule), ``L`` (latent attention), ``D`` (dense gated feed-forward) and
``E`` (held SiLU-gated experts) through ``LMTrainer``'s own scanned step
loop, measured as ``lib/hybrid_train_cell.py`` measures the ``nemotron_h``
stack: one trainer, the benchmark's weights, the first dispatch through
the window's own call, the window, then the plain reference
(``lib/reference_kimi_linear.py``) over the same first steps. What it
takes from that driver it imports; what names a group of leaves, a
weights module or a reference is its own:

- ``flops_per_token`` from the program's gauge of the rows that landed on
  the held experts (``lib/flops_kimi_linear.py``);
- ``moment_rel_err_scan`` over the delta rule's own leaves (``kda.a_log``,
  ``kda.dt_bias``);
- the reference's faults: ``half_batch`` planted here, ``no_routed``,
  ``no_carry``, ``no_delta`` and ``no_shared_key`` in the reference.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import (
    flops_kimi_linear, harness, reference, reference_kimi_linear,
    traffic as traffic_lib, train_cell, weights_kimi_linear as weights,
)
from benchmark.lib.hybrid_train_cell import _like, reduce_by_scope
from benchmark.lib.train_cell import Rows, global_batch

GROUPS = weights.GROUPS
# The recurrence's own parameters: the leaves whose gradient reaches them
# through the decay alone.
SCAN_LEAVES = ("kda.a_log.", "kda.dt_bias.")


def build_model(cfg: dict, traffic: dict):
    from distributed_tensorflow_tpu.models.hybrid import HybridLM

    z = weights.dims(cfg)
    return HybridLM(
        z["vocab"], z["d"], z["pattern"],
        kda_heads=z["kda_heads"], kda_head_dim=z["kda_head_dim"],
        kda_gate_rank=z["gate_rank"], conv_kernel=z["conv_kernel"],
        num_heads=z["heads"], kv_lora_rank=z["kv_rank"],
        qk_nope_dim=z["nope"], qk_shared_dim=z["shared_k"],
        v_head_dim=z["v_dim"], dense_dim=z["dense_dim"],
        expert_form="silu_gated", num_experts=z["experts"],
        experts_per_token=z["top_k"], expert_dim=z["expert_dim"],
        shared_dim=z["shared_dim"], routed_scale=z["routed_scale"],
        experts_held=z["held"], norm_eps=z["eps"], depth_for_init=z["depth"],
        dt_init=weights.DT,
        attention_impl=traffic.get("attention_impl", "xla"),
        remat=traffic.get("remat", False),
        balance_rounds=traffic.get("balance_rounds"),
    )


def to_program_params(tree: dict):
    from distributed_tensorflow_tpu.models import hybrid

    def stack(cls, group):
        """None for a kind the pattern lacks, as the program's ``init``."""
        return cls(**tree[group]) if len(tree[group]["norm"]) else None

    return hybrid.StackLMParams(
        embed=tree["embed"], norm_f=tree["norm_f"], head=tree["head"],
        mamba=None, attn=None, kda=stack(hybrid.KdaParams, "kda"),
        mla=stack(hybrid.MlaParams, "mla"),
        dense=stack(hybrid.DenseParams, "dense"),
        moe=stack(hybrid.GatedExpertParams, "moe"))


def _as_dict(params) -> dict:
    return {k: v._asdict() if k in GROUPS else v
            for k, v in params._asdict().items() if v is not None}


def build_trainer(cfg, traffic, chips, devices, rows):
    """The trainer as a user builds it on one chip (``LMTrainer``'s
    normal single-device path), state not yet the benchmark's."""
    from collections import namedtuple

    from distributed_tensorflow_tpu.config import TrainConfig
    from distributed_tensorflow_tpu.ops import optim
    from distributed_tensorflow_tpu.train import LMTrainer

    if chips != 1 or traffic["mesh"][str(chips)] is not None:
        raise SystemExit("the stack trains on LMTrainer's one-chip path")
    batch = global_batch(traffic, chips)
    held_out = Rows(rows[:batch])
    corpus = namedtuple("Corpus", "train validation test")(
        Rows(rows), held_out, held_out)
    tc = TrainConfig(
        batch_size=batch, epochs=1, optimizer=traffic["optimizer"],
        learning_rate=traffic["learning_rate"], log_frequency=10 ** 9,
        logs_path="", scan_epoch=True,
    )
    # The learning rate ramps up linearly over ``warmup_steps`` (optax
    # counts from 0: the first step's rate is 0), then stays.
    rate = optim.schedule(
        None, traffic["learning_rate"], 0,
        warmup_steps=traffic.get("warmup_steps", 0))
    return LMTrainer(build_model(cfg, traffic), corpus, tc,
                     optimizer=optim.make(traffic["optimizer"], rate),
                     print_fn=lambda *a: None)


def give_weights(trainer, cfg, seed) -> None:
    trainer.state = None
    gc.collect()
    trainer.state = trainer._init_state(to_program_params(weights.make(cfg, seed)))


def _norms(tree: dict, minus: dict | None = None) -> dict:
    """Per-leaf L2 norms (of ``tree - minus``), a stacked leaf per layer:
    {"kda.in_proj.0": ..}."""
    out = {}
    for name, v in tree.items():
        if name in GROUPS:
            for k, leaf in v.items():
                other = None if minus is None else minus[name][k]
                out[f"{name}.{k}"] = train_cell._norm(leaf, other, True)
        else:
            out[name] = train_cell._norm(
                v, None if minus is None else minus[name], False)
    flat = {}
    for name, v in jax.device_get(out).items():
        v = np.asarray(v)
        if v.ndim:
            flat.update({f"{name}.{i}": float(x) for i, x in enumerate(v)})
        else:
            flat[name] = float(v)
    return flat


def program_readings(trainer, cfg, seed, logger) -> dict:
    """As ``train_cell.program_readings``: each step's loss of the first
    dispatch, the first moment after it (norms, and the moment itself on
    the host), the parameters' change."""
    trainer.run_epoch(0, logger)
    losses = [float(x) for x in np.asarray(trainer._epoch_costs)]
    now = _as_dict(trainer.state.params)
    start = _like(weights.make(cfg, seed), now)
    change = _norms(now, minus=start)
    del start
    mu = _as_dict(train_cell._adam_mu(trainer.state.opt_state))
    return {"loss": losses, "moment": _norms(mu), "change": change,
            "moment_tree": jax.device_get(mu)}


def reference_readings(cfg, traffic, seed, rows, steps, precision="float32",
                       fault=None, against=None, keep_moment=False) -> dict:
    """The plain reference over the same first steps, on one chip: AdamW
    by hand at the traffic file's rate, ramped over its ``warmup_steps``.
    ``fault``: ``half_batch`` is planted here, the others
    (``reference_kimi_linear.FAULTS``) in the reference itself.
    ``against`` maps names to other runs' moments (host trees), compared
    leaf by leaf."""
    params = weights.make(cfg, seed)
    h = traffic["adamw"]
    warm = traffic.get("warmup_steps", 0)
    batch = rows.shape[0] // steps
    losses = []
    # The gradient's program needs the room of the two Adam slots: they
    # wait on the host while it runs.
    slots = None
    for s in range(steps):
        toks = rows[s * batch:(s + 1) * batch]
        if fault == "half_batch":
            toks = toks[: batch // 2]
        block = min(traffic["reference_rows_per_block"], toks.shape[0])
        loss, grads, _ = reference_kimi_linear.loss_and_grad(
            params, jnp.asarray(toks), cfg, block, precision,
            None if fault == "half_batch" else fault,
            traffic.get("balance_rounds"))
        losses.append(float(loss))
        mu, nu = (jax.device_put(slots) if slots is not None else
                  [jax.tree.map(jnp.zeros_like, params) for _ in range(2)])
        # the ramp: step s + 1 of the run moves at s / warmup of the rate
        rate = traffic["learning_rate"] * (min(1.0, s / warm) if warm else 1.0)
        hyper = (rate, h["b1"], h["b2"], h["eps"], h["weight_decay"])
        params, mu, nu = reference.adamw(params, mu, nu, grads, hyper, s + 1)
        del grads
        if s + 1 < steps:
            slots = jax.device_get((mu, nu))
            del mu, nu
    del nu, slots
    out = {"loss": losses, "moment": _norms(mu), "moment_err": {}}
    for name, tree in (against or {}).items():
        theirs = _like(tree, mu)
        out["moment_err"][name] = _norms(theirs, minus=mu)
        del theirs
    if keep_moment:
        out["moment_tree"] = jax.device_get(mu)
    del mu
    out["change"] = _norms(params, minus=weights.make(cfg, seed))
    return out


def compare(program: dict, ref: dict, name: str = "program") -> dict:
    """``train_cell.compare``'s numbers, and ``moment_rel_err_scan``: the
    norm of the difference of the two moments over :data:`SCAN_LEAVES`,
    against the reference's norm over the same leaves."""
    out = train_cell.compare(program, ref, name)
    err = ref["moment_err"].get(name)
    if err is not None:
        leaves = [k for k in err if k.startswith(SCAN_LEAVES)]
        if leaves:
            out["moment_rel_err_scan"] = float(
                np.sqrt(sum(err[k] ** 2 for k in leaves))
                / np.sqrt(sum(ref["moment"][k] ** 2 for k in leaves)))
    return out


def run(ctx) -> harness.Run:
    from distributed_tensorflow_tpu.utils.logging import StepLogger

    cfg, traffic, chips = ctx.cfg, ctx.traffic, ctx.chips
    if ctx.tracer.enabled:
        # a traced run may not read a program cached without its scopes
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    steps = traffic["steps_per_dispatch"]
    batch = global_batch(traffic, chips)
    rows = traffic_lib.train_rows(traffic, cfg["vocab_size"], ctx.seed, steps * batch)
    trainer = build_trainer(cfg, traffic, chips, ctx.devices, rows)
    ctx.mark("trainer_constructor")
    give_weights(trainer, cfg, ctx.seed)
    ctx.mark("weights")
    logger = StepLogger(freq=10 ** 9, print_fn=lambda *a: None)
    program = program_readings(trainer, cfg, ctx.seed, logger)
    ctx.mark("first_dispatch_and_readings")
    epoch = 1
    for _ in range(traffic["warm_dispatches"]):
        trainer.run_epoch(epoch, logger)
        epoch += 1
    ctx.mark("warm_dispatches")

    run = harness.Run(ctx.cell, cfg, traffic, chips, ctx.peaks)
    tokens_per_step = batch * traffic["seq_len"]
    compiles = ctx.compiles.count
    t_open = time.perf_counter()
    run.end_to_end["setup_s"] = t_open - ctx.t0
    ends, bad, landed = [], 0, []
    traced_from = None  # (dispatches done, clock) when the trace started
    gauge = trainer.metrics.gauge
    while True:
        now = time.perf_counter()
        if ctx.tracer.maybe_start(now - t_open, ctx.seconds):
            traced_from = (len(ends), time.perf_counter())
        with ctx.tracer.annotate("bench:trainer.step"):
            trainer.run_epoch(epoch, logger)
        now = time.perf_counter()
        epoch += 1
        ends.append(now)
        landed.append(gauge("moe_rows_per_step").value)
        bad += int(not np.all(np.isfinite(np.asarray(trainer._epoch_costs))))
        if now - t_open >= ctx.seconds:
            break
    ctx.tracer.stop()
    t_close = ends[-1]
    run.window_s = t_close - t_open
    run.compiles_in_window = ctx.compiles.count - compiles
    run.attempted, run.failed = len(ends), bad
    run.end_to_end["train_tokens_per_s"] = (
        len(ends) * steps * tokens_per_step / run.window_s)
    rows_per_token = float(np.mean(landed)) / tokens_per_step
    run.counters.update(
        dispatches=len(ends), tokens_per_dispatch=steps * tokens_per_step,
        steps_per_dispatch=steps, global_batch=batch,
        traced_dispatches=len(ends) - traced_from[0] if traced_from else 0,
        traced_host_s=t_close - traced_from[1] if traced_from else 0.0,
        moe_rows_per_token=rows_per_token,
        moe_expert_rows_max=gauge("moe_expert_rows_max").value,
        moe_expert_rows_mean=gauge("moe_expert_rows_mean").value,
        flops_per_token=flops_kimi_linear.train_flops_per_token(
            cfg, traffic["seq_len"], rows_per_token),
    )
    run.memory_peak_bytes = harness.memory_peak_bytes(ctx.devices)
    del trainer
    gc.collect()
    ctx.mark("window")
    ref = reference_readings(cfg, traffic, ctx.seed, rows, steps,
                             against={"program": program["moment_tree"]})
    lim = harness.limits(ctx.cell)
    run.checks = {k: (v, lim[k]) for k, v in compare(program, ref).items() if k in lim}
    ctx.mark("reference")
    by_scope = reduce_by_scope(ctx.tracer, chips)
    if by_scope is not None:
        run.counters["by_scope"] = by_scope
    run.trace = ctx.tracer.summary(chips)
    return run
