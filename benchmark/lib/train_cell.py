"""A training cell: ``LMTrainer``'s own scanned step loop
(``run_epoch``, what ``LMTrainer.run`` calls), one dispatch of
``steps_per_dispatch`` optimizer steps at a time, each ended by the
trainer's own fetch of the step costs.

Set-up builds ONE trainer, gives it the benchmark's weights, drives its
first dispatch (the first three steps, on rows that all differ) through
the same call the window uses, reads what the check compares, and hands
the same trainer to the window.
"""

from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops, harness, reference, traffic as traffic_lib, weights


class Rows:
    """The feed: a corpus of ``steps * batch`` rows that all differ. The
    first pass takes them in order, every later pass in an order rotated
    by one batch, so the reference knows the first dispatch's batches
    without asking the program. Same duck type as the program's
    ``TokenDataset``."""

    lengths = None

    def __init__(self, tokens: np.ndarray):
        self.tokens = tokens
        self._at = 0

    @property
    def num_examples(self) -> int:
        return self.tokens.shape[0]

    def next_indices(self, batch_size: int) -> np.ndarray:
        idx = (self._at + np.arange(batch_size)) % self.num_examples
        self._at = (self._at + batch_size) % self.num_examples
        return idx.astype(np.int32)

    def next_batch(self, batch_size: int):
        return self.tokens[self.next_indices(batch_size)]


def global_batch(traffic: dict, chips: int) -> int:
    return traffic["batch_per_chip"][str(chips)] * chips


def build_model(cfg: dict, traffic: dict):
    from distributed_tensorflow_tpu.models.gpt import GPTLM

    return GPTLM(
        vocab_size=cfg["vocab_size"], max_len=cfg["n_positions"],
        model_dim=cfg["n_embd"], num_heads=cfg["n_head"],
        num_layers=cfg["n_layer"],
        attention_impl=traffic.get("attention_impl", "xla"),
        remat=traffic.get("remat", False),
    )


def to_program_params(tree: dict):
    from distributed_tensorflow_tpu.models.gpt import GPTBlockParams, GPTLMParams

    return GPTLMParams(**{**tree, "blocks": GPTBlockParams(**tree["blocks"])})


def build_trainer(cfg, traffic, chips, devices, rows, train_config_kw=None):
    """The trainer as a user builds it, state not yet the benchmark's."""
    from collections import namedtuple

    from distributed_tensorflow_tpu.config import TrainConfig
    from distributed_tensorflow_tpu.train import LMTrainer

    batch = global_batch(traffic, chips)
    feed = Rows(rows)
    held_out = Rows(rows[:batch])
    corpus = namedtuple("Corpus", "train validation test")(feed, held_out, held_out)
    mesh_spec = traffic["mesh"][str(chips)]
    mesh, extra = None, {}
    if mesh_spec is not None:
        from distributed_tensorflow_tpu.parallel import make_mesh

        mesh = make_mesh(tuple(mesh_spec["shape"]), tuple(mesh_spec["axes"]),
                         devices=devices)
        extra["dp_mode"] = mesh_spec["dp_mode"]
    tc = TrainConfig(
        batch_size=batch, epochs=1, optimizer=traffic["optimizer"],
        learning_rate=traffic["learning_rate"], log_frequency=10 ** 9,
        logs_path="", scan_epoch=True, **extra, **(train_config_kw or {}),
    )
    trainer = LMTrainer(
        build_model(cfg, traffic), corpus, tc, mesh=mesh,
        print_fn=lambda *a: None,
    )
    return trainer


def give_weights(trainer, cfg, seed) -> None:
    """Replace the trainer's own initial state by the benchmark's weights
    (freed first, so the process's peak is the window's)."""
    trainer.state = None
    gc.collect()
    trainer.state = trainer._init_state(to_program_params(weights.make(cfg, seed)))


def _per_layer(norm_tree: dict) -> dict:
    """Flatten {leaf: scalar or [n_layer]} to {name: float}."""
    out = {}
    for name, v in norm_tree.items():
        if isinstance(v, dict):
            for k, arr in v.items():
                for i, x in enumerate(np.asarray(arr)):
                    out[f"blocks.{k}.{i}"] = float(x)
        else:
            out[name] = float(np.asarray(v))
    return out


@functools.partial(jax.jit, static_argnums=2)
def _norm(x, y, per_layer):
    x = x.astype(jnp.float32)
    if y is not None:
        x = x - y.astype(jnp.float32)
    axes = tuple(range(1, x.ndim)) if per_layer else None
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))


def _norms(tree: dict, minus: dict | None = None) -> dict:
    """Per-leaf L2 norms (of ``tree - minus``), block leaves per layer."""
    other = minus or {"blocks": {}}
    out = {k: _norm(v, other.get(k), False) for k, v in tree.items() if k != "blocks"}
    out["blocks"] = {
        k: _norm(v, other["blocks"].get(k), True) for k, v in tree["blocks"].items()}
    return _per_layer(jax.device_get(out))


def _as_dict(params) -> dict:
    d = params._asdict()
    d["blocks"] = d["blocks"]._asdict()
    return d


def _adam_mu(opt_state):
    for s in opt_state:
        if hasattr(s, "mu"):
            return s.mu
    raise RuntimeError("no Adam moment in the optimizer state")


def program_readings(trainer, cfg, seed, logger) -> dict:
    """The first dispatch through the window's own call, and what the
    check compares from it: each step's loss, the first moment the
    optimizer holds after the steps (its norm per leaf, and the moment
    itself, copied to the host), the parameters' change (per leaf)."""
    trainer.run_epoch(0, logger)
    losses = [float(x) for x in np.asarray(trainer._epoch_costs)]
    state = trainer.state
    start = weights.make(cfg, seed)
    now = _as_dict(state.params)
    start = jax.tree.map(
        lambda a, like: jax.device_put(a, like.sharding), start, now)
    change = _norms(now, minus=start)
    del start
    mu = _as_dict(_adam_mu(state.opt_state))
    return {"loss": losses, "moment": _norms(mu), "change": change,
            "moment_tree": jax.device_get(mu)}


def reference_readings(cfg, traffic, seed, rows, devices, steps,
                       precision="float32", fault=None, against=None,
                       keep_moment=False) -> dict:
    """The plain reference over the same first steps. ``fault`` plants
    one of the faults of "How correct is decided" in the reference put in
    the program's place: ``half_batch`` or ``no_exchange``. ``against``
    maps names to other runs' moments (host trees): the norm, per leaf, of
    each one's difference from this run's moment is read too."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("r",))
    n = len(devices)

    def sharding(x):
        # The state is spread over the chips so that it fits; any layout
        # gives the same mathematics.
        for axis in range(x.ndim - 1, -1, -1):
            if n > 1 and x.shape[axis] % n == 0 and x.shape[axis] >= 1024:
                spec = [None] * x.ndim
                spec[axis] = "r"
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    start = weights.make(cfg, seed)
    params = jax.tree.map(lambda x: jax.device_put(x, sharding(x)), start)
    del start
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    mu, nu = zeros(params), zeros(params)
    h = traffic["adamw"]
    hyper = (traffic["learning_rate"], h["b1"], h["b2"], h["eps"], h["weight_decay"])
    batch = rows.shape[0] // steps
    block = traffic["reference_rows_per_block"]
    losses = []
    for s in range(steps):
        toks = rows[s * batch:(s + 1) * batch]
        if fault == "half_batch":
            toks = toks[: batch // 2]
        loss, grads = reference.loss_and_grad(
            params, jnp.asarray(toks), cfg["n_head"], min(block, toks.shape[0]),
            precision, 2 if fault == "no_exchange" else 1)
        losses.append(float(loss))
        params, mu, nu = reference.adamw(params, mu, nu, grads, hyper, s + 1)
        del grads
    out = {"loss": losses, "moment": _norms(mu)}
    out["moment_err"] = {}
    for name, tree in (against or {}).items():
        theirs = jax.tree.map(
            lambda a, like: jax.device_put(a, like.sharding), tree, mu)
        out["moment_err"][name] = _norms(theirs, minus=mu)
        del theirs
    if keep_moment:
        out["moment_tree"] = jax.device_get(mu)
    del mu, nu
    first = jax.tree.map(
        lambda x: jax.device_put(x, sharding(x)), weights.make(cfg, seed))
    out["change"] = _norms(params, minus=first)
    return out


def compare(program: dict, ref: dict, name: str = "program") -> dict:
    """The numbers compared. Norms are compared by the worst leaf: the
    gap between the program's norm and the reference's, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Leaves whose reference moment is under a thousandth of the
    median leaf's are left out of the change (they move by round-off
    alone under Adam)."""
    out = {}
    for i, (a, b) in enumerate(zip(program["loss"], ref["loss"])):
        out[f"loss_gap_step{i + 1}"] = abs(a - b) / abs(b)
    med_m = float(np.median(list(ref["moment"].values())))
    med_c = float(np.median(list(ref["change"].values())))
    live = [k for k, v in ref["moment"].items() if v >= 1e-3 * med_m]
    out["moment_norm_gap"] = max(
        abs(program["moment"][k] - ref["moment"][k]) / max(ref["moment"][k], med_m)
        for k in ref["moment"])
    out["change_norm_gap"] = max(
        abs(program["change"][k] - ref["change"][k]) / max(ref["change"][k], med_c)
        for k in live)
    err = ref["moment_err"].get(name)
    if err is not None:
        # The numbers that see a direction: the norm of the difference of
        # the two moments (PERF.md section 2 says why the gaps of norms
        # alone cannot tell bfloat16 from int8), by the worst leaf and over
        # the whole tree. The second is steady from seed to seed.
        out["moment_rel_err"] = max(
            err[k] / max(ref["moment"][k], med_m) for k in ref["moment"])
        out["moment_rel_err_all"] = float(
            np.sqrt(sum(v * v for v in err.values()))
            / np.sqrt(sum(v * v for v in ref["moment"].values())))
    return out


def run(ctx) -> harness.Run:
    from distributed_tensorflow_tpu.utils.logging import StepLogger

    cfg, traffic, chips = ctx.cfg, ctx.traffic, ctx.chips
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
    tokens_per_dispatch = steps * batch * traffic["seq_len"]
    compiles = ctx.compiles.count
    t_open = time.perf_counter()
    run.end_to_end["setup_s"] = t_open - ctx.t0
    ends, bad = [], 0
    traced_from = None  # (dispatches done, clock) when the trace started
    while True:
        now = time.perf_counter()
        if ctx.tracer.maybe_start(now - t_open, ctx.seconds):
            traced_from = (len(ends), time.perf_counter())
        with ctx.tracer.annotate("bench:trainer.step"):
            trainer.run_epoch(epoch, logger)
        now = time.perf_counter()
        epoch += 1
        ends.append(now)
        bad += int(not np.all(np.isfinite(np.asarray(trainer._epoch_costs))))
        if now - t_open >= ctx.seconds:
            break
    ctx.tracer.stop()
    t_close = ends[-1]
    run.window_s = t_close - t_open
    run.compiles_in_window = ctx.compiles.count - compiles
    run.attempted, run.failed = len(ends), bad
    run.end_to_end["train_tokens_per_s"] = (
        len(ends) * tokens_per_dispatch / run.window_s)
    run.counters.update(
        dispatches=len(ends), tokens_per_dispatch=tokens_per_dispatch,
        steps_per_dispatch=steps, global_batch=batch,
        traced_dispatches=len(ends) - traced_from[0] if traced_from else 0,
        traced_host_s=t_close - traced_from[1] if traced_from else 0.0,
        flops_per_token=flops.train_flops_per_token(cfg, traffic["seq_len"]),
    )
    run.memory_peak_bytes = harness.memory_peak_bytes(ctx.devices)
    del trainer
    gc.collect()
    ctx.mark("window")
    ref = reference_readings(cfg, traffic, ctx.seed, rows, ctx.devices, steps,
                             against={"program": program["moment_tree"]})
    lim = harness.limits(ctx.cell)
    run.checks = {k: (v, lim[k]) for k, v in compare(program, ref).items() if k in lim}
    ctx.mark("reference")
    run.trace = ctx.tracer.summary(chips)
    return run
