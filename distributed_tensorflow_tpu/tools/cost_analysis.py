"""Analytical cost/roofline report for a compiled train step.

The reference reasoned about performance by wall-clock alone — hand-rolled
``AvgTime`` per 100 batches and per-epoch totals pasted into its experiment
log (reference tfdist_between.py:98-110, README.md:38-40,97-101), with no
way to say *why* a configuration was slow. On TPU the compiler itself can
answer that: XLA's analytical model reports FLOPs and bytes accessed for
any compiled program, and comparing their ratio (arithmetic intensity)
against the hardware's FLOPs/byte balance point classifies the program as
compute- or bandwidth-bound and predicts its per-step floor — the
"How to Scale Your Model" roofline recipe, as a tool.

Usage::

    python -m distributed_tensorflow_tpu.tools.cost_analysis --model mlp
    python -m distributed_tensorflow_tpu.tools.cost_analysis --model lstm --batch 512

or ``cost_analysis.analyze(model, batch_size=...)`` in code. Numbers come
from ``jax.stages.Compiled.cost_analysis()`` — the same estimates the XLA
scheduler uses; they are analytical (no execution, works on any backend),
so use them for *shape* questions (bound class, scaling with batch) and
the benchmark tools for measured wall clock.
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.ops import cross_entropy, sgd
from distributed_tensorflow_tpu.parallel.strategy import SingleDevice

# Peak numbers for rooflining, per chip. Sources: public TPU spec sheets
# (bf16 matmul peak / HBM bandwidth). A device that is not listed —
# the CPU the tests run on included — classifies as ``bound="unknown"``.
CHIP_PEAKS = {
    "tpu v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
    "tpu v4": {"flops": 275e12, "hbm_bytes_per_s": 1228e9},
}


def _chip_peaks(device) -> dict | None:
    """Peaks for the device, or None when unknown — a wrong balance point
    misclassifies every program, so refuse rather than guess."""
    kind = device.device_kind.lower()
    for prefix, peaks in CHIP_PEAKS.items():
        if kind.startswith(prefix):
            return peaks
    return None


def _roofline(compiled, batch_size: int, device) -> dict:
    """Shared report body: XLA's analytical FLOPs/bytes for a compiled
    step, arithmetic intensity, and (when the chip's peaks are known) the
    balance-point classification and per-step floor — used verbatim by the
    classifier and LM analyzers so the two can't drift."""
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    intensity = flops / bytes_accessed if bytes_accessed else float("inf")
    report = {
        "flops_per_step": flops,
        "bytes_per_step": bytes_accessed,
        "arithmetic_intensity_flops_per_byte": round(intensity, 3),
    }
    peaks = _chip_peaks(device)
    if peaks is None:
        report.update(
            chip_balance_flops_per_byte=None, bound="unknown",
            roofline_floor_us=None, examples_per_sec_roofline=None,
        )
        return report
    balance = peaks["flops"] / peaks["hbm_bytes_per_s"]  # FLOPs/byte
    t_compute = flops / peaks["flops"]
    t_memory = bytes_accessed / peaks["hbm_bytes_per_s"]
    report.update(
        chip_balance_flops_per_byte=round(balance, 1),
        bound="compute" if intensity > balance else "memory",
        roofline_floor_us=round(max(t_compute, t_memory) * 1e6, 3),
        examples_per_sec_roofline=round(
            batch_size / max(t_compute, t_memory, 1e-12), 1
        ),
    )
    return report


def analyze(
    model,
    batch_size: int = 100,
    in_dim: int = 784,
    out_dim: int = 10,
    learning_rate: float = 0.001,
    device=None,
) -> dict:
    """Compile one SGD train step for ``model`` and report its analytical
    cost plus the roofline classification on ``device`` (default: device 0).
    """
    device = device or jax.devices()[0]
    # Analyze the *actual* program the Trainer compiles — the SingleDevice
    # strategy's train step (parallel/strategy.py) — not a re-derivation
    # that could drift from it.
    strategy = SingleDevice()
    opt = sgd(learning_rate)
    state = strategy.init_state(model, opt, seed=1)
    step = strategy.make_train_step(model, cross_entropy, opt)

    x = jnp.zeros((batch_size, in_dim), jnp.float32)
    y = jnp.zeros((batch_size, out_dim), jnp.float32)
    compiled = step.lower(state, x, y).compile()
    n_params = sum(
        p.size for p in jax.tree_util.tree_leaves(state.params)
    )
    mem = compiled.memory_analysis()
    report = {
        "model": type(model).__name__,
        "batch_size": batch_size,
        "device_kind": device.device_kind,
        "param_count": int(n_params),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
    }
    report.update(_roofline(compiled, batch_size, device))
    return report


def analyze_lm(
    model,
    batch_size: int = 8,
    *,
    optimizer=None,
    device=None,
) -> dict:
    """Roofline for one LM training step (``make_lm_train_step`` — the
    actual program `LMTrainer`/`tools/lm_bench.py` run, not a
    re-derivation): compiled FLOPs/bytes, arithmetic intensity vs the
    chip's balance point, per-step floor, and the FLOPs count
    ``tools/lm_bench.py`` divides by measured step time for MFU."""
    from distributed_tensorflow_tpu.models.gpt import make_lm_train_step
    from distributed_tensorflow_tpu.ops import optim as optim_lib

    device = device or jax.devices()[0]
    optimizer = optimizer or optim_lib.make("adam", 1e-3)
    params = model.init(seed=1)
    opt_state = optimizer.init(params)
    step = make_lm_train_step(model, optimizer)
    tokens = jnp.zeros((batch_size, model.max_len), jnp.int32)
    compiled = step.lower(params, opt_state, tokens).compile()
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    report = {
        "model": "GPTLM",
        "batch_size": batch_size,
        "seq_len": model.max_len,
        "tokens_per_step": batch_size * model.max_len,
        "device_kind": device.device_kind,
        "param_count": int(n_params),
    }
    report.update(_roofline(compiled, batch_size, device))
    return report


def format_report(r: dict) -> str:
    lines = [
        f"{r['model']} @ batch {r['batch_size']} on {r['device_kind']}",
        f"  params:               {r['param_count']:,}",
        f"  flops/step:           {r['flops_per_step']:,.0f}",
        f"  bytes/step:           {r['bytes_per_step']:,.0f}",
        f"  arithmetic intensity: {r['arithmetic_intensity_flops_per_byte']} FLOP/B",
    ]
    if r["bound"] == "unknown":
        lines.append(
            "  bound:                unknown (no peak numbers for this chip"
            " — add them to CHIP_PEAKS)"
        )
    else:
        lines += [
            f"  chip balance:         {r['chip_balance_flops_per_byte']} FLOP/B",
            f"  bound:                {r['bound']}",
            f"  roofline floor:       {r['roofline_floor_us']} us/step"
            f"  ({r['examples_per_sec_roofline']:,.0f} ex/s)",
        ]
    return "\n".join(lines)


def main(argv=None) -> int:
    from distributed_tensorflow_tpu.models import MODEL_REGISTRY, build_model

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument(
        "--model", default="mlp", choices=sorted(MODEL_REGISTRY) + ["lm"]
    )
    p.add_argument("--batch", type=int, default=100)
    p.add_argument("--seq-len", type=int, default=512, help="lm only")
    p.add_argument("--model-dim", type=int, default=256, help="lm only")
    p.add_argument("--layers", type=int, default=4, help="lm only")
    p.add_argument("--json", action="store_true", help="emit JSON instead of text")
    args = p.parse_args(argv)
    if args.model == "lm":
        from distributed_tensorflow_tpu.models.gpt import GPTLM

        report = analyze_lm(
            GPTLM(
                vocab_size=8192,
                max_len=args.seq_len,
                model_dim=args.model_dim,
                num_heads=max(1, args.model_dim // 64),
                num_layers=args.layers,
            ),
            batch_size=args.batch,
        )
    else:
        report = analyze(build_model(args.model), batch_size=args.batch)
    if args.json:
        print(json.dumps(report))
    else:
        print(format_report(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
