"""Test-only sizes and a run context that skips the look for a chip."""

from __future__ import annotations

import copy
import time
import types

from benchmark.lib import harness, peaks

TINY_CFG = {
    "name": "tiny", "vocab_size": 256, "n_positions": 64, "n_embd": 64,
    "n_layer": 2, "n_head": 4, "initializer_range": 0.02,
}


def tiny_train_traffic() -> dict:
    t = copy.deepcopy(harness.traffic("pretrain-1k"))
    t.update(seq_len=64, batch_per_chip={"1": 4, "4": 2}, warm_dispatches=1,
             attention_impl="xla", remat=False, trace_seconds=0.5)
    return t


def tiny_serve_traffic(name: str) -> dict:
    t = copy.deepcopy(harness.traffic(name))
    t["server"].update(slots=4, chunk=4, block_size=4, kv_blocks=64)
    t["arrivals"]["warmup_s"] = 1.0
    if t["shared_prefix"]:
        t["shared_prefix"].update(min=16, max=28)
        t["arrivals"]["rate_per_s"] = 8.0
        t["arrivals"]["spare_cycles"] = 150
    else:
        t["arrivals"]["rate_per_s"] = 4.0
    t["prompt"].update(min=4, max=12, median=8)
    t["answer"].update(min=6, max=12, median=8)
    t["check_requests"] = 3
    return t


def context(cell: str, cfg: dict, traffic: dict, chips: int = 1,
            seed: int = 5, seconds: float = 2.0):
    import jax

    return types.SimpleNamespace(
        cell=cell, cfg=cfg, traffic=traffic, chips=chips, seed=seed,
        seconds=seconds, devices=jax.devices()[:chips],
        peaks=peaks.PEAKS["TPU v5 lite"], t0=time.perf_counter(),
        compiles=harness.CompileCounter(),
        tracer=harness.Tracer(False, traffic["trace_seconds"]),
        mark=lambda name: None,
    )


# Limits for the tiny sizes on the CPU, set as the real ones are: above
# what sound runs read there (loss 2e-5, moment 1e-3, change 3e-3) and
# below what the faults read (half of the batch: moment 0.4; the
# exchange left out: moment 0.3; a state left unchanged: change 1).
TINY_TRAIN_LIMITS = {
    "loss_gap_step1": 3e-4, "loss_gap_step2": 3e-4, "loss_gap_step3": 3e-4,
    "moment_norm_gap": 0.05, "change_norm_gap": 0.05, "moment_rel_err_all": 0.05,
}
TINY_SERVE_LIMITS = {"max_logit_gap": 0.02}
