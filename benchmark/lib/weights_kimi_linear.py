"""Weights of a ``kimi_linear`` configuration from ``--seed``: one jitted
call, on the device, float32.

A plain dict with the leaf names and per-kind stacked layout of the
program's ``StackLMParams`` (the driver wraps it; the reference reads it
as it is). Made by the benchmark, so the reference takes nothing the
program made. What the configuration's ``assumed`` says of the start:
matrices N(0, 0.02); the projections that write to the residual stream
(``out_proj``, ``wo``, every ``down``) scaled by 1/sqrt(2 x published
depth); ``A`` uniform in [1, 16]; ``dt_bias`` the inverse softplus of a
log-uniform draw in [0.001, 0.1] floored at 0.0001; the convolutions
uniform in +-1/sqrt(kernel); the norm weights 1; the router's correction
bias 0.

    python3 benchmark/lib/weights_kimi_linear.py

prints the parameter count of ``benchmark/configs/kimi-linear-48b-a3b.json``
term by term beside ISSUE 35's arithmetic (602,434,432).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

STD = 0.02
DT = (1e-3, 1e-1, 1e-4)  # the decay's start: min, max, floor of the step
GATE_RANK = 128  # assumed: the head size
EXPECTED = 602_434_432  # ISSUE 35's arithmetic for the benchmark's cut


def pattern_of(cfg: dict) -> str:
    """The published layers as the program's pattern, two letters a layer:
    the mixer (``K`` in ``kda_layers``, ``L`` in ``full_attn_layers``,
    counted from 1) then the feed-forward (``D`` for the first
    ``first_k_dense_replace`` layers, ``E`` after)."""
    lin = cfg["linear_attn_config"]
    out = ""
    for i in range(1, cfg["num_hidden_layers"] + 1):
        if (i in lin["kda_layers"]) == (i in lin["full_attn_layers"]):
            raise ValueError(f"layer {i} is in one of the two lists, not {lin}")
        out += "K" if i in lin["kda_layers"] else "L"
        out += "D" if i <= cfg["first_k_dense_replace"] else "E"
    return out


def dims(cfg: dict) -> dict:
    """The sizes the weights, the reference and the operation count read,
    from a configuration file's keys."""
    lin = cfg["linear_attn_config"]
    pattern = pattern_of(cfg)
    if pattern != cfg["deployment"]["pattern"]:
        raise ValueError("the layer lists and deployment.pattern disagree")
    return {
        "pattern": pattern, "d": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "n_k": pattern.count("K"), "n_l": pattern.count("L"),
        "n_d": pattern.count("D"), "n_e": pattern.count("E"),
        "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
        "kda_inner": lin["num_heads"] * lin["head_dim"],
        "gate_rank": GATE_RANK, "conv_kernel": lin["short_conv_kernel_size"],
        "heads": cfg["num_attention_heads"], "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "shared_k": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"], "dense_dim": cfg["intermediate_size"],
        "experts": cfg["deployment"]["router_width"],
        "held": tuple(cfg["deployment"]["experts_held"]),
        "top_k": cfg["num_experts_per_token"],
        "expert_dim": cfg["moe_intermediate_size"],
        "shared_dim": cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        "routed_scale": cfg["routed_scaling_factor"],
        "eps": cfg["rms_norm_eps"],
        # two residual sublayers a published layer
        "depth": 2 * cfg["published"]["num_hidden_layers"],
    }


def shapes(cfg: dict) -> dict:
    """Leaf name -> shape."""
    z = dims(cfg)
    d, nk, nl, nd, ne = z["d"], z["n_k"], z["n_l"], z["n_d"], z["n_e"]
    h, inner, rank = z["kda_heads"], z["kda_inner"], z["gate_rank"]
    held = z["held"][1]
    if held != cfg["num_experts"]:
        raise ValueError("num_experts is the experts held here")
    qk = z["nope"] + z["shared_k"]
    return {
        "embed": (z["vocab"], d),
        "kda": {
            "norm": (nk, d), "in_proj": (nk, d, 3 * inner + 2 * rank + h),
            "conv_w": (nk, z["conv_kernel"], 3 * inner),
            "decay_up": (nk, rank, inner), "dt_bias": (nk, inner),
            "a_log": (nk, h), "gate_up": (nk, rank, inner),
            "out_norm": (nk, z["kda_head_dim"]), "out_proj": (nk, inner, d),
        },
        "mla": {
            "norm": (nl, d), "wq": (nl, d, z["heads"] * qk),
            "w_dkv": (nl, d, z["kv_rank"] + z["shared_k"]),
            "kv_norm": (nl, z["kv_rank"]),
            "w_ukv": (nl, z["kv_rank"], z["heads"] * (z["nope"] + z["v_dim"])),
            "wo": (nl, z["heads"] * z["v_dim"], d),
        },
        "dense": {
            "norm": (nd, d), "w_gate": (nd, d, z["dense_dim"]),
            "w_up": (nd, d, z["dense_dim"]), "w_down": (nd, z["dense_dim"], d),
        },
        "moe": {
            "norm": (ne, d), "router": (ne, d, z["experts"]),
            "router_bias": (ne, z["experts"]),
            "w_gate": (ne, held, d, z["expert_dim"]),
            "w_up": (ne, held, d, z["expert_dim"]),
            "w_down": (ne, held, z["expert_dim"], d),
            "shared_gate": (ne, d, z["shared_dim"]),
            "shared_up": (ne, d, z["shared_dim"]),
            "shared_down": (ne, z["shared_dim"], d),
        },
        "norm_f": (d,), "head": (d, z["vocab"]),
    }


GROUPS = ("kda", "mla", "dense", "moe")


def count_by_group(cfg: dict) -> dict:
    shp = shapes(cfg)
    out = {g: sum(math.prod(s) for s in shp[g].values()) for g in GROUPS}
    out["embed_head_norm"] = sum(
        math.prod(shp[k]) for k in ("embed", "head", "norm_f"))
    return out


def count(cfg: dict) -> int:
    return sum(count_by_group(cfg).values())


RESIDUAL = {("kda", "out_proj"), ("mla", "wo"), ("dense", "w_down"),
            ("moe", "w_down"), ("moe", "shared_down")}
ONES = {("kda", "norm"), ("kda", "out_norm"), ("mla", "norm"),
        ("mla", "kv_norm"), ("dense", "norm"), ("moe", "norm")}
ZEROS = {("moe", "router_bias")}


def _make_leaves(shp: dict, depth: int, kernel: int, key):
    f32 = jnp.float32
    names = [(g, n) for g in GROUPS for n in shp[g]]
    keys = dict(zip(names + ["embed", "head"],
                    jax.random.split(key, len(names) + 2)))
    lo, hi, floor = DT
    out = {"embed": STD * jax.random.normal(keys["embed"], shp["embed"], f32),
           "head": STD * jax.random.normal(keys["head"], shp["head"], f32),
           "norm_f": jnp.ones(shp["norm_f"], f32), **{g: {} for g in GROUPS}}
    for g, n in names:
        shape, k = shp[g][n], keys[(g, n)]
        if (g, n) in ONES:
            leaf = jnp.ones(shape, f32)
        elif (g, n) in ZEROS:
            leaf = jnp.zeros(shape, f32)
        elif n == "conv_w":
            leaf = jax.random.uniform(k, shape, f32, -1.0, 1.0) / math.sqrt(kernel)
        elif n == "a_log":
            leaf = jnp.log(jax.random.uniform(k, shape, f32, 1.0, 16.0))
        elif n == "dt_bias":
            step = jnp.exp(jax.random.uniform(k, shape, f32)
                           * (math.log(hi) - math.log(lo)) + math.log(lo))
            step = jnp.maximum(step, floor)
            leaf = step + jnp.log(-jnp.expm1(-step))
        else:
            std = STD / math.sqrt(depth) if (g, n) in RESIDUAL else STD
            leaf = std * jax.random.normal(k, shape, f32)
        out[g][n] = leaf
    return out


@functools.lru_cache(maxsize=4)
def _maker(cfg_json: str):
    cfg = json.loads(cfg_json)
    z = dims(cfg)
    return jax.jit(functools.partial(
        _make_leaves, shapes(cfg), z["depth"], z["conv_kernel"]))


def make(cfg: dict, seed: int) -> dict:
    """The configuration's weights for this seed (seeds above 2**31 are
    fine: the key is built from the two 32-bit halves)."""
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0xFFFFFFFF), int(seed) >> 32)
    return _maker(json.dumps(cfg, sort_keys=True))(key)


def main() -> int:
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "kimi-linear-48b-a3b.json")
    with open(path) as f:
        cfg = json.load(f)
    z, by = dims(cfg), count_by_group(cfg)
    # ISSUE 35's terms: a mixer or feed-forward without its sublayer norm
    # (2304 each) and without the routers' bias buffers.
    issue = {"kda": z["n_k"] * 39_514_272, "mla": z["n_l"] * 29_114_880,
             "dense": z["n_d"] * 63_700_992,
             "moe": z["n_e"] * (9 * 7_077_888 + 2304 * 256),
             "embed_head_norm": 2 * 20_480 * 2304}
    norms = {"kda": z["n_k"] * z["d"], "mla": z["n_l"] * z["d"],
             "dense": z["n_d"] * z["d"],
             "moe": z["n_e"] * (z["d"] + z["experts"]),
             "embed_head_norm": z["d"]}
    for g, n in by.items():
        print(f"{g}: {n:,} (ISSUE's term {issue[g]:,} + norms and buffers "
              f"{norms[g]:,}: {'as counted' if n == issue[g] + norms[g] else 'DIFFERS'})")
    total = count(cfg)
    print(f"parameters: {total:,} "
          f"({'as ISSUE 35 reckons' if total == EXPECTED else f'NOT {EXPECTED:,}'})")
    return int(total != EXPECTED)


if __name__ == "__main__":
    raise SystemExit(main())
