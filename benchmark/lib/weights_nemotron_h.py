"""Weights of a ``nemotron_h`` configuration from ``--seed``: one jitted
call, on the device, float32.

A plain dict with the leaf names and per-kind stacked layout of the
program's ``HybridLMParams`` (the driver wraps it; the reference reads it
as it is). Made by the benchmark, so the reference takes nothing the
program made. What the configuration's ``assumed`` says of the start:
matrices N(0, 0.02); the projections that write to the residual stream
(``out_proj``, ``wo``, every ``down``) scaled by 1/sqrt(published depth);
``A`` uniform in [1, 16]; ``dt_bias`` the inverse softplus of a log-uniform
draw in [time_step_min, time_step_max] floored at time_step_floor; the
convolution uniform in +-1/sqrt(kernel); ``D`` and the norm weights 1; the
convolution's bias and the router's correction bias 0.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02


def dims(cfg: dict) -> dict:
    """The sizes the weights, the reference and the operation count read,
    from a configuration file's keys."""
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers disagree")
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    return {
        "pattern": pattern, "d": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "n_m": pattern.count("M"), "n_e": pattern.count("E"),
        "n_a": pattern.count("*"),
        "ssm_heads": heads, "ssm_head_dim": hd, "ssm_groups": groups,
        "ssm_state": state, "inner": heads * hd,
        "conv_dim": heads * hd + 2 * groups * state,
        "conv_kernel": cfg["conv_kernel"], "chunk": cfg["chunk_size"],
        "experts": cfg["deployment"]["router_width"],
        "held": tuple(cfg["deployment"]["experts_held"]),
        "top_k": cfg["num_experts_per_tok"],
        "expert_dim": cfg["moe_intermediate_size"],
        "shared_dim": cfg["moe_shared_expert_intermediate_size"],
        "routed_scale": cfg["routed_scaling_factor"],
        "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"], "eps": cfg["norm_eps"],
        "depth": cfg["published"]["num_hidden_layers"],
        "dt": (cfg["time_step_min"], cfg["time_step_max"], cfg["time_step_floor"]),
    }


def shapes(cfg: dict) -> dict:
    """Leaf name -> shape."""
    z = dims(cfg)
    d, nm, ne, na = z["d"], z["n_m"], z["n_e"], z["n_a"]
    h, inner, cdim = z["ssm_heads"], z["inner"], z["conv_dim"]
    held = z["held"][1]
    if held != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts is the experts held here")
    hq, hkv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    return {
        "embed": (z["vocab"], d),
        "mamba": {
            "norm": (nm, d), "in_proj": (nm, d, inner + cdim + h),
            "conv_w": (nm, z["conv_kernel"], cdim), "conv_b": (nm, cdim),
            "dt_bias": (nm, h), "a_log": (nm, h), "d_skip": (nm, h),
            "gate_norm": (nm, inner), "out_proj": (nm, inner, d),
        },
        "moe": {
            "norm": (ne, d), "router": (ne, d, z["experts"]),
            "router_bias": (ne, z["experts"]),
            "w_up": (ne, held, d, z["expert_dim"]),
            "w_down": (ne, held, z["expert_dim"], d),
            "shared_up": (ne, d, z["shared_dim"]),
            "shared_down": (ne, z["shared_dim"], d),
        },
        "attn": {
            "norm": (na, d), "wq": (na, d, hq), "wk": (na, d, hkv),
            "wv": (na, d, hkv), "wo": (na, hq, d),
        },
        "norm_f": (d,), "head": (d, z["vocab"]),
    }


def count(cfg: dict) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


RESIDUAL = {("mamba", "out_proj"), ("moe", "w_down"), ("moe", "shared_down"),
            ("attn", "wo")}
ONES = {("mamba", "norm"), ("mamba", "d_skip"), ("mamba", "gate_norm"),
        ("moe", "norm"), ("attn", "norm")}
ZEROS = {("mamba", "conv_b"), ("moe", "router_bias")}


def _make_leaves(shp: dict, depth: int, kernel: int, dt, key):
    f32 = jnp.float32
    names = [(g, n) for g in ("mamba", "moe", "attn") for n in shp[g]]
    keys = dict(zip(names + ["embed", "head"],
                    jax.random.split(key, len(names) + 2)))
    lo, hi, floor = dt
    out = {"embed": STD * jax.random.normal(keys["embed"], shp["embed"], f32),
           "head": STD * jax.random.normal(keys["head"], shp["head"], f32),
           "norm_f": jnp.ones(shp["norm_f"], f32),
           "mamba": {}, "moe": {}, "attn": {}}
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
    import json

    cfg = json.loads(cfg_json)
    z = dims(cfg)
    return jax.jit(functools.partial(
        _make_leaves, shapes(cfg), z["depth"], z["conv_kernel"], z["dt"]))


def make(cfg: dict, seed: int) -> dict:
    """The configuration's weights for this seed (seeds above 2**31 are
    fine: the key is built from the two 32-bit halves)."""
    import json

    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0xFFFFFFFF), int(seed) >> 32)
    return _maker(json.dumps(cfg, sort_keys=True))(key)
