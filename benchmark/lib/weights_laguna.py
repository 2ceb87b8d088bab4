"""Weights of a ``laguna`` configuration from ``--seed``: made on the
device in bfloat16, a layer of a stacked leaf at a time.

A plain dict with the leaf names of the program's ``StackLMParams``
(``full``, ``window``, ``dense``, ``moe``), a kind's leaf a list of its
layers' arrays (the cell's module wraps it, the reference reads it as it is). Made by the
benchmark, so the reference takes nothing the program made. What the
configuration's ``assumed`` says of the start: matrices N(0, 0.02), the
router and the heads' gates among them; the projections that write to the
residual stream (``wo``, every ``down``) scaled by 1/sqrt(2 x published
depth); norm weights 1; the routers' correction bias, a buffer the
configuration does not have, 0. Held in bfloat16 as served (3.87e9
parameters do not fit one chip in float32); the reference widens each
layer's leaves to float32 where it uses them.

    python3 benchmark/lib/weights_laguna.py

prints the parameter count of ``benchmark/configs/laguna-xs.2.json`` term
by term beside the issue's arithmetic (3,869,857,792).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

STD = 0.02
EXPECTED = 3_869_857_792  # the issue's count for the benchmark's cut


def pattern_of(cfg: dict) -> str:
    """The published layers as the program's pattern, two letters a layer:
    the mixer (``G`` full attention, ``W`` window attention) then the
    feed-forward (``D`` dense, ``E`` experts)."""
    mix = {"full_attention": "G", "sliding_attention": "W"}
    ffn = {"dense": "D", "sparse": "E"}
    n = cfg["num_hidden_layers"]
    if len(cfg["layer_types"]) != n or len(cfg["mlp_layer_types"]) != n:
        raise ValueError("layer_types and mlp_layer_types give every layer")
    return "".join(mix[a] + ffn[b] for a, b in
                   zip(cfg["layer_types"], cfg["mlp_layer_types"]))


def _heads(cfg: dict, kind: str) -> int:
    """The query heads of every layer of one attention kind (one number)."""
    got = {h for t, h in zip(cfg["layer_types"],
                             cfg["num_attention_heads_per_layer"]) if t == kind}
    if len(got) != 1:
        raise ValueError(f"{kind} layers differ in query heads: {got}")
    return got.pop()


def dims(cfg: dict) -> dict:
    """The sizes the weights, the reference and the operation count read,
    from a configuration file's keys."""
    pattern = pattern_of(cfg)
    if pattern != cfg["deployment"]["pattern"]:
        raise ValueError("the layer lists and deployment.pattern disagree")
    rope = cfg["rope_parameters"]
    full, win = rope["full_attention"], rope["sliding_attention"]
    hd = cfg["head_dim"]
    return {
        "pattern": pattern, "d": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "n_g": pattern.count("G"), "n_w": pattern.count("W"),
        "n_d": pattern.count("D"), "n_e": pattern.count("E"),
        "full_heads": _heads(cfg, "full_attention"),
        "window_heads": _heads(cfg, "sliding_attention"),
        "kv_heads": cfg["num_key_value_heads"], "head_dim": hd,
        "window": cfg["sliding_window"],
        "full_rope": {
            "theta": float(full["rope_theta"]),
            "dims": int(hd * full["partial_rotary_factor"]),
            "factor": float(full["factor"]),
            "original_len": int(full["original_max_position_embeddings"]),
            "beta_fast": float(full["beta_fast"]),
            "beta_slow": float(full["beta_slow"]),
            "attention_factor": float(full["attention_factor"]),
        },
        "window_rope": {
            "theta": float(win["rope_theta"]),
            "dims": int(hd * win["partial_rotary_factor"]),
        },
        "dense_dim": cfg["intermediate_size"],
        "experts": cfg["num_experts"], "top_k": cfg["num_experts_per_tok"],
        "expert_dim": cfg["moe_intermediate_size"],
        "shared_dim": cfg["shared_expert_intermediate_size"],
        "routed_scale": cfg["moe_routed_scaling_factor"],
        "eps": cfg["rms_norm_eps"],
        # two residual sublayers a published layer
        "depth": 2 * cfg["published"]["num_hidden_layers"],
    }


def shapes(cfg: dict) -> dict:
    """Leaf name -> shape."""
    z = dims(cfg)
    d, hd, kv = z["d"], z["head_dim"], z["kv_heads"] * z["head_dim"]

    def gated(n, heads):
        return {"norm": (n, d), "wq": (n, d, heads * hd), "wk": (n, d, kv),
                "wv": (n, d, kv), "w_gate": (n, d, heads),
                "wo": (n, heads * hd, d)}

    ne, f, s = z["n_e"], z["expert_dim"], z["shared_dim"]
    return {
        "embed": (z["vocab"], d),
        "full": gated(z["n_g"], z["full_heads"]),
        "window": gated(z["n_w"], z["window_heads"]),
        "dense": {
            "norm": (z["n_d"], d), "w_gate": (z["n_d"], d, z["dense_dim"]),
            "w_up": (z["n_d"], d, z["dense_dim"]),
            "w_down": (z["n_d"], z["dense_dim"], d),
        },
        "moe": {
            "norm": (ne, d), "router": (ne, d, z["experts"]),
            "router_bias": (ne, z["experts"]),
            "w_gate": (ne, z["experts"], d, f), "w_up": (ne, z["experts"], d, f),
            "w_down": (ne, z["experts"], f, d),
            "shared_gate": (ne, d, s), "shared_up": (ne, d, s),
            "shared_down": (ne, s, d),
        },
        "norm_f": (d,), "head": (d, z["vocab"]),
    }


GROUPS = ("full", "window", "dense", "moe")
RESIDUAL = {("full", "wo"), ("window", "wo"), ("dense", "w_down"),
            ("moe", "w_down"), ("moe", "shared_down")}
ONES = {(g, "norm") for g in GROUPS}
ZEROS = {("moe", "router_bias")}  # a buffer: no parameter of the configuration


def count_by_group(cfg: dict) -> dict:
    shp = shapes(cfg)
    out = {g: sum(math.prod(s) for n, s in shp[g].items()
                  if (g, n) not in ZEROS) for g in GROUPS}
    out["embed_head_norm"] = sum(
        math.prod(shp[k]) for k in ("embed", "head", "norm_f"))
    return out


def count(cfg: dict) -> int:
    return sum(count_by_group(cfg).values())


def _layer(shape, std, key):
    """One layer's slice of a leaf in bfloat16."""
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)


@functools.lru_cache(maxsize=64)
def _layer_maker(shape: tuple, std: float):
    return jax.jit(functools.partial(_layer, shape, std))


def make(cfg: dict, seed: int) -> dict:
    """The configuration's weights for this seed (seeds above 2**31 are
    fine: the key is built from the two 32-bit halves). A kind's leaf is a
    list of its layers' arrays, each its own buffer (the server reads a
    layer's matrices where they lie; a slice of a stacked leaf inside a
    compiled loop is a copy)."""
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0xFFFFFFFF), int(seed) >> 32)
    shp = shapes(cfg)
    depth = dims(cfg)["depth"]
    names = [(g, n) for g in GROUPS for n in shp[g]] + ["embed", "head"]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    out = {g: {} for g in GROUPS}
    for name in names:
        std = STD / math.sqrt(depth) if name in RESIDUAL else STD
        if isinstance(name, str):  # embed, head
            out[name] = _layer_maker(shp[name], std)(keys[name])
            continue
        shape = shp[name[0]][name[1]]
        if name in ONES or name in ZEROS:
            fill = 1.0 if name in ONES else 0.0
            layers = [jnp.full(shape[1:], fill, jnp.bfloat16)
                      for _ in range(shape[0])]
        else:
            layers = [_layer_maker(shape[1:], std)(k)
                      for k in jax.random.split(keys[name], shape[0])]
        out[name[0]][name[1]] = layers
    out["norm_f"] = jnp.ones(shp["norm_f"], jnp.bfloat16)
    return out


def main() -> int:
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "laguna-xs.2.json")
    with open(path) as f:
        cfg = json.load(f)
    z, by = dims(cfg), count_by_group(cfg)
    # The issue's terms: each kind's layers with their sublayer norms.
    issue = {"full": z["n_g"] * 29_460_480, "window": z["n_w"] * 37_881_856,
             "dense": z["n_d"] * 50_333_696, "moe": z["n_e"] * 808_978_432,
             "embed_head_norm": 411_041_792 + 2048}
    for g, n in by.items():
        print(f"{g}: {n:,} ({'as the issue counts' if n == issue[g] else 'DIFFERS'})")
    total = count(cfg)
    print(f"parameters: {total:,} "
          f"({'as the issue reckons' if total == EXPECTED else f'NOT {EXPECTED:,}'});"
          f" the program's tree holds {z['n_e'] * z['experts']:,} more: the "
          "routers' correction-bias buffers, zero")
    return int(total != EXPECTED)


if __name__ == "__main__":
    raise SystemExit(main())
