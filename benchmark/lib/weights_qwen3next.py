"""Weights of a ``qwen3_next`` configuration from ``--seed``: made on the
device in bfloat16, in one jitted call.

A plain dict with the leaf names of the program's ``StackLMParams``
(``gdn``, ``full``, ``moe``), a kind's leaf a list of its layers' arrays
(each its own buffer: the server reads a layer's matrices where they lie,
a slice of a stacked leaf inside a compiled loop is a copy; the cell's
module wraps the lists, the reference reads them as they are). Made by the
benchmark, so the reference takes nothing the program made. What the
configuration's ``assumed`` says of the start: matrices N(0, 0.02); the
projections that write to the residual stream (``out_proj``, ``wo``,
every ``down``) divided by sqrt(2 x published depth); the depthwise
convolution U(-1/2, 1/2); ``dt_bias`` ones and ``A_log = log U(0, 16)``;
the zero-centred norms at 0 and the gated output norm's weight at 1.

    python3 benchmark/lib/weights_qwen3next.py

prints the parameter count of ``benchmark/configs/qwen3-next-80b-a3b.json``
term by term beside the count the configuration's ``assumed`` states
(4,133,998,720).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

STD = 0.02
EXPECTED = 4_133_998_720  # the count the configuration file states for its cut


def pattern_of(cfg: dict) -> str:
    """The published layers as the program's pattern, two letters a layer:
    the mixer (``N`` Gated DeltaNet, ``G`` gated full attention every
    ``full_attention_interval``-th layer, counted from 1) then its experts
    (``E``: every layer has them, ``decoder_sparse_step`` 1)."""
    every = cfg["full_attention_interval"]
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("every layer of this configuration has experts")
    return "".join("GE" if (i + 1) % every == 0 else "NE"
                   for i in range(cfg["num_hidden_layers"]))


def dims(cfg: dict) -> dict:
    """The sizes the weights, the reference and the operation count read,
    from a configuration file's keys."""
    pattern = pattern_of(cfg)
    if pattern != cfg["deployment"]["pattern"]:
        raise ValueError("num_hidden_layers and deployment.pattern disagree")
    hd = cfg["head_dim"]
    return {
        "pattern": pattern, "d": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "n_n": pattern.count("N"), "n_g": pattern.count("G"),
        "n_e": pattern.count("E"),
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": hd,
        "rope": {"theta": float(cfg["rope_theta"]),
                 "dims": int(hd * cfg["partial_rotary_factor"])},
        "key_heads": cfg["linear_num_key_heads"],
        "key_dim": cfg["linear_key_head_dim"],
        "value_heads": cfg["linear_num_value_heads"],
        "value_dim": cfg["linear_value_head_dim"],
        "conv": cfg["linear_conv_kernel_dim"],
        "held": cfg["num_experts"],  # routed experts held on this chip
        "experts": cfg["published"]["num_experts"],  # the router's outputs
        "top_k": cfg["num_experts_per_tok"],
        "expert_dim": cfg["moe_intermediate_size"],
        "shared_dim": cfg["shared_expert_intermediate_size"],
        "eps": cfg["rms_norm_eps"],
        # two residual sublayers a published layer
        "depth": 2 * cfg["published"]["num_hidden_layers"],
    }


def shapes(cfg: dict) -> dict:
    """Leaf name -> shape (a kind's leaf stacked over its layers)."""
    z = dims(cfg)
    d, hd = z["d"], z["head_dim"]
    kd = z["key_heads"] * z["key_dim"]
    vd = z["value_heads"] * z["value_dim"]
    hv, nn, ng, ne = z["value_heads"], z["n_n"], z["n_g"], z["n_e"]
    q, kv = z["heads"] * hd, z["kv_heads"] * hd
    f, s, held = z["expert_dim"], z["shared_dim"], z["held"]
    return {
        "embed": (z["vocab"], d),
        "gdn": {
            "norm": (nn, d), "in_qkvz": (nn, d, 2 * kd + 2 * vd),
            "in_ba": (nn, d, 2 * hv), "conv_w": (nn, z["conv"], 2 * kd + vd),
            "dt_bias": (nn, hv), "a_log": (nn, hv),
            "out_norm": (nn, z["value_dim"]), "out_proj": (nn, vd, d),
        },
        "full": {
            "norm": (ng, d), "wq": (ng, d, q), "wk": (ng, d, kv),
            "wv": (ng, d, kv), "w_gate": (ng, d, q), "wo": (ng, q, d),
            "q_norm": (ng, hd), "k_norm": (ng, hd),
        },
        "moe": {
            "norm": (ne, d), "router": (ne, d, z["experts"]),
            "w_gate": (ne, held, d, f), "w_up": (ne, held, d, f),
            "w_down": (ne, held, f, d),
            "shared_gate": (ne, d, s), "shared_up": (ne, d, s),
            "shared_down": (ne, s, d), "shared_router": (ne, d, 1),
        },
        "norm_f": (d,), "head": (d, z["vocab"]),
    }


GROUPS = ("gdn", "full", "moe")
RESIDUAL = {("gdn", "out_proj"), ("full", "wo"), ("moe", "w_down"),
            ("moe", "shared_down")}
ZERO_CENTRED = {("gdn", "norm"), ("full", "norm"), ("full", "q_norm"),
                ("full", "k_norm"), ("moe", "norm")}  # start at 0
ONES = {("gdn", "out_norm"), ("gdn", "dt_bias")}
CONV = {("gdn", "conv_w")}  # U(-1/sqrt(kernel), 1/sqrt(kernel))
A_LOG = {("gdn", "a_log")}  # log U(A_RANGE)
A_RANGE = (0.0, 16.0)  # A = exp(A_log), as the public modelling code draws it


def count_by_group(cfg: dict) -> dict:
    shp = shapes(cfg)
    out = {g: sum(math.prod(s) for s in shp[g].values()) for g in GROUPS}
    out["embed_head_norm"] = sum(
        math.prod(shp[k]) for k in ("embed", "head", "norm_f"))
    return out


def count(cfg: dict) -> int:
    return sum(count_by_group(cfg).values())


def _leaf(name, shape, std, key, a_range):
    """One layer's slice of a leaf in bfloat16."""
    f32 = jnp.float32
    if name in ZERO_CENTRED:
        x = jnp.zeros(shape, f32)
    elif name in ONES:
        x = jnp.ones(shape, f32)
    elif name in CONV:
        bound = 1.0 / math.sqrt(shape[0])
        x = jax.random.uniform(key, shape, f32, -bound, bound)
    elif name in A_LOG:
        x = jnp.log(jax.random.uniform(key, shape, f32, *a_range))
    else:
        x = std * jax.random.normal(key, shape, f32)
    return x.astype(jnp.bfloat16)


@functools.lru_cache(maxsize=8)
def _maker(cfg_json: str, std: float, a_range: tuple):
    cfg = json.loads(cfg_json)
    shp = shapes(cfg)
    depth = dims(cfg)["depth"]
    names = [(g, n) for g in GROUPS for n in shp[g]] + ["embed", "head"]

    def make(key):
        keys = dict(zip(names, jax.random.split(key, len(names))))
        out = {g: {} for g in GROUPS}
        for name in names:
            s = std / math.sqrt(depth) if name in RESIDUAL else std
            if isinstance(name, str):  # embed, head
                out[name] = _leaf(name, shp[name], s, keys[name], a_range)
                continue
            shape = shp[name[0]][name[1]]
            out[name[0]][name[1]] = [
                _leaf(name, shape[1:], s, k, a_range)
                for k in jax.random.split(keys[name], shape[0])]
        out["norm_f"] = jnp.zeros(shp["norm_f"], jnp.bfloat16)  # zero-centred
        return out

    return jax.jit(make)


def make(cfg: dict, seed: int) -> dict:
    """The configuration's weights for this seed, in one jitted call
    (seeds above 2**31 are fine: the key is built from the two 32-bit
    halves)."""
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0xFFFFFFFF), int(seed) >> 32)
    return _maker(json.dumps(cfg, sort_keys=True), STD, A_RANGE)(key)


def main() -> int:
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "qwen3-next-80b-a3b.json")
    with open(path) as f:
        cfg = json.load(f)
    z, by = dims(cfg), count_by_group(cfg)
    # The stated terms: each kind's layers with their sublayer norms.
    stated = {"gdn": z["n_n"] * 33_720_512, "full": z["n_g"] * 27_265_536,
             "moe": z["n_e"] * (4_198_400 + 402_653_184),
             "embed_head_norm": 622_329_856 + 2048}
    for g, n in by.items():
        print(f"{g}: {n:,} ({'as stated' if n == stated[g] else 'DIFFERS'})")
    total = count(cfg)
    print(f"parameters: {total:,} "
          f"({'as stated' if total == EXPECTED else f'NOT {EXPECTED:,}'})"
          f" = {2 * total / 1e9:.2f} GB in bf16")
    return int(total != EXPECTED)


if __name__ == "__main__":
    raise SystemExit(main())
