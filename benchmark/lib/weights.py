"""Weights from ``--seed``: one jitted call, on the device, float32.

The tree is a plain dict with the leaf names and stacked-layer layout
the program's ``GPTLMParams`` uses (the cell drivers wrap it; the
reference reads it as it is). Made by the benchmark, so the reference
takes nothing the program made: it calls :func:`make` again after the
program's state is freed and gets the same bits.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

BLOCK_LEAVES = (
    "ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo",
    "ln2_scale", "ln2_bias", "w_up", "b_up", "w_down", "b_down",
)


def shapes(cfg: dict) -> dict:
    """Leaf name -> shape, for a configuration file's sizes."""
    d, n = cfg["n_embd"], cfg["n_layer"]
    return {
        "embed": (cfg["vocab_size"], d),
        "pos": (cfg["n_positions"], d),
        "blocks": {
            "ln1_scale": (n, d), "ln1_bias": (n, d),
            "wq": (n, d, d), "wk": (n, d, d), "wv": (n, d, d),
            "wo": (n, d, d),
            "ln2_scale": (n, d), "ln2_bias": (n, d),
            "w_up": (n, d, 4 * d), "b_up": (n, 4 * d),
            "w_down": (n, 4 * d, d), "b_down": (n, d),
        },
        "lnf_scale": (d,), "lnf_bias": (d,),
    }


def count(cfg: dict) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _make(vocab, n_pos, d, n, std, key):
    cfg = dict(vocab_size=vocab, n_positions=n_pos, n_embd=d, n_layer=n)
    shp = shapes(cfg)
    keys = iter(jax.random.split(key, 8))
    resid = std / math.sqrt(2 * n)

    def normal(shape, s):
        return s * jax.random.normal(next(keys), shape, jnp.float32)

    b = shp["blocks"]
    blocks = {
        name: (jnp.ones if name.endswith("scale") else jnp.zeros)(
            b[name], jnp.float32)
        for name in BLOCK_LEAVES if not name.startswith("w")
    }
    for name in ("wq", "wk", "wv", "w_up"):
        blocks[name] = normal(b[name], std)
    for name in ("wo", "w_down"):
        blocks[name] = normal(b[name], resid)
    return {
        "embed": normal(shp["embed"], std),
        "pos": normal(shp["pos"], std),
        "blocks": blocks,
        "lnf_scale": jnp.ones(shp["lnf_scale"], jnp.float32),
        "lnf_bias": jnp.zeros(shp["lnf_bias"], jnp.float32),
    }


def make(cfg: dict, seed: int) -> dict:
    """The configuration's weights for this seed (seeds above 2**31 are
    fine: the key is built from the two 32-bit halves)."""
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0xFFFFFFFF), int(seed) >> 32)
    return _make(
        cfg["vocab_size"], cfg["n_positions"], cfg["n_embd"], cfg["n_layer"],
        float(cfg["initializer_range"]), key,
    )
