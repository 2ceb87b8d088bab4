"""Rotary position embedding over the leading part of a head, with YaRN
frequencies.

:class:`Rotary` names one attention kind's rotation: the base ``theta``,
how many of a head's leading dimensions turn (``dims``; the rest pass
through unrotated), and, where ``factor`` exceeds 1, YaRN's stretch of
the slow frequencies (Peng et al., 2023, "YaRN: Efficient Context Window
Extension of Large Language Models"): frequencies that turn fewer than
``beta_slow`` times over ``original_len`` positions are divided by
``factor``, those that turn more than ``beta_fast`` times are kept, and a
linear ramp over the dimension index joins the two. cos and sin are then
multiplied by ``attention_factor``. Pairs are rotate-half pairs
``(i, i + dims/2)`` of the rotated part.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

import jax.numpy as jnp


class Rotary(NamedTuple):
    theta: float
    dims: int  # leading dimensions of a head that turn
    factor: float = 1.0  # YaRN's stretch; 1 is the plain rotation
    original_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


def inv_freq(r: Rotary) -> np.ndarray:
    """[dims/2] float32: the angle per position of each rotated pair."""
    half = r.dims // 2
    base = 1.0 / r.theta ** (np.arange(0, r.dims, 2, dtype=np.float64) / r.dims)
    if r.factor <= 1.0:
        return base.astype(np.float32)

    def dim_of(turns):  # the dimension index whose pair turns ``turns`` times
        return (r.dims * math.log(r.original_len / (turns * 2 * math.pi))
                / (2 * math.log(r.theta)))

    lo = max(math.floor(dim_of(r.beta_fast)), 0)
    hi = min(math.ceil(dim_of(r.beta_slow)), r.dims - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - lo) / (hi - lo), 0, 1)
    keep = 1.0 - ramp  # 1: extrapolate (the fast pairs), 0: interpolate
    return (base / r.factor * (1 - keep) + base * keep).astype(np.float32)


def apply(x, positions, r: Rotary):
    """``x`` [B, L, H, Dh] turned at absolute ``positions`` [B, L] (or
    [L]); float32 arithmetic, ``x``'s dtype out."""
    half = r.dims // 2
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq(r))
    cos = (jnp.cos(ang) * r.attention_factor)[..., None, :]
    sin = (jnp.sin(ang) * r.attention_factor)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:r.dims]
    turned = [x1 * cos - x2 * sin, x1 * sin + x2 * cos]
    if r.dims < x.shape[-1]:
        turned.append(x32[..., r.dims:])
    return jnp.concatenate(turned, axis=-1).astype(x.dtype)
