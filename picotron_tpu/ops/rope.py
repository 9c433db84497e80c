"""Rotary position embeddings (GPT-NeoX / HF-Llama rotate_half convention).

Numerics spec from the reference (picotron/model.py:15-30): inverse frequencies
computed in float32, angle table cos/sin(pos * theta) tiled to head_dim
(torch ``.repeat(1, 2)`` = concatenation), cast to compute dtype once; applied
as ``x * cos + rotate_half(x) * sin`` with rotate_half = [-x2, x1]. The
reference fuses this with a CUDA kernel when FLASH_ATTEN=1 (model.py:130-136);
on TPU the mul/add chain fuses into the surrounding matmuls under XLA, so no
Pallas kernel is needed for parity.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def precompute_rope(seq_length: int, head_dim: int, base: float, dtype,
                    scaling: dict | None = None) -> tuple:
    """Return (cos, sin), each [seq_length, head_dim], computed in float64/32
    on host for stable numerics (reference computes on CPU fp32, model.py:23).
    ``scaling``: a YaRN group, whose blended frequencies (``yarn_inv_freq``)
    take the plain ones' place."""
    assert head_dim % 2 == 0
    if scaling is not None:
        inv_freq = yarn_inv_freq(head_dim, base, scaling)
    else:
        inv_freq = 1.0 / (base ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    pos = np.arange(seq_length, dtype=np.float64)[:, None]  # [S, 1]
    angles = pos * inv_freq[None, :]  # [S, head_dim/2]
    cos = np.concatenate([np.cos(angles), np.cos(angles)], axis=-1)
    sin = np.concatenate([np.sin(angles), np.sin(angles)], axis=-1)
    return jnp.asarray(cos, dtype=dtype), jnp.asarray(sin, dtype=dtype)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: [batch, seq, heads, head_dim]; cos/sin: [seq, head_dim] shared
    across the batch (training), or [batch, seq, head_dim] per-sequence
    tables (KV-cache decode, where each slot sits at its own position —
    see ``rope_at_positions``)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    if cos.ndim == 3:
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    else:
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
    return x * c + rotated * s


def apply_rope_leading(x: jnp.ndarray, cos: jnp.ndarray,
                       sin: jnp.ndarray) -> jnp.ndarray:
    """``apply_rope`` on the leading ``cos.shape[-1]`` dimensions of every
    head (halves of that many paired), the others untouched: a partial
    rotation (models/mimo_v2.py: 64 of a head's 192)."""
    rot = cos.shape[-1]
    if rot == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate(
        [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)


def rope_at_positions(cos: jnp.ndarray, sin: jnp.ndarray,
                      pos: jnp.ndarray) -> tuple:
    """Gather per-sequence angle rows for decode-at-offset: ``pos`` is [B]
    (one new token per sequence) or [B, S]; returns [B, S, head_dim] tables
    that ``apply_rope`` broadcasts over heads. Out-of-table positions clamp
    to the last row (callers bound generation by max_seq_len)."""
    if pos.ndim == 1:
        pos = pos[:, None]
    pos = jnp.clip(pos, 0, cos.shape[0] - 1)
    return jnp.take(cos, pos, axis=0), jnp.take(sin, pos, axis=0)


# --------------------------------------------------------------------------- #
# M-RoPE (models/keye_vl2.py): three position streams, a section of pairs each
# --------------------------------------------------------------------------- #


def mrope_rows(cos: jnp.ndarray, sin: jnp.ndarray, section) -> tuple:
    """M-RoPE's angle rows [B, S, D] from each position stream's own rows
    ``cos``/``sin`` [3, B, S, D] (temporal, height, width; the plain table's
    rows at that stream's positions, halves tiled): pair ``i`` of a head's
    ``D / 2`` takes the stream that owns its section, ``section`` the pairs
    each stream owns in that order (Qwen2-VL's ``mrope_section``, which sums
    to ``D / 2``). With equal streams the rows are the plain table's, bit
    for bit."""
    half = cos.shape[-1] // 2
    if sum(section) != half or len(section) != 3:
        raise ValueError(f"mrope_section {list(section)} must give the "
                         f"{half} pairs of a head to three streams")
    owner = np.tile(np.repeat(np.arange(3), section), 2)  # a column's stream

    def pick(rows):
        return jnp.where(owner == 0, rows[0],
                         jnp.where(owner == 1, rows[1], rows[2]))

    return pick(cos), pick(sin)


def mrope_at_positions(cos: jnp.ndarray, sin: jnp.ndarray, pos: jnp.ndarray,
                       section) -> tuple:
    """M-RoPE's angle rows [B, S, D] for ``pos`` [3, B, S] (a token's
    temporal, height and width position; a text token's three are equal)
    out of the plain tables ``cos``/``sin`` [T, D]: each stream's rows
    (``rope_at_positions``), then ``mrope_rows``."""
    n, B, S = pos.shape
    c, s = rope_at_positions(cos, sin, pos.reshape(n * B, S))
    return mrope_rows(c.reshape(n, B, S, -1), s.reshape(n, B, S, -1),
                      section)


# --------------------------------------------------------------------------- #
# YaRN (models/deepseek_v32.py): a frequency blend and the softmax's mscale
# --------------------------------------------------------------------------- #


def yarn_inv_freq(dim: int, base: float, scaling: dict) -> np.ndarray:
    """The ``dim/2`` inverse frequencies of a YaRN-scaled RoPE (Peng et al.
    2023, as DeepSeek-V3's published code computes them): each is a blend of
    ``base^(-2i/dim)`` (kept: the pairs that turn more than ``beta_fast``
    times over the original window) and that over ``factor`` (the pairs
    that turn less than ``beta_slow`` times), by a linear ramp over the
    pair index between the two correction dims."""
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))),
               dim - 1)
    if low == high:
        high += 0.001  # the published code's guard against a zero ramp
    plain = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def yarn_mscale(scaling: dict) -> float:
    """``0.1 * mscale_all_dim * ln(factor) + 1``: the attention's softmax
    scale is multiplied by its square when the window passes the original
    one (the tables themselves stay unscaled: ``mscale == mscale_all_dim``
    in the published configuration)."""
    factor = float(scaling["factor"])
    if factor <= 1.0:
        return 1.0
    return 0.1 * float(scaling.get("mscale_all_dim", 1.0)) \
        * math.log(factor) + 1.0


def apply_rope_interleaved(x: jnp.ndarray, cos: jnp.ndarray,
                           sin: jnp.ndarray) -> jnp.ndarray:
    """RoPE on adjacent pairs ``(x[2i], x[2i+1])``, the pairing DeepSeek's
    published attention uses (``apply_rope`` pairs ``(x[i], x[i + D/2])``).
    x: [batch, seq, heads, D]; cos/sin: per-sequence tables [batch, seq, D]
    in the tiled layout, of which the first half holds the D/2 angles."""
    half = x.shape[-1] // 2
    c = cos[:, :, None, :half]
    s = sin[:, :, None, :half]
    pairs = x.reshape(*x.shape[:-1], half, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * c - b * s, a * s + b * c], axis=-1)
    return out.reshape(x.shape)
