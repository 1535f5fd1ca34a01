"""int8 gradient compression with error feedback — the DP all-reduce
bandwidth trick for the multi-pod mesh.

The ``pod`` axis rides the slow inter-pod links; compressing the gradient
all-reduce 4x (bf16 -> int8) cuts the dominant multi-pod collective term
(see EXPERIMENTS.md §Perf).  Error feedback (residual accumulation)
keeps SGD/Adam convergence: e_{t+1} = g_t + e_t - Q(g_t + e_t).

``compressed_psum`` is the shard_map building block; pjit programs use
``compress_decompress`` around the autodiff gradient (quantization is
simulated identically — the wire format is what the HLO all-reduce
operand dtype would be).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["compress_state_init", "compress_decompress", "compressed_psum"]


def compress_state_init(params):
    """Error-feedback residuals, one per parameter leaf."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compress_decompress(grads, residuals):
    """Quantize grad+residual to int8, return (dequantized, new_residuals)."""
    def one(g, e):
        x = g.astype(jnp.float32) + e
        q, scale = _quantize(x)
        deq = q.astype(jnp.float32) * scale
        return deq.astype(g.dtype), x - deq

    flat = jax.tree.map(one, grads, residuals)
    deq = jax.tree.map(lambda t: t[0], flat,
                       is_leaf=lambda t: isinstance(t, tuple))
    res = jax.tree.map(lambda t: t[1], flat,
                       is_leaf=lambda t: isinstance(t, tuple))
    return deq, res


def compressed_psum(x: jnp.ndarray, residual: jnp.ndarray, axis_name: str):
    """int8 all-reduce with error feedback (shard_map building block).

    The int8 operand is what crosses the links; the sum is widened
    locally.  Returns (mean-reduced value, new residual)."""
    t = jax.lax.axis_size(axis_name)
    val = x.astype(jnp.float32) + residual
    q, scale = _quantize(val)
    # wire: int8 payload (+ one f32 scale each) — each contribution is
    # dequantized with ITS OWN scale, so the reduce is exact up to the
    # local quantization error (summing raw int8 under a mean scale
    # would distort whenever per-device scales differ).
    all_q = lax.all_gather(q, axis_name)            # (t, ...) int8 wire
    all_scale = lax.all_gather(scale, axis_name)    # (t,) f32
    shape = (t,) + (1,) * q.ndim
    approx = jnp.sum(all_q.astype(jnp.float32)
                     * all_scale.reshape(shape), axis=0)
    new_residual = val - q.astype(jnp.float32) * scale
    return (approx / t).astype(x.dtype), new_residual
