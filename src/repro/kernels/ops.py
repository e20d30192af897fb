"""jit'd public wrappers around the Pallas kernels.

* Interpret mode only where jax's default backend is the CPU
  (`core.backend.pallas_interpret`; the kernels TARGET TPU, interpret=True
  executes the kernel body in Python for correctness validation).
* Handle arbitrary-rank inputs by flattening leading dims and padding the
  last dim to tile multiples, so the optimizer / KV cache / checkpoint
  codecs can quantize any parameter tensor.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ..core.backend import pallas_interpret
from . import ref
from .dequant_matmul import dequant_matmul as _dequant_matmul_pallas
from .quantize_blockwise import (dequantize_blockwise_2d,
                                 quantize_blockwise_2d)
from .ref import DEFAULT_BLOCK


def _to_2d(x: jnp.ndarray, block: int):
    """Flatten to (M, N) with N a multiple of block; M padded to tile rows."""
    n = x.shape[-1]
    lead = 1
    for d in x.shape[:-1]:
        lead *= d
    flat = x.reshape(lead, n)
    pad_n = (-n) % block
    if pad_n:
        flat = jnp.pad(flat, ((0, 0), (0, pad_n)))
    return flat, lead, n


@functools.partial(jax.jit, static_argnames=("block", "use_pallas"))
def quantize_blockwise(x: jnp.ndarray, block: int = DEFAULT_BLOCK,
                       use_pallas: bool = True
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Any-rank blockwise int8 quantization.

    Returns (q int8, same shape as x; scales f32, shape
    x.shape[:-1] + (ceil(N/block),)).
    """
    n = x.shape[-1]
    nb = -(-n // block)
    if not use_pallas:
        return ref.quantize_blockwise(x, block)
    flat, lead, _ = _to_2d(x, block)
    # pick a row tile that divides the (padded) row count
    tile_m = 1
    for cand in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if lead % cand == 0:
            tile_m = cand
            break
    tile_n = flat.shape[1]
    for cand in (512, 256, 128):
        if flat.shape[1] % cand == 0 and cand % block == 0:
            tile_n = cand
            break
    q, s = quantize_blockwise_2d(flat, block, interpret=pallas_interpret(),
                                 tile_m=tile_m, tile_n=tile_n)
    q = q[:, :n].reshape(x.shape)
    s = s[:, :nb].reshape(x.shape[:-1] + (nb,))
    return q, s


@functools.partial(jax.jit,
                   static_argnames=("block", "dtype", "use_pallas"))
def dequantize_blockwise(q: jnp.ndarray, scales: jnp.ndarray,
                         block: int = DEFAULT_BLOCK, dtype=jnp.float32,
                         use_pallas: bool = True) -> jnp.ndarray:
    if not use_pallas:
        return ref.dequantize_blockwise(q, scales, block, dtype)
    n = q.shape[-1]
    nb = -(-n // block)
    flat, lead, _ = _to_2d(q, block)
    sflat = scales.reshape(lead, nb)
    pad_b = flat.shape[1] // block - nb
    if pad_b:
        sflat = jnp.pad(sflat, ((0, 0), (0, pad_b)))
    tile_m = 1
    for cand in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if lead % cand == 0:
            tile_m = cand
            break
    tile_n = flat.shape[1]
    for cand in (512, 256, 128):
        if flat.shape[1] % cand == 0 and cand % block == 0:
            tile_n = cand
            break
    out = dequantize_blockwise_2d(flat, sflat, block, dtype,
                                  interpret=pallas_interpret(),
                                  tile_m=tile_m, tile_n=tile_n)
    return out[:, :n].reshape(q.shape)


@functools.partial(jax.jit, static_argnames=("block", "use_pallas"))
def dequant_matmul(a: jnp.ndarray, qw: jnp.ndarray, scales: jnp.ndarray,
                   block: int = DEFAULT_BLOCK,
                   use_pallas: bool = True) -> jnp.ndarray:
    """a (M, K) @ dequant(qw (K, N)) with per-(K-block, N) scales."""
    if not use_pallas:
        return ref.dequant_matmul(a, qw, scales, block)
    m, k = a.shape
    _, n = qw.shape
    tile_m = 1
    for cand in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if m % cand == 0:
            tile_m = cand
            break
    tile_n = n
    for cand in (256, 128):
        if n % cand == 0:
            tile_n = cand
            break
    return _dequant_matmul_pallas(a, qw, scales, block,
                                  interpret=pallas_interpret(),
                                  tile_m=tile_m, tile_n=tile_n)
