"""Public attention op: Pallas flash kernel on TPU, jnp oracle elsewhere."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..backend import interpret_requested, on_tpu
from .flash import flash_attention_pallas
from .ref import mha_ref


def mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    kv_len: Optional[jax.Array] = None,
    force_interpret: bool = False,
) -> jax.Array:
    """Causal GQA attention.  q: (B,H,Sq,D), k/v: (B,KVH,Sk,D).

    The Pallas path requires static shapes divisible by the 128-tile and no
    ragged kv_len (decode paths with ragged caches use the oracle, which XLA
    fuses well for q_len == 1).
    """
    interpret = interpret_requested(force_interpret)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    usable = (
        (on_tpu() or interpret)
        and kv_len is None
        and sq % 128 == 0
        and sk % 128 == 0
        and d in (64, 128, 256)
    )
    if not usable:
        return mha_ref(q, k, v, causal=causal, scale=scale, kv_len=kv_len)
    return flash_attention_pallas(
        q, k, v, causal=causal, scale=scale, interpret=interpret
    )
