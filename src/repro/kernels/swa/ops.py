"""jit'd wrapper for the SWA kernel in the model's (B, S, H, dh) layout."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.swa.kernel import swa_attention_pallas


def swa_attention(q, k, v, *, window: int, causal: bool = True,
                  block_q: int = 128, block_k: int = 128,
                  interpret: bool | None = None):
    """q (B,S,H,dh), k/v (B,S,G,dh) -> (B,S,H,dh).  ``interpret=None``
    compiles the kernel on a TPU and interprets it anywhere else."""
    assert causal, "SWA kernel is causal-only"
    interp = (jax.default_backend() != "tpu" if interpret is None
              else interpret)
    s = q.shape[1]
    bq = min(block_q, s)
    bk = min(block_k, s, window)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = swa_attention_pallas(qt, kt, vt, window=window,
                               block_q=bq, block_k=bk, interpret=interp)
    return out.transpose(0, 2, 1, 3)
