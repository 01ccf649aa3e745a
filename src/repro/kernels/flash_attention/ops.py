"""Public dispatch: pads sequences (at the tail) to block multiples with
explicit real-length masking.  `prefer="auto"` runs the compiled Pallas
kernel on TPU and the jnp reference elsewhere; "pallas" forces the
kernel (interpret off-TPU), "ref" forces the oracle — same contract as
`segment_sum.ops`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref



def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    prefer: str = "auto",
) -> jax.Array:
    """Causal GQA attention, queries end-aligned with keys (ref.py semantics)."""
    if prefer == "ref" or (prefer == "auto" and not on_tpu()):
        return attention_ref(q, k, v, causal=causal)
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    bq = min(block_q, max(8, Sq))
    bk = min(block_k, max(8, Skv))
    sq_pad = -(-Sq // bq) * bq
    sk_pad = -(-Skv // bk) * bk
    if sq_pad != Sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - Sq), (0, 0), (0, 0)))
    if sk_pad != Skv:
        k = jnp.pad(k, ((0, 0), (0, sk_pad - Skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad - Skv), (0, 0), (0, 0)))
    out = flash_attention_pallas(
        q, k, v, causal=causal, q_offset=Skv - Sq, kv_len=Skv,
        block_q=bq, block_k=bk, interpret=not on_tpu(),
    )
    return out[:, :Sq]
