"""Operations and bytes of algorithms, from their shapes: the arithmetic
that the configurations' references share. Which of it is one
configuration's step is that configuration's to say
(``references/<config>.py``: ``train_flops``, ``attention_work``); a
per-layer metric only divides.

These count the ALGORITHM's work, whatever implements it: matmuls only, no
credit for anything recomputed. A change to the program cannot move them.
"""
from __future__ import annotations


def lm_forward_flops_per_token(d, L, ff, V, T):
    """One token's forward pass through a GPT-2 style decoder (full MHA,
    one up and one down projection in the MLP) at sequence length T: a
    causal query at position t sees t + 1 keys, on average (T + 1) / 2.
    qkv 6d^2, out 2d^2, MLP 4*d*ff a layer; logits 2dV; scores and
    probs*V 4*d a key."""
    return L * (8 * d * d + 4 * d * ff) + 2 * d * V + L * 4 * d * (T + 1) / 2.0


def lm_train_flops_per_token(d, L, ff, V, T):
    """Forward and backward (twice the forward) of one token at sequence
    length T. ``bench_lm.model_flops_per_token`` (the Megatron/PaLM
    convention) counts the masked half of attention too, 6*L*d*(T - 1) more
    a token; the benchmark's ``mfu`` counts the causal work only, so it
    cannot exceed what the chip executed."""
    return 3 * lm_forward_flops_per_token(d, L, ff, V, T)


def causal_attention_work(B, H, T, D, itemsize=2):
    """Causal self-attention over [B, H, T, D], forward and backward, as
    an algorithm: FLOPs (forward 2 matmuls, backward 4, each 2*T*(T+1)/2*D
    a head) and the least bytes (forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv)."""
    pairs = T * (T + 1) / 2.0
    flops = B * H * 6 * 2 * pairs * D
    nbytes = B * H * T * D * itemsize * (4 + 8)
    return flops, nbytes


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which bound gives it."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("compute" if by_flops >= by_bytes
                                     else "memory")
