"""Operations and bytes that the algorithm needs, from shapes alone.

FLOPs count two per multiply-add of the matrix products (softmax and
norms are left out: they do not run on the matrix unit).  Bytes count
each operand read once from HBM and each result written once, at its
stored width.  A roofline's least time is the larger of FLOPs over the
chip's bf16 peak and bytes over its HBM bandwidth; a share of it is that
least time over the measured time, and says which bound it is.

Counts are of *useful* work: the live keys of each slot, the valid rows
of a chunk, the LM head only where a token is produced.  What an
implementation does beyond that (padding, skipped blocks it still walks,
logits of a chunk that produces no token) shows as a lower share.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def _geom(a: Dict) -> Tuple[int, int, int]:
    Hk = a["n_kv_heads"]
    return Hk, a["n_heads"] // Hk, a["head_dim"]


def paged_decode(a: Dict, contexts: Iterable[int], kv_bytes: int = 2,
                 act_bytes: int = 2) -> Tuple[float, float]:
    """One call of the paged decode kernel (one layer, one token per
    slot); ``contexts`` are the keys each active slot attends, the new
    one included.  Returns ``(flops, bytes)``."""
    Hk, G, d = _geom(a)
    ctx = list(contexts)
    keys = sum(ctx)
    flops = 4.0 * Hk * G * d * keys
    nbytes = 2.0 * Hk * d * kv_bytes * keys + 2.0 * len(ctx) * Hk * G * d \
        * act_bytes
    return flops, nbytes


def paged_prefill(a: Dict, start: int, valid: int, kv_bytes: int = 2,
                  act_bytes: int = 2) -> Tuple[float, float]:
    """One call of the paged prefill kernel: ``valid`` queries of one
    slot at absolute positions ``start ..``, each attending the keys up to
    its own position."""
    Hk, G, d = _geom(a)
    pairs = valid * start + valid * (valid + 1) / 2.0
    flops = 4.0 * Hk * G * d * pairs
    nbytes = 2.0 * Hk * d * kv_bytes * (start + valid) \
        + 2.0 * valid * Hk * G * d * act_bytes
    return flops, nbytes


def layer_matmul_params(a: Dict) -> int:
    d, H, Hk, hd, f = (a["d_model"], a["n_heads"], a["n_kv_heads"],
                       a["head_dim"], a["d_ff"])
    return d * (H + 2 * Hk) * hd + H * hd * d + 3 * d * f


def _kv_token_bytes(a: Dict, kv_bytes: int) -> float:
    return 2.0 * a["n_kv_heads"] * a["head_dim"] * kv_bytes


def prefill_chunk(a: Dict, start: int, valid: int, last: bool,
                  kv_bytes: int = 2, w_bytes: int = 2) -> Tuple[float, float]:
    """One chunk of one prompt through the whole model: every layer's
    weights and the slot's keys read, the chunk's keys written, and the
    LM head for one row when the chunk produces the first token."""
    L, d, V = a["n_layers"], a["d_model"], a["vocab_size"]
    P = layer_matmul_params(a)
    af, ab = paged_prefill(a, start, valid, kv_bytes)
    flops = L * (2.0 * P * valid + af)
    nbytes = L * (P * w_bytes + ab + _kv_token_bytes(a, kv_bytes) * valid) \
        + valid * d * w_bytes
    if last:
        flops += 2.0 * d * V
        nbytes += d * V * w_bytes
    return flops, nbytes


def decode_token_step(a: Dict, contexts: Iterable[int], kv_bytes: int = 2,
                      w_bytes: int = 2) -> Tuple[float, float]:
    """One token for each active slot through the whole model."""
    L, d, V = a["n_layers"], a["d_model"], a["vocab_size"]
    P = layer_matmul_params(a)
    ctx = list(contexts)
    S = len(ctx)
    af, ab = paged_decode(a, ctx, kv_bytes)
    flops = L * (2.0 * P * S + af) + 2.0 * d * V * S
    nbytes = L * (P * w_bytes + ab + _kv_token_bytes(a, kv_bytes) * S) \
        + d * V * w_bytes + S * d * w_bytes
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """Least seconds on the chip and the bound that sets them."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def decode_block_contexts(slots, n_steps: int):
    """Per token step of a fused decode block, the keys each active slot
    attends.  ``slots`` holds ``(rid, past_len, budget)`` at block start:
    a slot decodes while its budget lasts, one key more each step."""
    return [[past + t + 1 for _, past, budget in slots if t < budget]
            for t in range(n_steps)]
