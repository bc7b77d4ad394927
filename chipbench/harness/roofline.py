"""Operations and bytes that the algorithm needs, from shapes alone.

FLOPs count two per multiply-add of the matrix products (softmax and
norms are left out: they do not run on the matrix unit).  Bytes count
each operand read once from HBM and each result written once, at its
stored width.  A roofline's least time is the larger of FLOPs over the
chip's bf16 peak and bytes over its HBM bandwidth; a share of it is that
least time over the measured time, and says which bound it is.

Counts are of *useful* work: the live keys of each slot, the valid rows
of a chunk, the LM head only where a token is produced, the experts a
token is routed to.  What an implementation does beyond that (padding,
skipped blocks it still walks, logits of a chunk that produces no token)
shows as a lower share.

The counts themselves depend on the architecture, and each architecture
module (``chipbench/arch``) gives them: ``prefill_chunk``,
``decode_token_step`` and ``kernel_calls``.  This module prices them.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def least_time(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """Least seconds on the chip and the bound that sets them."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def calls_least_time(calls: Iterable[Tuple[int, float, float]],
                     peaks: Dict) -> float:
    """Least seconds of kernel calls given as ``[(calls, flops, bytes)]``
    with the counts of one call (``kernel_calls`` of an architecture)."""
    return sum(n * least_time(f, b, peaks)[0] for n, f, b in calls)


def decode_block_contexts(slots, n_steps: int):
    """Per token step of a fused decode block, the keys each active slot
    attends.  ``slots`` holds ``(rid, past_len, budget)`` at block start:
    a slot decodes while its budget lasts, one key more each step."""
    return [[past + t + 1 for _, past, budget in slots if t < budget]
            for t in range(n_steps)]
