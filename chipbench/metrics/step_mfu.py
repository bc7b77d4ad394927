"""Whole step: the useful work of every engine step in the window, each
piece priced at its roofline least time, over the host wall time of
those steps, in %.

Useful work is each prompt chunk's valid tokens at their past length,
and each decoded token at its slot's live context, as the
configuration's architecture counts them (its ``prefill_chunk`` /
``decode_token_step``).  Each piece is bound by compute or by memory,
whichever is slower on the chip; at these sizes decode is memory bound
(weights and live KV read per token step) and prefill chunks are
compute bound, so the figure is a share of the roofline and not of the
FLOP peak alone.  The formula is the same whatever implements the
step."""
from chipbench.harness import roofline as R


def step_least_time(run, st):
    t = 0.0
    n_steps = run.engine["decode_block"]
    arch = run.architecture
    for ev in run.events[st.ev_lo:st.ev_hi]:
        if ev.kind == "prefill_chunk":
            t += R.least_time(*arch.prefill_chunk(
                run.arch, ev.past_len, ev.chunk, ev.last, run.kv_bytes),
                run.peaks)[0]
        elif ev.kind == "decode_block":
            for ctx in R.decode_block_contexts(ev.slots, n_steps):
                if ctx:
                    t += R.least_time(*arch.decode_token_step(
                        run.arch, ctx, run.kv_bytes), run.peaks)[0]
    return t


def read(run):
    steps = run.window_steps()
    wall = sum(st.t1 - st.t0 for st in steps)
    least = sum(step_least_time(run, st) for st in steps)
    if wall <= 0 or least <= 0:
        return None
    return 100.0 * least / wall
