"""Scheduler: mean number of active slots per fused decode block
(``decode_block`` trace events of the engine) over the window."""


def read(run):
    blocks = run.window_events("decode_block")
    if not blocks:
        return None
    return sum(len(ev.slots) for ev in blocks) / len(blocks)
