"""Model step (the engine's prefill dispatches): device time in the window
of the programs named for prefill, per thousand prompt tokens the
scheduler ran through them (``SlotStats.prefill_tokens``). Every such
program runs between decode launches, so each row waits for it."""
from bench import trace as tr

UNIT = "ms"
LAYER = "model step"
MOVES = "tpot_p90_ms"
# the engine's prefill programs (``Engine._lane_jit`` names): the fused
# insert, the chunk steps and fused final chunk, and the prefix-cache ones
PROGRAMS = ("jit_packkv_insert(*", "jit_packkv_chunk_step(*",
            "jit_packkv_chunk_final(*", "jit_packkv_insert_prefix(*",
            "jit_packkv_prefix_chunk(*")


def read(run):
    if run.trace is None or "prefill_tokens" not in run.stats1:
        return None
    tokens = run.delta("prefill_tokens")
    lo, hi = run.window_ns
    names = {m.name for m in run.trace.modules if tr.match(m.name, PROGRAMS)}
    ns = tr.module_ns(run.trace, names, lo, hi)
    return ns * 1e-6 / (tokens / 1000) if ns and tokens else None
