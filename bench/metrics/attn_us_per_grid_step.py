"""Kernels (``kernels/packed_attention.py``
``fused_packed_attention_paged``): device time in the window of the
operations that carry the paged attention kernel's name, per grid step
the scheduler launched it with (``SlotStats.attn_grid_steps``). With
``attn_dead_step_share`` it tells a kernel change that runs fewer steps
from one that makes each step cheaper."""
from bench import trace as tr

UNIT = "us"
LAYER = "kernels"
MOVES = "out_tok_s"
# the kernel's Mosaic custom call is named for it
# (``packed_attention.PAGED_KERNEL``)
KERNELS = ("%packkv_paged_attention* (tpu_custom_call)",)


def read(run):
    if run.trace is None or "attn_grid_steps" not in run.stats1:
        return None
    grid = run.delta("attn_grid_steps")
    lo, hi = run.window_ns
    ns = tr.op_ns(run.trace, KERNELS, lo, hi)
    return ns * 1e-3 / grid if ns and grid else None
