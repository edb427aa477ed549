"""Kernels (``kernels/packed_attention.py``
``fused_packed_attention_paged``): the share of the paged attention
kernel's grid steps in the window whose page lies past its row's live
compressed pages, every step of a free row included. Such a step re-reads
the row's last page and skips each tile, but still runs. The scheduler
counts both from its host mirror of each row's counters, with the
function the launch builds its grid from (``SlotStats.attn_grid_steps``,
``attn_dead_steps``)."""
UNIT = "fraction"
LAYER = "kernels"
MOVES = "out_tok_s"


def read(run):
    if "attn_grid_steps" not in run.stats1:  # a program without the count
        return None
    grid = run.delta("attn_grid_steps")
    return run.delta("attn_dead_steps") / grid if grid else None
