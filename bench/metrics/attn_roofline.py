"""Kernels (``kernels/packed_attention.py``
``fused_packed_attention_paged``): the least time the chip could take for
the decode attention kernel's work in the window, as a percentage of the
time its launches took on the device.

The work is what PackKV decode attention must do for the live tokens of
each step: read every compressed token's pages and metadata (bytes per
token from the pool's shape), take the f32 query in and write the partial
output, and compute scores and weighted values. The least time is the
larger of bytes over peak bandwidth and operations over peak FLOP/s
(bytes bound it at these shapes). The bf16 residual is merged outside the
kernel and is not counted here."""
from bench import trace as tr
from bench import work

UNIT = "%"
LAYER = "kernels"
MOVES = "out_tok_s"
# the Pallas launches inside the decode programs: on this path the paged
# attention kernel is the only one (a Mosaic ``tpu_custom_call``)
KERNELS = ("* (tpu_custom_call)",)


def read(run):
    if run.trace is None or run.peak is None:
        return None
    lo, hi = run.window_ns
    ns = tr.op_ns(run.trace, KERNELS, lo, hi,
                  within=tr.waited_programs(run.trace, "bench.decode"))
    if not ns:
        return None
    nbytes = flops = 0.0
    for launch in run.launches:
        for s in range(launch["n_exec"]):
            for prompt, cached in launch["rows"]:
                n_comp, _ = work.packkv_counters(
                    prompt, cached + s + 1, run.block, run.residual,
                    run.capacity)
                nbytes += work.attn_kernel_bytes(run.model, n_comp,
                                                 run.comp_bytes_per_token)
                flops += work.attn_flops(run.model, n_comp)
    t_min = max(nbytes / run.peak["hbm_bytes_per_s"],
                flops / run.peak["bf16_flops_per_s"])
    return 100.0 * t_min / (ns * 1e-9)
