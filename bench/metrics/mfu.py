"""Device, whole step: the FLOPs that prefill and decode of the tokens
processed in the window require, as a percentage of the chip's bf16 peak
over the window. Decode counts occupied rows only; the output head counts
on decode tokens and on each prompt's last token."""
from bench import work

UNIT = "%"
LAYER = "device"
MOVES = "out_tok_s"


def read(run):
    if run.peak is None:
        return None
    flops = 0.0
    for launch in run.launches:
        for s in range(launch["n_exec"]):
            for _, cached in launch["rows"]:
                flops += work.decode_flops(run.model, cached + s + 1)
    for p in run.prefills:
        flops += work.prefill_flops(run.model, p["n_tokens"], p["n_ctx"],
                                    p["last"])
    return 100.0 * flops / (run.window_s * run.peak["bf16_flops_per_s"])
