"""A cell cut to a size the CPU runs in seconds, for tests and rehearsals.

Everything but the sizes stays as the cell has it: the same engine path,
policy, kernels (interpreted off the chip), loop type and reference. No
number from such a run is a device metric.
"""
from __future__ import annotations

import copy
import dataclasses

from . import spec

SIZES = {"num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
         "vocab_size": 512}
# 5 pages of 64 tokens for 2 slots of 256: admission waits for pages
ENGINE = {"capacity": 256, "max_batch": 2, "page_size": 64, "pool_pages": 5,
          "prefill_chunk_pages": 1, "decode_chunk": 4}
LENGTHS = {"prompt_len": {"values": [64, 128], "p": [0.5, 0.5]},
           "output_len": {"values": [8, 16], "p": [0.5, 0.5]}, "deck": 4}


def shrink(cell: spec.Cell) -> spec.Cell:
    """A copy of ``cell`` at smoke sizes."""
    cell = dataclasses.replace(cell, config=copy.deepcopy(cell.config),
                               traffic=copy.deepcopy(cell.traffic))
    cfg = cell.config
    cfg.update(SIZES)
    cfg["model"] = {v: cfg[k] for k, v in spec.MODEL_KEYS.items()}
    cfg["engine"].update(ENGINE)
    mix = cell.traffic
    mix.update(copy.deepcopy(LENGTHS))
    if mix["loop"] == "closed":
        mix["clients"] = ENGINE["max_batch"]
    else:
        mix.update(rate_per_s=2.0, warmup_s=1.0)
    return cell
