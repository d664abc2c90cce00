"""Cells cut to a size the CPU runs in seconds, for the harness's tests.

Only the tests use this: every width is shrunk (a real cell never is),
the traffic is short and the window lasts a second. The limit on
``max_logit_gap`` sits between the smoke program's readings (below 0.01
on the CPU) and the fp8 control's (0.04-0.07).
"""
from __future__ import annotations

import dataclasses

from bench import cells

SIZES = {"n_layers": 4, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 32, "d_ff": 256, "vocab_size": 512, "ssm_state": 16,
         "ssm_headdim": 16, "ssm_chunk": 32}
TRAFFIC = {"prompt": {"median": 24, "sigma": 0.8, "min": 16, "max": 64,
                      "grid": 16},
           "output": {"median": 6, "sigma": 0.6, "min": 3, "max": 12},
           "rows": 4, "cache_len": 96, "page_len": 16, "check_requests": 3}


def smoke_cell(name: str, **traffic) -> cells.Cell:
    cell = cells.load_cell(name)
    config = cells.family(cell.config).smoke(dict(
        cell.config, **{k: v for k, v in SIZES.items() if k in cell.config}))
    mix = dict(cell.traffic, **dict(TRAFFIC, **traffic))
    if mix["arrivals"]["process"] == "poisson":
        mix["arrivals"] = {"process": "poisson", "rate_per_s": 20.0}
    else:
        mix["arrivals"] = {"process": "backlog", "requests": 24}
    return dataclasses.replace(cell, config=config, traffic=mix,
                               limits={"max_logit_gap": 0.02})
