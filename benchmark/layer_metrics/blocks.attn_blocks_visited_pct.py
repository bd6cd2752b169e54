"""Blocks of the attention kernels' forward grid that hold a pair in the mask,
as a share of the grid's blocks, over a step's three forwards and all the
attention layers: the fused calls' own counters (``StepMetrics.attention``,
kept by ``drivers/learner_feed_collected.py``, which prints them by layer
kind: the window's layers read under the full ones).  None from a run whose
calls count none."""


def read(r):
    c = r.counters
    total = sum(v for k, v in c.items() if k.startswith("attention_blocks_total_"))
    visited = sum(v for k, v in c.items() if k.startswith("attention_blocks_visited_"))
    return visited / total * 100.0 if total else None
