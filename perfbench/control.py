"""The control of the correctness check: the plain reference put in the
program's place, computed in bfloat16, the precision below the float32
that the configurations state.  The check has to fail it.

For an ingest cell, the gaps between the float32 and the bfloat16
replays of the cell's chunks from the starting pool; for a read cell,
the gaps between the float32 and the bfloat16 answers on
``checked_per_kind`` requests of each kind against the cell's snapshot.
``perfbench/readings.py`` prints them on the chip at the cells' sizes;
the benchmark's runs never run this.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from perfbench import compare, deploy, figmn_ref, streams


def ingest_pools(config, seed: int, chunks: int) -> Tuple[dict, dict]:
    """(bfloat16 pool, float32 pool) after ``chunks`` chunks."""
    import jax.numpy as jnp
    dep = deploy.deployment(config, seed)
    chunk = int(config["chunk"])
    xs = [streams.ingest_chunk(dep.model, seed, chunk, i)
          for i in range(chunks)]
    pools = {}
    for dt in ("float32", "bfloat16"):
        p = figmn_ref.cast(dep.start_pool(), jnp.dtype(dt))
        for x in xs:
            p = figmn_ref.learn(p, x, dep.sigma, dep.threshold)
        pools[dt] = p
    return pools["bfloat16"], pools["float32"]


def ingest_gaps(config, seed: int, chunks: int) -> Dict[str, float]:
    dep = deploy.deployment(config, seed)
    low, ref = ingest_pools(config, seed, chunks)
    return compare.pool_gaps(low, ref, dep.start_means(),
                             chunks * int(config["chunk"]))


def read_gaps(config, traffic, seed: int) -> Dict[str, float]:
    import jax.numpy as jnp
    dep = deploy.deployment(config, seed)
    m = int(traffic["checked_per_kind"])
    rows = streams.draw(dep.model, seed, 2 * m, streams.READ)
    score_rows, label_rows = rows[:m], rows[m:, :dep.features]
    out = {}
    for dt in ("float32", "bfloat16"):
        p = figmn_ref.cast(dep.served_pool(traffic["snapshot"]),
                           jnp.dtype(dt))
        out[dt] = (np.asarray(figmn_ref.log_density(p, score_rows)),
                   np.asarray(figmn_ref.conditional_mean(
                       p, label_rows, n_out=dep.classes)))
        del p
    (s32, l32), (s16, l16) = out["float32"], out["bfloat16"]
    return compare.answer_gaps(s16, s32, l16, l32)
