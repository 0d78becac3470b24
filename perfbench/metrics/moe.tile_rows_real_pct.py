"""Of the rows the held experts' grouped matmuls multiply, the share
that are real (token, choice) pairs: 100 x 2 x `moe_pairs{held=here}`
over `moe_gmm_tile_rows` (the rows of the 128-row tiles that
`_moe_gmm_kernel` visits, two products a block, counted by the step on
the device), the run's steps together. Low where a held expert sees
few pairs a step: a tile is multiplied whole however few of its rows
are some expert's. None on the `ragged_dot` route, which visits no
tile (the counter reads 0)."""

HERE, TILE_ROWS = "moe_pairs{held=here}", "moe_gmm_tile_rows"


def read(run):
    rows = run.counters.get(TILE_ROWS, 0)
    if HERE not in run.counters or rows <= 0:
        return None
    return 100.0 * 2 * run.counters[HERE] / rows
