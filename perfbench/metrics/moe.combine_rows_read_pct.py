"""Of the rows of the held experts' second product that the combine
could read, the share it read: 100 x `moe_combine_rows` over
`moe_combine_pairs` (the step's (token, choice) pairs, `slots x chunk x
top_k` an expert block), both counted by the step on the device, the
run's steps together. `_moe_combine_kernel` reads the rows of the
pairs whose expert is held here and no other; the jnp form un-sorts
every pair's, so 100 says the kernel did not engage. None where the
program counts no pairs (a program without the counters)."""

ROWS, PAIRS = "moe_combine_rows", "moe_combine_pairs"


def read(run):
    pairs = run.counters.get(PAIRS, 0)
    if pairs <= 0:
        return None
    return 100.0 * run.counters.get(ROWS, 0) / pairs
