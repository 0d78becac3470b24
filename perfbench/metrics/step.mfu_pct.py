"""Operations the model requires for the useful tokens of the window's
steps (work `model_step`: projections, MLP, attention at each token's
context, head at each sampled token), per second of the window, over
the cell's chips times the bf16 peak."""

from perfbench import work


def read(run):
    if not run.steps:
        return None
    spec = work.load(run.root, "model_step")
    flops = sum(
        work.step_needs(spec, run.sizes,
                        [(r.n, r.ctx, r.emits) for r in s.rows])["flops"]
        for s in run.steps)
    span = run.steps[-1].t1 - run.steps[0].t0
    if span <= 0 or flops <= 0:
        return None
    return 100.0 * flops / span / (run.chips
                                   * run.peaks["bf16_flops_per_s"])
