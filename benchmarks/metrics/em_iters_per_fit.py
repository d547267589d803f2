"""em_iters_per_fit: EM iterations to the convergence rule, as the fit
returned them (`LDAResult.em_iters`); repeats exactly for a seed."""


def read(ctx):
    return float(ctx["em_iters"])
