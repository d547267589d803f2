"""place_stack_copy_s: the part of a fit's placement under the spans
`fit.stack.copy`: per shape group `np.stack` of the batches' arrays and the
`astype` of counts and masks into a fresh host stack (their `.counts` say
`bytes`).  With `place_stack_put_s` it splits
`place_transfer_s`.  Clipped to the placement; mean over the traced fits."""

from benchmarks.jobs import fit_tail


def read(ctx):
    return fit_tail.mean(ctx, ("fit.stack.copy",), "place")
