"""place_stack_put_s: the part of a fit's placement under the spans
`fit.stack.put`: per shape group handing the host stack to the runtime
(`jnp.asarray` on one device, `device_put` with the documents' sharding under
a mesh; their `.counts` say `bytes` and `shards`).  With
`place_stack_copy_s` it splits `place_transfer_s`.  Clipped to the placement;
mean over the traced fits."""

from benchmarks.jobs import fit_tail


def read(ctx):
    return fit_tail.mean(ctx, ("fit.stack.put",), "place")
