"""What the fit job's trace calls things.  The program gives its kernels no
`name=` and has no `named_scope` yet (PERF.md, list for the tracing issue),
so these patterns match what the v5e trace of PR 26 shows; a reader that
finds nothing returns nothing.

  EM program     the jitted chunk program of models/fused.py: an `XLA
                 Modules` event `jit_run_chunk_dispatch(...)`.
  E-step kernel  the Pallas dense E-step inside it: `XLA Ops` events
                 `%tpu_custom_call.<n> = ... kind=kCustom` (the only
                 Mosaic kernels in the chunk program).
"""

import re

EM_MODULE = re.compile(r"run_chunk")
ESTEP_KERNEL = re.compile(r"^%?tpu_custom_call")


def is_em_module(name: str) -> bool:
    return bool(EM_MODULE.search(name))


def em_programs(trace: dict, dev: int) -> list:
    """(start, end) of every EM chunk program on the device, in order."""
    return [(s, s + d) for n, s, d in trace["modules"].get(dev, [])
            if is_em_module(n)]


def per_fit(trace: dict, dev: int) -> list:
    """For every traced fit: (fit_start, fit_end, [EM programs in it])."""
    programs = em_programs(trace, dev)
    return [(lo, hi, [p for p in programs if lo <= p[0] < hi])
            for lo, hi in trace["fits"]]
