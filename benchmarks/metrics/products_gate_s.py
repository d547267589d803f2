"""products_gate_s: the seconds of a traced fit's store gate, the span
`fit.plan.gate`, on a program whose gate also answers whether a densified
cell of the day passes 256, the question that keys the products at every
precision (`.counts`: `accurate`; at f32 one pass over the Corpus' counts,
every fit).  `place_gate_s`'s reading (clipped to the placement, mean over
the traced fits), given only where the gate's counts carry `accurate`: a
program without the decision gives nothing.
"""

from __future__ import annotations

from benchmarks.metrics import place_gate_s


def read(ctx):
    if not any(name == place_gate_s.GATE + ".counts" and "accurate" in stats
               for name, _, _, stats, _ in place_gate_s.spans(ctx)):
        return None
    return place_gate_s.read(ctx)
