"""feedback_gate_s: the seconds of a traced fit's store gate, the span
`fit.plan.gate` (`dense_estep.corpus_store_dtype`), on the
analyst-feedback day: the ladder bfloat16 / int16 / float32, whose first
pass meets the feedback's raw counts over 256 (`.counts`:
`over_bf16_tokens`) and leaves undecided only the rows over int16's
32,767.  `place_gate_s`'s reading (clipped to the placement, mean over the
traced fits), given only where the gate's counts name the ladder's `rung`:
a program without the ladder (the parent of the integer store) gives
nothing.
"""

from __future__ import annotations

from benchmarks.metrics import place_gate_s


def read(ctx):
    if not any(name == place_gate_s.GATE + ".counts" and "rung" in stats
               for name, _, _, stats, _ in place_gate_s.spans(ctx)):
        return None
    return place_gate_s.read(ctx)
