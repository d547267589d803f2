"""tools/config4_hbm_probe.py mechanics at small width.

The tool's value is the real-width record (V=512k, produced by a tool
run); here we pin that the
compile-only pipeline works on the virtual mesh and that the
per-device buffer accounting matches the sharding arithmetic the
architecture doc argues from.
"""

import os
import sys

sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "tools"),
)

import config4_hbm_probe


def test_probe_compiles_both_plans_and_accounts_buffers():
    v, b, k = 2048, 32, 4
    rec = config4_hbm_probe.probe(v=v, b=b, k=k, var_max_iters=3)
    vs = rec["plans"]["vocab_sharded_dense"]
    dp = rec["plans"]["data_parallel_dense"]
    # DP keeps the full [b, v] corpus shard (+ replicated beta) resident
    # per device; vocab sharding cuts the per-device argument bytes to
    # ~1/n_devices of that.  These are XLA buffer-assignment numbers,
    # not hand arithmetic.
    corpus_bytes = b * v * 4
    assert dp["argument_bytes"] >= corpus_bytes
    assert vs["argument_bytes"] < corpus_bytes / 4
    assert vs["argument_bytes"] < dp["argument_bytes"]
    for plan in (vs, dp):
        assert plan["peak_bytes"] > 0
        assert isinstance(plan["fits_hbm"], bool)
    # At toy width everything fits, so the claim gate must hold.
    assert rec["claim_verified"] is True
    assert rec["b_per_chip"] == b and rec["v"] == v
