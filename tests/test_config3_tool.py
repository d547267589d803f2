"""tools/config3_30day.py — the config-3 evidence tool — at tiny scale.

The tool's realistic-cardinality run (524,937 docs / 50,169 vocab /
K=50 to convergence on one CPU host, an hour-scale 16 GB job) is
ROADMAP B1's starting point; these tests keep its mechanics honest
without it:

- the power-law IP population actually scales document cardinality
  with the configured populations (the
  reference maps every active IP to a document,
  flow_pre_lda.scala:366-380);
- the --train stage records convergence and writes likelihood.dat
  beside --out;
- the emitted JSON record carries the contract fields downstream
  readers (captures README, evidence index) cite.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "config3_30day.py")


@pytest.fixture(scope="module")
def tool_record(tmp_path_factory):
    """One tiny end-to-end run shared by the assertions below."""
    out = tmp_path_factory.mktemp("config3") / "rec.json"
    env = dict(os.environ)
    proc = subprocess.run(
        [sys.executable, TOOL,
         "--events-per-day", "6000", "--days", "2",
         "--n-src", "3000", "--n-dst", "1500",
         "--ip-zipf-a", "1.2", "--n-svc-ports", "8",
         "--train", "--num-topics", "4", "--em-max-iters", "30",
         "--batch-size", "256",
         "--out", str(out)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(out) as f:
        rec = json.load(f)
    return rec, out


def test_record_contract_fields(tool_record):
    rec, _ = tool_record
    for field in ("gen_wall_s", "raw_gb", "pre_wall_s", "events",
                  "word_count_rows", "corpus_wall_s", "num_docs",
                  "vocab_size", "num_tokens", "train_wall_s",
                  "em_iters", "final_likelihood", "likelihood_rows",
                  "peak_rss_gb", "ip_zipf_a", "n_svc_ports"):
        assert field in rec, field
    # The pre stage drops one line as the header (reference
    # removeHeader parity, flow_pre_lda.scala:22-26) — the r05 capture
    # shows the same 149,999,999-of-150M shape.
    assert rec["days"] == 2 and 11_999 <= rec["events"] <= 12_000
    # Two documents per event (src and dst perspectives) bound docs by
    # events*2 and by the address population.
    assert 0 < rec["num_docs"] <= min(24_000, 3000 + 1500)


def test_power_law_population_scales_cardinality(tool_record):
    """12k events over a 4.5k-IP rank^-1.2 population must surface a
    large share of that population as documents — the fixed 6k-host
    round-4 pool gave 6,000 docs at 150M events, which is the failure
    mode this tool's realistic mode exists to rule out."""
    rec, _ = tool_record
    # With a=1.2 over 3k src ranks, 12k draws cover most of the head
    # and a meaningful tail: expect >1/3 of the population seen.
    assert rec["num_docs"] > 1500, rec["num_docs"]
    # The widened service mix yields a multi-hundred-word vocabulary
    # even at this scale (6 fixed services gave ~100).
    assert rec["vocab_size"] > 200, rec["vocab_size"]


def test_train_stage_converges_and_keeps_likelihood(tool_record):
    rec, out = tool_record
    assert rec["num_topics"] == 4
    assert 1 <= rec["em_iters"] <= 30
    assert rec["likelihood_rows"] == rec["em_iters"]
    ll_copy = str(out)[:-5] + "_likelihood.dat"
    assert os.path.exists(ll_copy)
    with open(ll_copy) as f:
        lls = [float(line.split()[0]) for line in f if line.strip()]
    assert len(lls) == rec["em_iters"]
    # Monotone non-decreasing likelihood (EM invariant).
    assert all(b >= a - 1e-6 * abs(a) for a, b in zip(lls, lls[1:]))
    assert rec["final_likelihood"] == pytest.approx(lls[-1], rel=1e-6)
