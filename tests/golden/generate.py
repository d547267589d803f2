"""Golden-day fixture generator.

Writes the committed inputs (a tiny synthetic flow day, DNS day, and
whitelist) and the expected outputs for every stage-boundary file
contract (SURVEY.md §1: the layer interfaces ARE files).  The test
(tests/test_golden.py) recomputes the outputs from the committed inputs
and compares BYTES — any drift in featurization, corpus id assignment,
result formatting, or scoring emit fails loudly.

Run only to intentionally re-pin the contract after a deliberate
format change:  python tests/golden/generate.py
Then review the git diff of tests/golden/expected/ like any contract
change.

Training is deliberately NOT part of the fixture: float EM results vary
across backends, so the model (final.beta/final.gamma) is a committed
pseudo-random input, which also pins the beta/gamma file formats.
"""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", ".."))

from oni_ml_tpu.features import featurize_flow_file
from oni_ml_tpu.features.native_dns import featurize_dns_sources
from oni_ml_tpu.io import Corpus, formats
from oni_ml_tpu.scoring import ScoringModel, score_dns, score_flow

# Single source of truth for the fixture's scoring knobs: the test
# (tests/test_golden.py) imports these, so re-pinning with a changed
# threshold cannot desync generator and test.
FLOW_TOL = 0.005    # ~30th pct of the day: keeps a strict subset
DNS_TOL = 0.015
FLOW_FALLBACK = 0.05   # reference's unseen-IP rows (SURVEY §2.6)
DNS_FALLBACK = 0.1
K = 5


def write_inputs() -> None:
    rng = np.random.default_rng(1234)
    inp = os.path.join(HERE, "inputs")

    # -- flow day: 60 rows + header + edge cases ------------------------
    lines = ["tstart,year,month,day,hour,min,sec,tdur,sip,dip,sport,dport,"
             "proto,flag,fwd,stos,ipkt,ibyt,opkt,obyt,in,out,sas,das,dtos,"
             "dir,rip"]
    for i in range(60):
        c = ["x"] * 27
        c[1], c[2], c[3] = "2016", "1", "22"
        c[4] = str(int(rng.integers(0, 24)))
        c[5] = str(int(rng.integers(0, 60)))
        c[6] = str(int(rng.integers(0, 60)))
        c[8] = f"10.0.0.{int(rng.integers(1, 9))}"
        c[9] = f"192.168.1.{int(rng.integers(1, 7))}"
        # port mix hits every adjust_port case incl. str(float) edges
        c[10] = ["80", "443", "39999", "0", "1e15"][i % 5]
        c[11] = ["52100", "1024", "45000", "7777", "0.0001"][(i // 5) % 5]
        c[16] = str(int(rng.integers(1, 200)))
        c[17] = str(int(rng.integers(40, 9000)))
        lines.append(",".join(c))
    lines.append(",".join(["##"] * 27))          # garbage row (NaN numerics)
    lines.append("only,three,fields")            # wrong width -> dropped
    body = "\r\n".join(lines[:30]) + "\r\n" + "\n".join(lines[30:]) + "\n"
    with open(os.path.join(inp, "flow.csv"), "w", newline="") as f:
        f.write(body)

    # -- dns day: 40 rows + edge-case names -----------------------------
    qnames = [
        "www.google.com", "a.b.co.uk", "5.4.3.2.in-addr.arpa", "intel",
        "www.intel.com", "dga-x7f3k9q2.evil.biz", "deep.sub.example.org",
        "justtld", "two.parts", "trailing.dot.net.", "a..b.example.com",
    ]
    rows = []
    for i in range(40):
        rows.append(",".join([
            "frame", str(1454000000 + int(rng.integers(0, 86400))),
            str(int(rng.integers(40, 1500))),
            f"172.16.0.{int(rng.integers(1, 9))}",
            qnames[i % len(qnames)],
            "1", str(int(rng.integers(1, 17))), str(int(rng.integers(0, 4))),
        ]))
    with open(os.path.join(inp, "dns.csv"), "w", newline="") as f:
        f.write("\n".join(rows) + "\n")

    with open(os.path.join(inp, "top1m.csv"), "w") as f:
        f.write("1,google.com\n2,example.org\n3,intel.com\n")


def pinned_model(num_docs: int, vocab_size: int, seed: int):
    """Deterministic pseudo-random LDA posterior standing in for a
    trained model (training floats vary across backends)."""
    rng = np.random.default_rng(seed)
    gamma = rng.gamma(2.0, 1.0, (num_docs, K)) + 0.01
    beta = rng.dirichlet(np.ones(vocab_size) * 0.5, size=K)
    return gamma, np.log(np.maximum(beta, 1e-300))


def load_flow_feats():
    return featurize_flow_file(os.path.join(HERE, "inputs", "flow.csv"))


def load_dns_feats():
    from oni_ml_tpu.features import load_top_domains

    top = load_top_domains(os.path.join(HERE, "inputs", "top1m.csv"))
    return featurize_dns_sources(
        [os.path.join(HERE, "inputs", "dns.csv")], top_domains=top
    )


def generate(sub: str) -> None:
    """One pin recipe for both dsources: featurize -> corpus files ->
    pinned model -> (text-roundtripped) result CSVs -> scored output."""
    feats, seed, fallback, tol, score = {
        "flow": (load_flow_feats(), 77, FLOW_FALLBACK, FLOW_TOL, score_flow),
        "dns": (load_dns_feats(), 99, DNS_FALLBACK, DNS_TOL, score_dns),
    }[sub]
    exp = os.path.join(HERE, "expected", sub)
    formats.write_word_counts(
        os.path.join(exp, "word_counts.dat"), feats.word_counts()
    )
    corpus = Corpus.from_word_counts_file(os.path.join(exp, "word_counts.dat"))
    corpus.save(exp)                       # words.dat, doc.dat, model.dat

    gamma, log_beta = pinned_model(corpus.num_docs, corpus.num_terms, seed)
    formats.write_gamma(os.path.join(exp, "final.gamma"), gamma)
    formats.write_beta(os.path.join(exp, "final.beta"), log_beta)
    # downstream files derive from the COMMITTED (text-roundtripped)
    # model, exactly as the test recomputes them
    gamma = formats.read_gamma(os.path.join(exp, "final.gamma"))
    log_beta = formats.read_beta(os.path.join(exp, "final.beta"))
    norm = gamma / gamma.sum(-1, keepdims=True)
    formats.write_doc_results(
        os.path.join(exp, "doc_results.csv"), corpus.doc_names, norm
    )
    formats.write_word_results(
        os.path.join(exp, "word_results.csv"), corpus.vocab, log_beta
    )
    model = ScoringModel.from_files(
        os.path.join(exp, "doc_results.csv"),
        os.path.join(exp, "word_results.csv"),
        fallback=fallback,
    )
    rows, _ = score(feats, model, threshold=tol)
    with open(os.path.join(exp, f"{sub}_results.csv"), "w") as f:
        f.write("\n".join(rows) + ("\n" if rows else ""))


if __name__ == "__main__":
    write_inputs()
    generate("flow")
    generate("dns")
    print("golden fixture regenerated under", HERE)
