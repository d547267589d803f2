"""Frozen golden-day byte contract.

Recomputes every stage-boundary file from the committed inputs in
tests/golden/inputs/ and compares BYTES against the committed expected
files.  SURVEY.md §1: the reference's layer interfaces are files with
fixed formats — this is the pinned artifact that makes any contract
drift (featurization, first-seen id assignment, result formatting,
scoring emit) fail loudly instead of shipping silently.

To intentionally re-pin after a deliberate contract change, run
tests/golden/generate.py and review the diff.
"""

import os
import sys

import numpy as np
import pytest

from oni_ml_tpu.io import Corpus, formats
from oni_ml_tpu.scoring import ScoringModel, score_dns, score_flow

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")
INP = os.path.join(GOLDEN, "inputs")
sys.path.insert(0, GOLDEN)
# Scoring knobs and featurize recipes come from the generator itself so
# a re-pin with changed constants cannot desync generator and test.
from generate import (  # noqa: E402
    DNS_FALLBACK,
    DNS_TOL,
    FLOW_FALLBACK,
    FLOW_TOL,
    load_dns_feats,
    load_flow_feats,
)


def _read(p: str) -> bytes:
    with open(p, "rb") as f:
        return f.read()


def _expect(sub: str, name: str) -> bytes:
    return _read(os.path.join(GOLDEN, "expected", sub, name))


def _assert_file_matches(tmp_path, sub, name, writer) -> None:
    out = str(tmp_path / name)
    writer(out)
    assert _read(out) == _expect(sub, name), (
        f"{sub}/{name} drifted from the golden contract "
        "(tests/golden/generate.py re-pins after deliberate changes)"
    )


@pytest.fixture(scope="module")
def flow_feats():
    return load_flow_feats()


@pytest.fixture(scope="module")
def dns_feats():
    return load_dns_feats()


@pytest.mark.parametrize("sub", ["flow", "dns"])
def test_corpus_files_pinned(tmp_path, sub, flow_feats, dns_feats):
    feats = flow_feats if sub == "flow" else dns_feats
    _assert_file_matches(
        tmp_path, sub, "word_counts.dat",
        lambda p: formats.write_word_counts(p, feats.word_counts()),
    )
    corpus = Corpus.from_word_counts_file(
        os.path.join(GOLDEN, "expected", sub, "word_counts.dat")
    )
    corpus.save(str(tmp_path))
    for name in ("words.dat", "doc.dat", "model.dat"):
        assert _read(str(tmp_path / name)) == _expect(sub, name), name


@pytest.mark.parametrize("sub", ["flow", "dns"])
def test_result_formatting_pinned(tmp_path, sub):
    exp = os.path.join(GOLDEN, "expected", sub)
    corpus = Corpus.from_word_counts_file(
        os.path.join(exp, "word_counts.dat")
    )
    gamma = formats.read_gamma(os.path.join(exp, "final.gamma"))
    log_beta = formats.read_beta(os.path.join(exp, "final.beta"))
    norm = gamma / gamma.sum(-1, keepdims=True)
    _assert_file_matches(
        tmp_path, sub, "doc_results.csv",
        lambda p: formats.write_doc_results(p, corpus.doc_names, norm),
    )
    _assert_file_matches(
        tmp_path, sub, "word_results.csv",
        lambda p: formats.write_word_results(p, corpus.vocab, log_beta),
    )
    # beta/gamma writers roundtrip to identical bytes as well
    _assert_file_matches(
        tmp_path, sub, "final.gamma",
        lambda p: formats.write_gamma(p, gamma),
    )
    _assert_file_matches(
        tmp_path, sub, "final.beta",
        lambda p: formats.write_beta(p, log_beta),
    )


def test_flow_scoring_pinned(tmp_path, flow_feats):
    exp = os.path.join(GOLDEN, "expected", "flow")
    model = ScoringModel.from_files(
        os.path.join(exp, "doc_results.csv"),
        os.path.join(exp, "word_results.csv"),
        fallback=FLOW_FALLBACK,
    )
    rows, scores = score_flow(flow_feats, model, threshold=FLOW_TOL)
    got = ("\n".join(rows) + ("\n" if rows else "")).encode()
    assert got == _expect("flow", "flow_results.csv")
    # non-trivial fixture: keeps some events, drops others, ascending
    assert 0 < len(rows) < flow_feats.num_raw_events
    assert np.all(np.diff(scores) >= 0)


def test_dns_scoring_pinned(tmp_path, dns_feats):
    exp = os.path.join(GOLDEN, "expected", "dns")
    model = ScoringModel.from_files(
        os.path.join(exp, "doc_results.csv"),
        os.path.join(exp, "word_results.csv"),
        fallback=DNS_FALLBACK,
    )
    rows, scores = score_dns(dns_feats, model, threshold=DNS_TOL)
    got = ("\n".join(rows) + ("\n" if rows else "")).encode()
    assert got == _expect("dns", "dns_results.csv")
    assert 0 < len(rows) < dns_feats.num_raw_events
    assert np.all(np.diff(scores) >= 0)
