"""The reference's side of the FILE contract of `lda est` (oni-lda-c as the
reference product ran it, ml_ops.sh:80): what goes in, what comes out, and
in which format.  Plain Python and numpy; imports nothing of the program.

In:   model.dat     one line a document, `N w1:c1 ... wN:cN`, N the number
                    of distinct words (lda_pre.py:84-94); the vocabulary is
                    the largest word id + 1, as lda-c's read_data takes it
      settings.txt  lda-c's five lines (`var max iter`, `var convergence`,
                    `em max iter`, `em convergence`, `alpha estimate|fixed`)
Out (README.md:116-121; how lda_post.py:70 reads them back):
      final.beta      K lines of V values, log p(word | topic)
      final.gamma     D lines of K values, in model.dat's document order
      final.other     `num_topics K`, `num_terms V`, `alpha A`
      likelihood.dat  one line an EM iteration: likelihood, a tab, |dll/ll|
    Every value of the two matrices and alpha is written `%5.10f`, a
    likelihood `%10.10f`, a convergence `%5.5e`: ten digits after the point
    (five in the exponent form).  Ten printed digits bound what the files
    can resolve: 1e-10 absolute, on gamma near 1-300 and log beta near -9.

The readers check shape and format before they return arrays: the line
count, the values a line, and the digits after the point on every value of
the first, middle and last line of each file.  A missing file or another
shape is a `BadFile`; a value in another format is a line in `problems`,
and the arrays still come back to be compared.  `read_fit` gathers both:
an empty list is what the job's `files` number reads 0 for.  The writers of
the OUTPUT files are for the reference standing in the program's place
(tests and the control script).
"""

from __future__ import annotations

import os
import re
from types import SimpleNamespace

import numpy as np

FIXED = re.compile(r"-?\d+\.\d{10}")            # %5.10f, %10.10f
EXPONENT = re.compile(r"-?\d\.\d{5}e[+-]\d{2,3}")   # %5.5e
FILES = ("final.beta", "final.gamma", "final.other", "likelihood.dat")


class BadFile(ValueError):
    """A file of the contract is missing, short, ragged or in another
    format."""


# -- what goes in --------------------------------------------------------

def write_model_dat(path: str, doc_ptr, word_idx, counts) -> int:
    """CSR arrays -> model.dat, in bulk: the tokens `w:c` come from two
    tables of strings indexed by arrays, one join a document.  Returns the
    bytes written."""
    word_idx = np.asarray(word_idx)
    counts = np.asarray(counts).astype(np.int64)
    words = np.array([str(w) for w in range(int(word_idx.max()) + 1)]
                     if len(word_idx) else [], dtype=object)
    tails = np.array([f":{c}" for c in range(int(counts.max()) + 1)]
                     if len(counts) else [], dtype=object)
    tokens = (words[word_idx] + tails[counts]).tolist()
    ptr = np.asarray(doc_ptr).tolist()
    lines = [" ".join([str(hi - lo)] + tokens[lo:hi])
             for lo, hi in zip(ptr[:-1], ptr[1:])]
    blob = ("\n".join(lines) + "\n").encode("ascii")
    with open(path, "wb") as f:
        f.write(blob)
    return len(blob)


def settings_lines(lda: dict) -> list:
    """settings.txt's five lines from a configuration's `lda` group."""
    return [
        f"var max iter {int(lda['var_max_iters'])}",
        f"var convergence {float(lda['var_tol']):g}",
        f"em max iter {int(lda['em_max_iters'])}",
        f"em convergence {float(lda['em_tol']):g}",
        "alpha estimate" if lda["estimate_alpha"] else "alpha fixed",
    ]


def write_settings(path: str, lda: dict) -> None:
    with open(path, "w") as f:
        f.write("\n".join(settings_lines(lda)) + "\n")


def est_argv(lda: dict, settings: str, model_dat: str, out_dir: str,
             nproc: int = 20) -> list:
    """The reference's argument vector (ml_ops.sh:80):
    est <alpha> <topics> <settings> <nproc> <model.dat> random <dir>."""
    return ["est", f"{float(lda['alpha_init']):g}",
            str(int(lda["num_topics"])), settings, str(nproc), model_dat,
            "random", out_dir]


# -- what comes out ------------------------------------------------------

def _lines(path: str) -> list:
    """The file's lines; it must exist and end in a newline."""
    name = os.path.basename(path)
    if not os.path.isfile(path):
        raise BadFile(f"{name}: missing")
    with open(path) as f:
        text = f.read()
    if not text.endswith("\n"):
        raise BadFile(f"{name}: no newline at the end (empty or cut short)")
    return text[:-1].split("\n")


def _format_problems(name: str, lines: list, pattern_of) -> list:
    """Values of the first, middle and last line that are not in their
    column's format."""
    return [f"{name}: line {i + 1} value {j + 1} {value!r} is not in the "
            "contract's format"
            for i in sorted({0, len(lines) // 2, len(lines) - 1})
            for j, value in enumerate(lines[i].split())
            if not pattern_of(j).fullmatch(value)]


def read_matrix(path: str, rows: int, cols: int, problems: list
                ) -> np.ndarray:
    """final.beta / final.gamma -> float64 [rows, cols].  Another shape is
    a `BadFile`; a value in another format goes to `problems`."""
    name = os.path.basename(path)
    lines = _lines(path)
    if len(lines) != rows:
        raise BadFile(f"{name}: {len(lines)} lines, expected {rows}")
    problems += _format_problems(name, lines, lambda j: FIXED)
    widths = {len(lines[i].split())
              for i in (0, len(lines) // 2, len(lines) - 1)}
    flat = np.fromstring(" ".join(lines), dtype=np.float64, sep=" ")
    if widths != {cols} or flat.size != rows * cols:
        raise BadFile(f"{name}: {flat.size} values in {rows} lines "
                      f"({sorted(widths)} a line), expected {cols} a line")
    return flat.reshape(rows, cols)


def read_other(path: str, problems: list) -> dict:
    lines = [line.split() for line in _lines(path)]
    if [line[:1] for line in lines] != [["num_topics"], ["num_terms"],
                                        ["alpha"]] or any(
            len(line) != 2 for line in lines):
        raise BadFile(f"final.other: {len(lines)} lines {lines[:4]}, "
                      "expected num_topics, num_terms, alpha")
    topics, terms, alpha = (line[1] for line in lines)
    if not (topics.isdigit() and terms.isdigit()):
        raise BadFile(f"final.other: {topics!r}, {terms!r} are not counts")
    if not FIXED.fullmatch(alpha):
        problems.append(f"final.other: alpha {alpha!r} is not in the "
                        "contract's format")
    return {"num_topics": int(topics), "num_terms": int(terms),
            "alpha": float(alpha)}


def read_likelihood(path: str, problems: list) -> np.ndarray:
    """likelihood.dat -> float64 [EM iterations, 2]: likelihood, |dll/ll|."""
    lines = _lines(path)
    problems += _format_problems(
        "likelihood.dat", lines, lambda j: FIXED if j == 0 else EXPONENT)
    rows = [line.split("\t") for line in lines]
    try:
        if any(len(r) != 2 for r in rows):
            raise ValueError("a line is not `likelihood<tab>convergence`")
        return np.array(rows, dtype=np.float64)
    except ValueError as e:
        raise BadFile(f"likelihood.dat: {e}") from None


def conv_problems(ll: np.ndarray, em_tol: float, em_max_iters: int) -> list:
    """likelihood.dat's second column against its first: it is |dll/ll| of
    the first in float64 (1 on the first line), to the five digits it is
    printed with; and the fit stopped where it first fell under `em_tol`
    (or at `em_max_iters`), not before and not after."""
    conv = ll[:, 1]
    want = np.r_[1.0, np.abs((ll[:-1, 0] - ll[1:, 0]) / ll[:-1, 0])]
    out = []
    if not np.allclose(conv, want, rtol=2e-5, atol=1e-12):
        out.append("likelihood.dat: the second column is not |dll/ll| of "
                   "the first")
    under = conv[1:] < em_tol
    stopped = len(ll) == em_max_iters or (under.size and under[-1])
    if under[:-1].any() or not stopped or len(ll) > em_max_iters:
        out.append("likelihood.dat: the second column does not end where "
                   f"it first falls under {em_tol:g}")
    return out


def read_fit(out_dir: str, num_docs: int, num_topics: int, num_terms: int
             ) -> tuple:
    """The four files of one fit -> (fit, problems).  `fit` carries what
    harness/fit_check.compare reads (log_beta, gamma, alpha, likelihoods,
    em_iters) plus `conv` (likelihood.dat's second column) and `other`;
    it is None where a file is missing or has another shape.  `problems`
    lists everything that breaks the contract: empty means the four files
    are there, K x V, D x K, 3 lines, one line an EM iteration, every
    checked value in its format and final.other naming this corpus."""
    problems = []

    def attempt(read, *args):
        try:
            return read(*args, problems)
        except BadFile as e:
            problems.append(str(e))
            return None

    other = attempt(read_other, os.path.join(out_dir, "final.other"))
    if other and (other["num_topics"], other["num_terms"]) != (
            num_topics, num_terms):
        problems.append(f"final.other says {other}, the corpus has "
                        f"num_topics {num_topics} num_terms {num_terms}")
    beta = attempt(read_matrix, os.path.join(out_dir, "final.beta"),
                   num_topics, num_terms)
    gamma = attempt(read_matrix, os.path.join(out_dir, "final.gamma"),
                    num_docs, num_topics)
    ll = attempt(read_likelihood, os.path.join(out_dir, "likelihood.dat"))
    if other is None or beta is None or gamma is None or ll is None:
        return None, problems
    return SimpleNamespace(
        log_beta=beta, gamma=gamma, alpha=other["alpha"],
        likelihoods=ll[:, 0].tolist(), conv=ll[:, 1].tolist(),
        em_iters=len(ll), other=other, ll=ll), problems


# -- the reference in the program's place (tests, the control) -----------

def read_model_dat(path: str) -> tuple:
    """model.dat -> CSR (doc_ptr int64, word_idx int32, counts int32)."""
    with open(path) as f:
        flat = np.fromstring(f.read().replace(":", " "), dtype=np.int64,
                             sep=" ")
    sizes, at = [], 0
    while at < len(flat):
        sizes.append(int(flat[at]))
        at += 1 + 2 * sizes[-1]
    if at != len(flat):
        raise BadFile("model.dat: the last document is cut short")
    ptr = np.r_[0, np.cumsum(sizes)].astype(np.int64)
    pairs = np.delete(flat, ptr[:-1] * 2 + np.arange(len(sizes)))
    return (ptr, pairs[0::2].astype(np.int32), pairs[1::2].astype(np.int32))


def read_settings(path: str) -> dict:
    """settings.txt -> the keys of a configuration's `lda` group."""
    out = {}
    with open(path) as f:
        for line in f:
            words = line.split()
            if words[:3] == ["var", "max", "iter"]:
                out["var_max_iters"] = int(words[3])
            elif words[:2] == ["var", "convergence"]:
                out["var_tol"] = float(words[2])
            elif words[:3] == ["em", "max", "iter"]:
                out["em_max_iters"] = int(words[3])
            elif words[:2] == ["em", "convergence"]:
                out["em_tol"] = float(words[2])
            elif words[:1] == ["alpha"]:
                out["estimate_alpha"] = words[1] == "estimate"
    return out


def write_fit(out_dir: str, fit, num_terms: int) -> None:
    """A fit's answers as the four files, in the contract's formats."""
    np.savetxt(os.path.join(out_dir, "final.beta"),
               np.asarray(fit.log_beta, np.float64), fmt="%5.10f")
    np.savetxt(os.path.join(out_dir, "final.gamma"),
               np.asarray(fit.gamma, np.float64), fmt="%5.10f")
    with open(os.path.join(out_dir, "final.other"), "w") as f:
        f.write(f"num_topics {len(fit.log_beta)}\nnum_terms {num_terms}\n"
                f"alpha {fit.alpha:5.10f}\n")
    with open(os.path.join(out_dir, "likelihood.dat"), "w") as f:
        prev = None
        for ll in fit.likelihoods:
            conv = 1.0 if prev is None else abs((prev - ll) / prev)
            f.write(f"{ll:10.10f}\t{conv:5.5e}\n")
            prev = ll
