"""The one traffic generator: a mix's parameters and a seed -> requests.

A mix is a JSON file ``bench/traffic/<mix>.json``.  A cell's own settings
(its offered rate) sit in ``bench/traffic/<mix>.<config>.json`` and are
merged over the mix.  The generator knows three things: Poisson arrivals at
a fixed rate, a pool of documents each asked a fixed number of times, and
clipped log-normal lengths.

Every seed gets the same work in another order.  Lengths and
inter-arrival gaps are the distribution's quantiles at ``(i + 0.5) / n``
(a stratified sample), and each request's (document, question, output)
sizes are paired by a fixed permutation; the seed draws the order in which
these requests fall due, the order of the gaps between them, and every
token id (and, in the harness, the weights).  So no seed changes how much
work a window holds, and no single arrival order is the benchmark's.

Before the window, a lead-in of ``arrival.lead_s`` seconds runs the same
Poisson stream on a fixed subset of the same requests (the seed draws
only its order), so that the window opens on an engine already under
load; the lead-in's requests fall due at negative times and are not
attempted ones.  Where each document is asked once
(``asks_per_document`` 1), the lead-in's requests get documents of their
own, of the same lengths, so that every document of the run is asked once.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"

# the fixed pairing of sizes (not the seed: every seed pairs them alike)
_PAIRING_SEED = 20250318


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load(mix: str, config: str, directory: Path = TRAFFIC_DIR) -> dict:
    """The mix's parameters with the cell's own file merged over them."""
    spec = json.loads((directory / f"{mix}.json").read_text())
    cell = directory / f"{mix}.{config}.json"
    if cell.exists():
        spec = _merge(spec, json.loads(cell.read_text()))
    return spec


def seed_rng(seed: int) -> np.random.Generator:
    """A numpy generator for any whole-number seed (negative or past 64 bits
    included)."""
    return np.random.default_rng(int(seed) % (1 << 64))


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a clipped log-normal, as whole numbers,
    ascending: the quantiles at ``(i + 0.5) / n``."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` stratified inter-arrival gaps of a Poisson process at ``rate``,
    scaled so that they sum to exactly ``n / rate``."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (n / rate) / g.sum()


@dataclasses.dataclass
class Req:
    """One request as the harness submits it."""

    idx: int  # position in arrival order in the window; negative in the lead-in
    due_s: float  # when it falls due, from the start of the window (< 0: lead-in)
    doc: int  # index into Traffic.docs
    question: List[int]
    max_new_tokens: int


@dataclasses.dataclass
class Traffic:
    docs: List[List[int]]  # the document pool (token ids), the lead-in's own last
    requests: List[Req]  # in arrival order, the lead-in's first
    fill_prompts: List[List[int]]  # one per document, for the set-up fill
    expected_reuses: int
    rate_per_s: float


def sizes(spec: dict, seconds: float, rate: Optional[float] = None):
    """The seed-independent sizes: document lengths, and per request
    (document, question length, output length), paired by a fixed
    permutation."""
    rate = float(rate if rate is not None else spec["arrival"]["rate_per_s"])
    asks = int(spec["documents"]["asks_per_document"])
    n_docs = max(1, int(round(rate * seconds / asks)))
    n = n_docs * asks
    doc_lens = quantiles(spec["documents"]["length"], n_docs)
    q_lens = quantiles(spec["question"]["length"], n)
    out_lens = quantiles(spec["output"]["length"], n)
    fixed = np.random.default_rng(_PAIRING_SEED)
    docs_of = np.repeat(np.arange(n_docs), asks)
    q_lens = q_lens[fixed.permutation(n)]
    out_lens = out_lens[fixed.permutation(n)]
    return rate, doc_lens, list(zip(docs_of.tolist(), q_lens.tolist(), out_lens.tolist()))


def generate(
    spec: dict, seed: int, seconds: float, vocab: int, rate: Optional[float] = None
) -> Traffic:
    """The requests of one run: ``rate * seconds`` of them (rounded to whole
    documents), falling due over ``seconds`` in an order drawn from the
    seed, after ``rate * lead_s`` lead-in requests, a fixed subset of the
    same set."""
    if spec["arrival"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['arrival']['process']!r}")
    rate, doc_lens, reqs = sizes(spec, seconds, rate)
    n = len(reqs)
    n_lead = int(round(rate * float(spec["arrival"].get("lead_s", 0.0))))
    rng = seed_rng(seed)
    order = rng.permutation(n)
    due = np.cumsum(exponential_gaps(rate, n)[rng.permutation(n)])
    subset = np.resize(np.random.default_rng(_PAIRING_SEED + 2).permutation(n), n_lead)
    lead = subset[rng.permutation(n_lead)]
    lead_gaps = exponential_gaps(rate, n_lead)[rng.permutation(n_lead)] if n_lead else []
    lead_due = -np.cumsum(lead_gaps[::-1])[::-1] if n_lead else []
    docs = [rng.integers(0, vocab, int(k)).tolist() for k in doc_lens]
    out = []
    for i, (j, t) in enumerate(zip(list(lead) + list(order), list(lead_due) + list(due))):
        doc, q_len, n_out = reqs[j]
        out.append(Req(
            idx=i - n_lead, due_s=float(t), doc=int(doc),
            question=rng.integers(0, vocab, int(q_len)).tolist(),
            max_new_tokens=int(n_out),
        ))
    fill_len = int(spec["fill_prompt_tokens"])
    fills = [rng.integers(0, vocab, fill_len).tolist() for _ in docs]
    if int(spec["documents"]["asks_per_document"]) == 1:
        # every document asked once: the lead-in's copies get documents of
        # their own, of the same lengths, so that none is seen twice
        for r in out[:n_lead]:
            docs.append(rng.integers(0, vocab, len(docs[r.doc])).tolist())
            r.doc = len(docs) - 1
    return Traffic(
        docs=docs, requests=out, fill_prompts=fills,
        expected_reuses=int(spec["documents"]["asks_per_document"]),
        rate_per_s=rate,
    )
